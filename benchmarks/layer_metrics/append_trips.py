"""Chunk writes of the queue append a device step: the sum of
``append_chunks`` over a check's ``step`` records (the trips of
``append_novel``'s loop, counted in the packed stats vector) over the sum of
their ``dsteps``, median over the window's checks.  1 where a step's novel
rows fit one batch-sized chunk; it rises where ``cand`` is raised without
``batch`` - the append then pays for the window's width again.  Exact.
Nothing where a step record lacks the key: a program that does not count
its chunks (every tree before PR 53)."""

UNIT = "count"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        steps = [r for r in c.get("records", []) if r.get("kind") == "step"]
        if any("append_chunks" not in r or "dsteps" not in r for r in steps):
            return None
        dsteps = sum(r["dsteps"] for r in steps)
        if dsteps:
            vals.append(sum(r["append_chunks"] for r in steps) / dsteps)
    if not vals:
        return None
    return float(ctx["median"](vals))

"""Share of the queue's rows that the search wrote: the ``unique`` of the
check's LAST ``step`` record (every unique state is appended to the queue
once, and a presized queue is never compacted) over the workload's
``spawn.queue_capacity``.  The queue is ``queue_capacity`` rows of
``width`` words whatever the search does; this says how much of that
memory the traffic FILLS - a reserved pool reads low however large
``peak_hbm`` is.  Median over the window's checks; exact.  Nothing where
the workload leaves the queue to ``spawn_tpu``'s defaults (it grows), or
where the program records no steps."""

UNIT = "%"
LAYER = "device step program"
MOVES = "peak_hbm"
SOURCE = "program_counter"


def read(ctx):
    rows = (ctx["workload"].get("spawn") or {}).get("queue_capacity")
    if not rows:
        return None
    vals = []
    for c in ctx["checks"]:
        steps = [r for r in c.get("records", [])
                 if r["kind"] == "step" and "unique" in r]
        if steps:
            vals.append(100.0 * steps[-1]["unique"] / rows)
    if not vals:
        return None
    return float(ctx["median"](vals))

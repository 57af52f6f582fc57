"""Seconds a WINDOW check spent acquiring its engines: the sum of the
``duration`` of the check's flight-recorder ``compile`` events (each a
program served from the persistent cache, per rung), median over the
window's checks.  Only a check on a model object of its own (the ``cold``
loop) has any: where no window check recorded one there is nothing to read
(``engine_acquire_s`` reads the same events of the warm-up check)."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    per_check = [
        sum(float(r.get("duration", 0.0)) for r in c.get("records", [])
            if r["kind"] == "compile")
        for c in ctx["checks"]
        if any(r["kind"] == "compile" for r in c.get("records", []))
    ]
    if not per_check:
        return None
    return float(ctx["median"](per_check))

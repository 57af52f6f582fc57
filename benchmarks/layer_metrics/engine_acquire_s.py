"""Seconds the warm-up check spent acquiring engines: the sum of the
``duration`` of its flight-recorder ``compile`` events (a fresh compile or
a persistent-cache retrieval, per rung)."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    events = [r for r in ctx["warmup_records"] if r["kind"] == "compile"]
    if not events:
        return None
    return float(sum(float(r.get("duration", 0.0)) for r in events))

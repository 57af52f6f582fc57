"""Host syncs per check: the flight recorder's ``step`` records (one per
blocking device call), median over the window's checks.  Exact."""

UNIT = "count"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    counts = [
        sum(1 for r in c.get("records", []) if r["kind"] == "step")
        for c in ctx["checks"]
    ]
    if not counts or not any(counts):
        return None
    return float(ctx["median"](counts))

"""Share of the popped lanes that held a state: the pinned unique count
(every state is popped once) over the sum of ``dsteps * batch`` of the
check's ``step`` records.  Median over the window's checks; exact."""

UNIT = "%"
LAYER = "device step program"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        steps = [r for r in c.get("records", [])
                 if r["kind"] == "step" and "dsteps" in r]
        lanes = sum(r["dsteps"] * r["batch"] for r in steps)
        if lanes:
            vals.append(100.0 * ctx["pins"]["unique"] / lanes)
    if not vals:
        return None
    return float(ctx["median"](vals))

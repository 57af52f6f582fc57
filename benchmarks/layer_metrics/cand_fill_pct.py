"""Share of the candidate lanes that held a state: the pinned generated
count (every generated state is one lane of one step's ``[batch,
max_actions]`` successor block) over the sum of ``dsteps * batch`` of the
check's ``step`` records times the twin's ``max_actions``.  Every stage
from expand to the insert's compaction runs at all the lanes; this is how
many of them were work.  Median over the window's checks; exact."""

UNIT = "%"
LAYER = "device step program"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        steps = [r for r in c.get("records", [])
                 if r["kind"] == "step" and "dsteps" in r]
        lanes = sum(r["dsteps"] * r["batch"] for r in steps) * ctx["row"]["max_actions"]
        if lanes:
            vals.append(100.0 * ctx["pins"]["generated"] / lanes)
    if not vals:
        return None  # a program that does not count its steps
    return float(ctx["median"](vals))

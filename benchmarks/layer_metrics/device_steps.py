"""Steps of the device program per check (one step pops one batch): the
sum of ``dsteps`` over the check's ``step`` records — the ``while_loop``
trip counts the program returns in its packed stats vector.  Median over
the window's checks.  Exact: it repeats from check to check and run to run."""

UNIT = "count"
LAYER = "device step program"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    counts = []
    for c in ctx["checks"]:
        steps = [r["dsteps"] for r in c.get("records", [])
                 if r["kind"] == "step" and "dsteps" in r]
        if steps:
            counts.append(sum(steps))
    if not counts:
        return None  # a program that does not count its steps
    return float(ctx["median"](counts))

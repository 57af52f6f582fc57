"""Seconds of a WINDOW check the actor compiler took to make the check's
own device twin: the program's ``twin_compile`` span in the check's
flight-recorder ring, median over the window's checks.  Only a check on a
model object of its own (the ``cold`` loop) records one: where no window
check did there is nothing to read (``twin_compile_s`` reads the same span
of the warm-up check, which lies in ``setup_s``)."""

UNIT = "s"
LAYER = "compiled actor twin"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    per_check = [
        float(r["dur"]) for c in ctx["checks"] for r in c.get("records", [])
        if r["kind"] == "span" and r.get("name") == "twin_compile"
    ]
    if not per_check:
        return None
    return float(ctx["median"](per_check))

"""Seconds per check in ``discoveries()`` + ``discovery(name)``: the table
pulled to the host, the parent chain walked and replayed on the host
object model.  The harness's own span, median over the window's checks."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "host_clock"


def read(ctx):
    vals = [
        t1 - t0
        for c in ctx["checks"]
        for name, t0, t1 in c["spans"] if name == "reconstruct"
    ]
    if not vals:
        return None
    return float(ctx["median"](vals))

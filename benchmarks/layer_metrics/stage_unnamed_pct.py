"""Share of the device's busy self time, in the profiled check, whose
operations carry NO ``sr.<stage>`` scope (operations XLA made itself, and
whatever a later change forgets to wrap): what the stage metrics cannot
see.  100 where the executable carries no names at all.
srbench/xstages.py prints those operations with their source."""

UNIT = "%"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    out = xstages.trace_of(ctx, __file__)
    if not out:
        return None
    return float(out["unnamed_pct"])

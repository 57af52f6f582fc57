"""Seconds of device SELF time, in the profiled check, of the operations
under the step program's ``sr.props`` scope: the property kernels, ``eval_props`` and ``flush_terminal``.
From the trace's event metadata (srbench/xstages.py); the stages and
``stage_unnamed_pct``'s share add up to the device's busy time."""

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    return xstages.stage_seconds(ctx, __file__, "sr.props")

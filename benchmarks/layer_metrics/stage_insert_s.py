"""Seconds of device SELF time, in the profiled check, of the operations
under the step program's ``sr.insert`` scope: ``ops/buckets.bucket_insert`` (both calls under POR): the dedup rank pipeline and the table scatter.
From the trace's event metadata (srbench/xstages.py); the stages and
``stage_unnamed_pct``'s share add up to the device's busy time."""

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    return xstages.stage_seconds(ctx, __file__, "sr.insert")

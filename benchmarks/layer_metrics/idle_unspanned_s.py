"""Idle seconds of the device, in the profiled check, that none of the
program's ``sr/*`` host spans covers: each idle gap between leaf operations
is charged to the innermost span over it (srbench/xstages.py), and this is
the rest — what the tracing still cannot explain."""

UNIT = "s"
LAYER = "device"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    out = xstages.trace_of(ctx, __file__)
    if not out or not out["span_s"]:
        return None  # a program without the host spans: nothing to read
    return float(out["idle"].get(xstages.UNSPANNED, 0.0))

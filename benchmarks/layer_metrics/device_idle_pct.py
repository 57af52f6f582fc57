"""Share of the profiled check in which no operation ran on the chip:
1 - (union of the device operations' intervals) / (the check's span), from
the profiler trace (srbench/xplane.py)."""

UNIT = "%"
LAYER = "device"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    return float(trace["idle_pct"])

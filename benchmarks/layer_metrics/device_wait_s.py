"""Seconds per check the host spent BLOCKED on device calls (the flight
recorder's ``device_secs`` stage, host clock around the blocking calls),
median over the window's checks.  Not the device's busy time."""

UNIT = "s"
LAYER = "device step program"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    vals = [
        float(c["stages"]["device_secs"])
        for c in ctx["checks"] if "device_secs" in c.get("stages", {})
    ]
    if not vals:
        return None
    return float(ctx["median"](vals))

"""Seconds per check inside growth (carry -> host -> rehash -> re-upload):
the flight recorder's ``growth_secs`` stage, median over the window's
checks.  0 in a presized cell."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    vals = [
        float(c["stages"].get("growth_secs", 0.0))
        for c in ctx["checks"] if c.get("stages")
    ]
    if not vals:
        return None
    return float(ctx["median"](vals))

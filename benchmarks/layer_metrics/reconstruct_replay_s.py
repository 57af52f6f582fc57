"""Seconds per check replaying the discovery paths on the host object model
(``Path.from_fingerprints``): the program's ``reconstruct.replay`` spans
(flight-recorder ``span`` records; ``sr/reconstruct.replay`` in the
profiler's trace), median over the window's checks."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "reconstruct.replay", marker="reconstruct")

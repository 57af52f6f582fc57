"""Seconds per check pulling the carry to the host at growth events
(``np.asarray`` of every buffer): the program's ``grow.pull`` spans
(flight-recorder ``span`` records; ``sr/grow.pull`` in the profiler's
trace), median over the window's checks.  0 in a presized cell."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "grow.pull", marker="device_call")

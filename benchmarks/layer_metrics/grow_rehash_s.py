"""Seconds per check re-hashing the visited table into its doubled size on
the host at growth events (``host_bucket_rehash``, numpy): the program's
``grow.rehash`` spans (flight-recorder ``span`` records; ``sr/grow.rehash``
in the profiler's trace), median over the window's checks.  0 in a
presized cell."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "grow.rehash", marker="device_call")

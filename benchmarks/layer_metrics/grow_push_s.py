"""Seconds per check handing the grown carry back to the device at growth
events (``jnp.asarray``; what the upload leaves in flight shows in the next
device call's wait): the program's ``grow.push`` spans (flight-recorder
``span`` records; ``sr/grow.push`` in the profiler's trace), median over
the window's checks.  0 in a presized cell."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "grow.push", marker="device_call")

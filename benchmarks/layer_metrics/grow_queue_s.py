"""Seconds per check compacting the queue on the host at growth events
(the consumed prefix dropped by a copy of the live rows, then the re-pad to
the new allocation — two host copies of the queue's rows): the program's
``grow.queue`` spans (flight-recorder ``span`` records; ``sr/grow.queue``
in the profiler's trace), median over the window's checks.  0 in a
presized cell.  With ``grow_pull_s``, ``grow_rehash_s`` and ``grow_push_s``
it adds up to ``growth_s``."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "grow.queue", marker="device_call")

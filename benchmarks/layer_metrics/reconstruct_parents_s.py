"""Seconds per check building the fingerprint -> parent dict of every
visited state (``_parents_from_table``): the program's
``reconstruct.parents`` spans (flight-recorder ``span`` records;
``sr/reconstruct.parents`` in the profiler's trace), median over the
window's checks."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "reconstruct.parents", marker="reconstruct")

"""Seconds per check pulling both arrays of the visited table to the host
(``_table_np``): the program's ``reconstruct.pull`` spans (flight-recorder
``span`` records; ``sr/reconstruct.pull`` in the profiler's trace), median
over the window's checks."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "reconstruct.pull", marker="reconstruct")

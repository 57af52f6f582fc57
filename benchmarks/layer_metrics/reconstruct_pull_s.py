"""Seconds per check bringing to the host what path reconstruction needs
there: the program's ``reconstruct.pull`` spans (flight-recorder ``span``
records; ``sr/reconstruct.pull`` in the profiler's trace), median over the
window's checks.  Since PR 47, in every cell, that is the discovered states'
parent chains, resolved on the device (hundreds of bytes:
``reconstruct_pull_bytes``); only on the HOST path, which no cell runs (a
spill store that holds the roots), is it both arrays of the visited table
(``_table_np``)."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "reconstruct.pull", marker="reconstruct")

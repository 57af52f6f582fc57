"""Seconds a WINDOW check spent lowering jaxprs to MLIR modules: the sum of
the check's ``program.lower`` spans, median over the window's checks.
0 on resident engines; nothing to read where the program does not split
the seam."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.per_check(ctx, "lower_s")

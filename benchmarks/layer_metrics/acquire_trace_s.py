"""Seconds of the warm-up check's device calls that were neither lowering
nor the backend: the SELF time of its ``dispatch`` spans (``dur`` minus the
``program.lower`` / ``program.load`` children, by ``parent_id``).  On a
fresh object that is the Python tracing of each step program, plus the
enqueues: the part of an acquisition no persistent cache saves.  Nothing
to read where the program does not split the seam."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.warmup(ctx, "trace_s")

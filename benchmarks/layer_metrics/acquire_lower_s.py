"""Seconds the warm-up check spent lowering jaxprs to MLIR modules: the sum
of its ``program.lower`` spans (JAX's ``jaxpr_to_mlir_module_duration``, one
a module).  Like the tracing before it, paid whether or not the persistent
cache holds the program.  Nothing to read where the program does not split
the seam."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.warmup(ctx, "lower_s")

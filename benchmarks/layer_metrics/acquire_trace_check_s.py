"""Seconds of a WINDOW check's device calls that were neither lowering nor
the backend: the SELF time of the check's ``dispatch`` spans (``dur`` minus
their ``program.lower`` / ``program.load`` children, by ``parent_id``),
median over the window's checks.  The Python tracing of each rung's step
program plus the enqueues: with ``acquire_lower_check_s`` and
``acquire_check_s`` it adds up to ``dispatch_s``.  Only a check on a model
object of its own (the ``cold`` loop) acquires anything: on resident
engines this reads the enqueues alone.  Nothing to read where the program
does not split the seam."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.per_check(ctx, "trace_s")

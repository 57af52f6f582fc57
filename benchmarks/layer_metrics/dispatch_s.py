"""Seconds per check the host spent inside device calls BEFORE the device had
the work: the program's ``dispatch`` spans (``sr/dispatch`` in the
profiler's trace; a child of ``device_call``, closed when the jitted call
returns), summed over a check, median over the window's checks.  On a
resident engine that is the enqueue, milliseconds; on a model object of its
own (the ``cold`` loop) the first call of each rung's step program traces,
lowers and loads it there, of which ``acquire_check_s`` sees only what JAX's
monitoring calls compilation.  Nothing to read where no check recorded one."""

UNIT = "s"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "dispatch", marker="dispatch")

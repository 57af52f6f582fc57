"""Seconds a WINDOW check spent retrieving programs from the persistent
cache: the sum of ``retrieved_s`` over the check's ``program.load`` spans
(JAX's ``cache_retrieval_time_sec``: read and deserialise), median over the
window's checks.  It lies INSIDE ``acquire_check_s``; the rest of a load is
the cache key.  Divided by ``programs_loaded_check`` it is what one
retrieval costs.  0 on resident
engines; nothing to read where the program does not split the seam."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.per_check(ctx, "retrieved_s")

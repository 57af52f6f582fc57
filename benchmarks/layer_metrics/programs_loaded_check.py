"""How many programs a WINDOW check asked the backend to compile or load:
the number of its ``program.load`` spans, median over the window's checks
(the work of an acquisition, as a count: the step program of each rung, the
init program, the small helpers).  0 on resident engines; nothing to read
where the program does not split the seam."""

UNIT = "count"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xacquire

    return xacquire.per_check(ctx, "programs")

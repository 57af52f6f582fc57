"""Seconds of device SELF time, in the profiled check, of the ``sr.expand``
operations under a compiled actor twin's ``twin.net`` scope:
slot deliver / send / canonicalise, ordered and unordered
(``parallel/actor_tensor.py``'s kernels, which hand-written twins share).
From the trace's event metadata (srbench/xtwin.py); with the other two
``twin.*`` parts and the unscoped rest (printed, no metric) it adds up to
``stage_expand_s``.  0 where the executable carries no such scope (a
hand-written twin without it, or an executable compiled before the names)."""

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xtwin

    return xtwin.part_seconds(ctx, __file__, "twin.net")

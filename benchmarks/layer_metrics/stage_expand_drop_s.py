"""Seconds of device SELF time, in the profiled check, of the ``sr.expand``
operations under a compiled actor twin's ``twin.drop`` scope: the Drop
columns of a twin built under ``lossy_network(True)`` - the second
successor block (every occupied slot consumed without a delivery) and its
canonicalising sort (``parallel/actor_compiler.py``; the scope comes first
on a Drop operation's path, so the slot kernels it calls are charged here
and not to ``twin.net``).
From the trace's event metadata (srbench/xtwin.py); with the three
``twin.*`` parts every compiled twin opens and the unscoped rest (printed,
no metric) it adds up to ``stage_expand_s``.  0 where the executable
carries no such scope (a lossless twin, or a program from before the
name).  ``xtwin.report`` prints the parts every twin has; this reader
prints the Drop part's row and its top operations beside them (stderr)."""

import sys

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


PART = "twin.drop"


def read(ctx):
    from srbench import xtwin

    secs = xtwin.part_seconds(ctx, __file__, PART)
    if secs:
        out = xtwin.expand_of(ctx, __file__)  # analysed once a trace, cached
        share = 100.0 * secs / out["expand_s"]
        print(f"xtwin:   {PART:<12} {secs:12.6f} s {share:6.2f}% of sr.expand",
              file=sys.stderr)
        for label, source, s in out["part_ops"].get(PART, []):
            print(f"xtwin:       {s:12.6f} s  {label}  [{source}]", file=sys.stderr)
    return secs

"""Seconds of device SELF time, in the profiled check, of the ``sr.props``
operations under the linearizability verdict's ``props.lin`` scope: the
history fields decoded out of every popped row's packed word (per client
thread a phase, a snapshot of the other threads' completed operations and
a returned value) and the verdict on them — the precedence graph's
closure by squaring (``parallel/history_tensor.py:closure_verdict``) or a
key and a table look-up.  Opened by the compiled actor twin's
``property_masks`` (``parallel/actor_compiler.py``) and by the hand twin
``models/paxos_tensor.py``.  From the trace's event metadata
(srbench/xprops.py); with the unscoped rest of the stage (printed, no
metric: ``value chosen``'s slot scan, the masks' stacking, the discovery
bookkeeping) it adds up to ``stage_props_s``.  Every operation of
``sr.props`` is printed (stderr, ``xprops:``).  0 where no operation
carries the scope (a twin without a history, an executable compiled
before the name, a verdict fused into a neighbour's operation); nothing
without a trace."""

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xprops

    return xprops.lin_seconds(ctx, __file__)

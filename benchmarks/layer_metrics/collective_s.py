"""Seconds of device SELF time, in the profiled check, of the operations
that cross between chips: the leaf operations whose HLO opcode is
``all-reduce``, ``all-gather``, ``all-to-all``, ``reduce-scatter`` or
``collective-permute`` (an asynchronous one's ``-start`` and ``-done``
halves each for its own time, not the span between them), averaged over the
chips as a stage is.  Whatever scope the operation carries: the ones the
compiler combines lose their ``op_name`` and lie under ``unnamed``, the rest
under the stage they serve - this is a part OF the stages, and ``xstages``
prints its split by stage.  From the pass ``srbench/xstages.py`` makes over
the trace's event metadata anyway.  Nothing without a trace."""

UNIT = "s"
LAYER = "GSPMD collectives"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    out = xstages.trace_of(ctx, __file__)
    if "collective_s" not in out:
        return None
    return float(out["collective_s"])

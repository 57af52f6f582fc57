"""The expand stage's share of the HBM roofline over one whole check: the
least time the chip could take for the bytes expand NEEDS to move
(srbench/expand_bytes.py: every unique row read once, every generated
row written once) over the device self time of the ``sr.expand``
operations in the profiled check (``stage_expand_s``).  What a twin's
``step_rows`` costs beyond moving its rows: table gathers, the slot sort,
masked lanes."""

UNIT = "%"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import expand_bytes, peaks, xtwin

    out = xtwin.expand_of(ctx, __file__)
    if not out:
        return None
    if out["expand_s"] <= 0:
        # no operation carries ``sr.expand`` (an executable that lost its
        # names): 0, as ``stage_expand_s`` reads there, not a missing metric
        return 0.0
    # the benchmark runs on the v5e alone; the CPU rehearsal (no published
    # peak, every line labelled) prints its share against the same chip's
    chip = ctx.get("peaks") or peaks.PEAKS["TPU v5 lite"]
    return expand_bytes.expand_roofline_pct(
        ctx["row"]["width"], ctx["pins"]["generated"], ctx["pins"]["unique"],
        chip["hbm_bytes_per_s"], out["expand_s"],
    )

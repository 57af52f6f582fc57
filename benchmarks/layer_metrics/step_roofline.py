"""The step program's share of the HBM roofline over one whole check: the
least time the chip could take for the bytes the check NEEDS
(srbench/necessary.py, from the row width and the pinned counts) over the
time its operations actually ran (the trace's busy time).  Integer work
only, so bandwidth is the bound that applies."""

UNIT = "%"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import necessary

    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks:
        return None
    return necessary.roofline_pct(
        ctx["row"]["width"], ctx["pins"]["generated"], ctx["pins"]["unique"],
        peaks["hbm_bytes_per_s"], trace["busy_s"],
    )

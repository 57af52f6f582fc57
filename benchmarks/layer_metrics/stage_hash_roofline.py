"""The hash stage's share of the HBM roofline over one whole check: the
least time the chip could take for the bytes the stage NEEDS to move
(srbench/hash_bytes.py: every generated row read once, its 8-byte key
written once) over the device self time of the ``sr.hash`` operations in
the profiled check (``stage_hash_s``).  What the stage costs beyond
hashing its rows: the masked candidate lanes, the pre-dedup, the candidate
broadcasts and — under ``.symmetry()`` — the canonicaliser
(``representative_rows``), whose share this then is.  Prints EVERY
operation of the stage with its seconds (stderr): the stage table's top 8
hid equal gathers twice."""

UNIT = "%"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"

STAGE = "sr.hash"


def read(ctx):
    import os
    import sys

    from srbench import hash_bytes, xplane, xstages

    out, peaks = xstages.trace_of(ctx, __file__), ctx.get("peaks")
    if not out or not peaks:
        return None  # no trace, or no published peak (the CPU rehearsal)
    hash_s = out["stages"].get(STAGE, 0.0)
    if hash_s <= 0:
        # no operation carries ``sr.hash`` (an executable that lost its
        # names): nothing to read - a share of a roofline is never 0;
        # ``stage_hash_s`` 0 and ``stage_unnamed_pct`` 100 say it loudly
        return None
    # the trace ``trace_of`` read, again, for the list the table cuts at 8
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    trace = xstages.load(xplane.find_xplane(
        os.path.join(root, ".bench_trace", ctx["cell"]["name"])))
    every = xstages.reduce_stages(trace["devices"], trace["ops"],
                                  trace["annotation"], top=sys.maxsize)
    rows = every["stage_ops"].get(STAGE, [])
    print(f"hashops: {STAGE} {hash_s:.6f} s in {len(rows)} operations, "
          f"{100.0 * hash_s / out['self_s']:.2f}% of the device's self time",
          file=sys.stderr)
    for label, source, secs in rows:
        print(f"hashops:   {secs:12.6f} s  {label}  [{source}]", file=sys.stderr)
    sys.stderr.flush()
    return hash_bytes.hash_roofline_pct(
        ctx["row"]["width"], ctx["pins"]["generated"],
        peaks["hbm_bytes_per_s"], hash_s,
    )

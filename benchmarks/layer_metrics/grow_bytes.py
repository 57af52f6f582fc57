"""Bytes a check's growth events move between host and device: the sum of
``d2h_bytes`` + ``h2d_bytes`` over its ``growth`` records (the recorder's
own byte counters around each event), median over the window's checks.
Since PR 48 the carry is transformed where it lies and an event moves the
scalars the host rewrites; on the host path (the spill tier, the mesh
engine) it is the whole carry down and up again.  0 in a check that did not
grow.  Exact.  Nothing where a growth record lacks the counters, or where
the checks were not recorded."""

UNIT = "bytes"
LAYER = "host run loop"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        if "records" not in c:
            continue
        events = [r for r in c["records"] if r.get("kind") == "growth"]
        if any("d2h_bytes" not in r or "h2d_bytes" not in r for r in events):
            return None
        vals.append(sum(r["d2h_bytes"] + r["h2d_bytes"] for r in events))
    if not vals:
        return None
    return float(ctx["median"](vals))

"""Bytes a check's path reconstruction moves to the host: the sum of the
``bytes`` attribute over its ``reconstruct.pull`` spans (flight-recorder
``span`` records), median over the window's checks that have one.  On the
device path (since PR 47) that is the discovered states' parent chains with
their lengths and ends - hundreds of bytes; where a check falls back to the
host map (a spill store that holds the roots) it is both arrays of the
visited table, 2 x 8 x capacity.  Exact.  Nothing where no span carries the
attribute."""

UNIT = "bytes"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        pulled = [r["bytes"] for r in c.get("records", [])
                  if r.get("kind") == "span" and r.get("name") == "reconstruct.pull"
                  and "bytes" in r]
        if pulled:
            vals.append(sum(pulled))
    if not vals:
        return None
    return float(ctx["median"](vals))

"""How unevenly the visited table's states lie over the chips at a check's
end: 100 x (max / mean - 1) of the ``shard_load`` of the check's ``mesh``
record (unique states a chip's bucket range holds, counted on the device),
median over the window's checks.  0 is an even split; the fullest chip sets
the insert's and the table's cost for all.  Exact for one stop.  Nothing
where no check has the record (a one-chip engine) or the table is empty."""

UNIT = "%"
LAYER = "GSPMD collectives"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    vals = []
    for c in ctx["checks"]:
        loads = [r["shard_load"] for r in c.get("records", [])
                 if r.get("kind") == "mesh" and r.get("shard_load")]
        if loads and sum(loads[-1]):
            load = loads[-1]
            vals.append(100.0 * (max(load) * len(load) / sum(load) - 1.0))
    if not vals:
        return None
    return float(ctx["median"](vals))

"""Collectives in the mesh engine's compiled step program: the
``collective_count`` of the program's ``mesh.program`` record (one a
compiled mesh step: all-gather + all-reduce + all-to-all +
collective-permute + reduce-scatter instructions of the optimised HLO) - of
the program the run ENDED on, wherever the traced run acquired it: the last
such record of the profiled check, else of the latest window check that has
one, else of the warm-up (a closed loop's engines are resident from there).
Exact: a count of instructions, the same in every run of one program.
Nothing where no record has the key: a one-chip engine, or a program
without the record."""

UNIT = "count"
LAYER = "GSPMD collectives"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    held = [(ctx.get("profiled") or {}).get("records")]
    held += [c.get("records") for c in reversed(ctx.get("checks") or [])]
    held.append(ctx.get("warmup_records"))
    for records in held:
        counts = [r["collective_count"] for r in records or []
                  if r.get("kind") == "mesh.program" and "collective_count" in r]
        if counts:
            return float(counts[-1])
    return None

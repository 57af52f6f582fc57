"""Seconds per check resolving the discovered states' parent links: the
program's ``reconstruct.parents`` spans (flight-recorder ``span`` records;
``sr/reconstruct.parents`` in the profiler's trace), median over the
window's checks.  Since PR 47, in every cell, that is ONE device call a
check (``ops/buckets.parent_chains``, on a mesh under the table's own
sharding) up to its sync (attributes ``path: device``, ``lookups``); only on
the HOST path, which no cell runs (a spill store that holds the roots), is
it the fingerprint -> parent dict of every visited state
(``_parents_from_table``)."""

UNIT = "s"
LAYER = "host trace reconstruction"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(ctx, "reconstruct.parents", marker="reconstruct")

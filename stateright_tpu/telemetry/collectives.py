"""What a compiled multi-device program moves between chips, read off the
executable: the ``mesh.program`` flight-recorder record.

The mesh engine writes no collective (``parallel/mesh.py``): GSPMD does,
from the carry's placement and the step's.  So what a step costs across
chips is decided at compile time and is invisible in the source - this
module reads it back from the optimised HLO of the executable that will
run: how many all-gathers / all-reduces / all-to-alls / collective-permutes
/ reduce-scatters the partitioner put in, the largest one with the source
operation it serves (the ``op_name`` the instruction kept: its ``sr.*``
stage scope and the jax primitive), and what the program holds a chip
(``memory_analysis()``: arguments = the carry's shards, temporaries = what
the step needs besides, code = the executable's own image, resident while
it is loaded).  Counted from text: nothing runs.
"""

from __future__ import annotations

import re

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
# one instruction: `%name = <shape> <kind>[-start](`; an asynchronous pair
# is counted at its `-start`, the `-done` carries nothing of its own
_INSTRUCTION = re.compile(
    r"=\s+(?P<shape>\S.*?)\s+(?P<kind>" + "|".join(COLLECTIVE_KINDS)
    + r")(?:-start)?\("
)
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape as printed (layouts ignored; a tuple is the
    sum of its arrays)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size
    return total


def hlo_collectives(hlo_text: str) -> dict:
    """``{"collectives": {kind: count}, "collective_count": total,
    "largest": {kind, shape, bytes, op} | None}`` of an optimised HLO
    module's text."""
    counts = dict.fromkeys(COLLECTIVE_KINDS, 0)
    largest = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.search(line)
        if m is None:
            continue
        counts[m["kind"]] += 1
        shape = re.sub(r"\{[^{}]*\}", "", m["shape"])  # drop the layouts
        nbytes = shape_bytes(shape)
        if largest is None or nbytes > largest["bytes"]:
            op = _OP_NAME.search(line)
            largest = {
                "kind": m["kind"], "shape": shape, "bytes": nbytes,
                "op": op.group(1) if op else "",
            }
    return {
        "collectives": counts,
        "collective_count": sum(counts.values()),
        "largest": largest,
    }


def program_record(compiled) -> dict:
    """The ``mesh.program`` record's fields for a compiled executable
    (``jax.stages.Compiled``): :func:`hlo_collectives` of its module plus
    ``argument_bytes`` / ``temp_bytes`` / ``code_bytes`` a chip where the
    backend reports a memory analysis."""
    out = hlo_collectives(compiled.as_text())
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - a backend without the analysis
        mem = None
    if mem is not None:
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        # the executable's own image: it stays in device memory, constants
        # folded into it included, for as long as the program is loaded
        out["code_bytes"] = int(mem.generated_code_size_in_bytes)
    return out

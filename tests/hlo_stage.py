"""What a stage of a compiled step program moves, read off the module's
text (``Compiled.as_text()``: XLA:CPU's and the TPU compiler's alike).

``stage_movers(text, "sr.append")`` lists every gather, scatter,
``dynamic-update-slice`` and collective whose ``op_name`` lies in the stage,
fused computations included, each with the ROWS it moves: a gather's result
rows, a scatter's index rows, an update slice's update rows, a collective's
result elements (a collective's result may be laid out ``[devices, 1, n]``:
its rows say nothing).  Operands carry no shapes in the text, so every
definition's shape is kept by name.
"""

from __future__ import annotations

import math
import re

from stateright_tpu.telemetry.collectives import COLLECTIVE_KINDS

_DEF = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<shape>.*?) "
    r"(?P<op>[a-z][\w\-]*)\((?P<args>[^)]*)\)"
)
_DIMS = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def _dims(shape: str) -> list:
    """The dimension lists of a shape's arrays (a tuple shape has many)."""
    return [[int(d) for d in m.split(",") if d] for m in _DIMS.findall(shape)]


def stage_movers(text: str, stage: str) -> list:
    """``(opcode, rows)`` of every data-moving operation of ``stage``."""
    shapes, out = {}, []
    lines = text.splitlines()
    for line in lines:
        m = _DEF.match(line)
        if m:
            shapes[m["name"]] = m["shape"]
    for line in lines:
        m = _DEF.match(line)
        if not m or f"/{stage}/" not in line:
            continue
        op, result = m["op"], _dims(m["shape"])
        args = [a.strip().lstrip("%") for a in m["args"].split(",") if a.strip()]
        kind = op[:-len("-start")] if op.endswith("-start") else op
        if kind == "gather":
            out.append((kind, result[0][0] if result[0] else 1))
        elif kind == "scatter":
            indices = _dims(shapes[args[len(result)]])[0]
            out.append((kind, indices[0] if indices else 1))
        elif kind == "dynamic-update-slice":
            update = _dims(shapes[args[1]])[0]
            out.append((kind, update[0] if update else 1))
        elif kind in COLLECTIVE_KINDS:
            out.append((kind, max(math.prod(d) for d in result)))
    return out

"""``sr.props`` split by the linearizability verdict's sub-scope, for the
per-layer readers.

A twin whose state holds a linearizability history (the compiled actor
twin, ``stateright_tpu/parallel/actor_compiler.py``, and the hand twin
``models/paxos_tensor.py``) opens one ``jax.named_scope`` block inside its
``property_masks``, which the step program runs under ``sr.props``:

 - ``props.lin`` — the history fields decoded out of the packed word and
   held to the verdict (``parallel/history_tensor.py``: the precedence
   graph's closure, or a key and a table look-up).

It does not start with ``sr.``, so :func:`xstages.stage_of` still files
its operations under ``sr.props``; here an operation of that stage is
charged to ``props.lin`` when its scope path holds that component and to
the ``rest`` otherwise (``value chosen``'s scan of the slots, the
stacking of the masks, the step program's own discovery bookkeeping).
The two add up to ``stage_props_s``.  EVERY operation of the stage is
kept and printed, not a top few: the stage table's top 8 hid equal
gathers twice.

A trace with no ``props.lin`` scope — a twin without a history, a program
from before the name, an executable compiled before it and served from
the compile cache since (JAX's cache key ignores scope names; XLA:CPU
keeps scope paths only in an executable it compiled itself, so a CPU
rehearsal of a ``cold`` cell reads this), or a verdict XLA fused whole
into an operation that carries a neighbour's scope — reads 0 seconds and
all of ``sr.props`` in the rest.  On the chip a 0 in a cell whose state
holds a history therefore means a stale compile cache, not a free verdict.

    python3 benchmarks/srbench/xprops.py <trace.xplane.pb | logdir> [annotation]
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional

if __package__ in (None, ""):  # run as a script: find the sibling modules
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from srbench import xplane, xstages  # noqa: E402

PROPS = "sr.props"
# the program keeps the same name in stateright_tpu/telemetry/spans.py
# (PROPS_LIN); a test holds the two together
LIN = "props.lin"
REST = "rest"

_printed: set = set()


def part_of(scope: str) -> str:
    """``props.lin`` where the scope path holds that component, else ``rest``."""
    return LIN if any(p.rstrip(":") == LIN for p in scope.split("/")) else REST


def reduce_props(devices: dict, ops: dict, window: Optional[tuple] = None) -> dict:
    """Self seconds of the ``sr.props`` operations inside ``window``
    (averaged over the chips, as :func:`xstages.reduce_stages`):
    ``props_s``, ``parts`` (``{"props.lin": s, "rest": s}``), ``lin_ops``
    (how many operations carry the scope) and ``ops`` — EVERY operation of
    the stage as ``[part, label, source, seconds]``, the largest first."""
    if not devices or not any(devices.values()):
        return {}
    w0, w1 = window or (float("-inf"), float("inf"))
    chips = len(devices)
    part_ns = {LIN: 0.0, REST: 0.0}
    op_ns: dict = {}
    for _plane, events in sorted(devices.items()):
        for op_id, s, e, self_ns in xplane.self_times(events):
            op = ops[op_id]
            if xplane.is_container(op["name"]) or e <= w0 or s >= w1:
                continue
            if xstages.stage_of(op["scope"]) != PROPS:
                continue
            part_ns[part_of(op["scope"])] += self_ns
            op_ns[op_id] = op_ns.get(op_id, 0.0) + self_ns
    rows = [
        [part_of(ops[i]["scope"]), xplane.op_label(ops[i]["name"]),
         ops[i]["source"], ns / chips / 1e9]
        for i, ns in sorted(op_ns.items(), key=lambda kv: -kv[1])
    ]
    return {
        "props_s": sum(part_ns.values()) / chips / 1e9,
        "parts": {k: v / chips / 1e9 for k, v in part_ns.items()},
        "lin_ops": sum(1 for r in rows if r[0] == LIN),
        "ops": rows,
    }


@functools.lru_cache(maxsize=2)
def analyse(path: str, annotation: str = xstages.WINDOW_ANNOTATION) -> dict:
    """:func:`reduce_props` for one trace file (parsed once a process by
    :func:`xstages.load`); the window is the
    annotation's, else the whole trace.  ``{}`` when the file holds no
    device operation."""
    trace = xstages.load(path, annotation)
    return reduce_props(trace["devices"], trace["ops"], trace["annotation"])


def report(out: dict) -> str:
    total = out["props_s"]
    rows = [f"xprops: sr.props {total:.6f} s in {len(out['ops'])} operations = "
            f"{LIN} {out['parts'][LIN]:.6f} ({out['lin_ops']} operations) + "
            f"{REST} {out['parts'][REST]:.6f}"]
    for part, label, source, secs in out["ops"]:
        rows.append(f"xprops:   {secs:12.6f} s  {part:<9}  {label}  [{source}]")
    return "\n".join(rows)


# -- for the per-layer readers ----------------------------------------------------


def props_of(ctx: dict, reader_file: str) -> dict:
    """The analysis of the traced check of ``ctx``'s cell, for a reader at
    ``<checkout>/benchmarks/layer_metrics/<metric>.py`` (the harness keeps
    the trace under ``<checkout>/.bench_trace/<cell>/`` until every reader
    has run); ``{}`` when there is no trace.  Prints the whole list
    (stderr) the first time a process asks for a file."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
    try:
        path = xplane.find_xplane(os.path.join(root, ".bench_trace", ctx["cell"]["name"]))
    except FileNotFoundError:
        return {}
    out = analyse(path)
    if out and path not in _printed:
        _printed.add(path)
        print(report(out), file=sys.stderr, flush=True)
    return out


def lin_seconds(ctx: dict, reader_file: str) -> Optional[float]:
    """The verdict's device self seconds in the profiled check; 0 where no
    operation carries the scope (as :func:`xstages.stage_seconds` and
    :func:`xtwin.part_seconds`: a 0 is louder than a metric that goes
    missing).  None without a trace."""
    out = props_of(ctx, reader_file)
    if not out:
        return None
    return float(out["parts"][LIN])


def main(argv: list) -> int:
    if not 1 <= len(argv) <= 2:
        print(__doc__.rsplit("\n\n", 1)[1], file=sys.stderr)
        return 2
    path = xplane.find_xplane(argv[0]) if os.path.isdir(argv[0]) else argv[0]
    out = analyse(path, *argv[1:])
    if not out:
        print(f"xprops: {path} holds no device operation", file=sys.stderr)
        return 1
    print(report(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

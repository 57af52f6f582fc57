"""``sr.expand`` split by a compiled actor twin's sub-scopes, and the twin's
``twin_compile`` span, for the per-layer readers.

A compiled actor twin (``stateright_tpu/parallel/actor_compiler.py``) opens
three ``jax.named_scope`` blocks inside its ``step_rows``, which the step
program runs under ``sr.expand``:

 - ``twin.table``   — the ``(actor state, envelope)`` transition look-ups
   and the decoding of their effects;
 - ``twin.net``     — slot deliver / send / canonicalise (the kernels of
   ``parallel/actor_tensor.py``, which hand-written twins share);
 - ``twin.history`` — the linearizability history fields' update.

They do not start with ``sr.``, so :func:`xstages.stage_of` still files
their operations under ``sr.expand``; here an operation of that stage is
charged to the FIRST ``twin.<part>`` component of its scope path, and what
carries none is the ``rest`` (the packed-field writes, the concatenation
of the successor block, the step program's own masks).  The parts and the
rest add up to ``stage_expand_s``.

A trace with no ``twin.`` scope at all — a hand-written twin without slot
kernels, a program from before the names, an executable compiled before
them and served from the compile cache since (JAX's cache key ignores
scope names) — reads 0 in every part and all of ``sr.expand`` in the rest.

    python3 benchmarks/srbench/xtwin.py <trace.xplane.pb | logdir> [annotation]
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional

if __package__ in (None, ""):  # run as a script: find the sibling modules
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from srbench import xplane, xstages  # noqa: E402

EXPAND = "sr.expand"
TWIN_PREFIX = "twin."
# in program order (the program keeps the same list in
# stateright_tpu/telemetry/spans.py; a test holds the two together)
PARTS = ("twin.table", "twin.net", "twin.history")
REST = "rest"
COMPILE_SPAN = "twin_compile"

_printed: set = set()


def part_of(scope: str) -> str:
    """The first ``twin.<part>`` component of a scope path, else ``rest``."""
    for piece in scope.split("/"):
        if piece.startswith(TWIN_PREFIX):
            return piece.rstrip(":")
    return REST


def reduce_expand(devices: dict, ops: dict, window: Optional[tuple] = None,
                  top: int = 6) -> dict:
    """Self seconds of the ``sr.expand`` operations inside ``window``, by
    part (averaged over the chips, as :func:`xstages.reduce_stages`):
    ``expand_s``, ``parts`` (``{part: seconds}``, every part of
    :data:`PARTS` present, plus ``rest``) and ``part_ops`` (each part's
    ``top`` operations as ``[label, source, seconds]``)."""
    if not devices or not any(devices.values()):
        return {}
    w0, w1 = window or (float("-inf"), float("inf"))
    chips = len(devices)
    part_ns = dict.fromkeys(PARTS + (REST,), 0.0)
    op_ns: dict = {}
    for _plane, events in sorted(devices.items()):
        for op_id, s, e, self_ns in xplane.self_times(events):
            op = ops[op_id]
            if xplane.is_container(op["name"]) or e <= w0 or s >= w1:
                continue
            if xstages.stage_of(op["scope"]) != EXPAND:
                continue
            part = part_of(op["scope"])
            part_ns[part] = part_ns.get(part, 0.0) + self_ns
            op_ns[op_id] = op_ns.get(op_id, 0.0) + self_ns
    part_ops: dict = {}
    for op_id, ns in sorted(op_ns.items(), key=lambda kv: -kv[1]):
        op = ops[op_id]
        rows = part_ops.setdefault(part_of(op["scope"]), [])
        if len(rows) < top:
            rows.append([xplane.op_label(op["name"]), op["source"], ns / chips / 1e9])
    return {
        "expand_s": sum(part_ns.values()) / chips / 1e9,
        "parts": {k: v / chips / 1e9 for k, v in part_ns.items()},
        "part_ops": part_ops,
    }


@functools.lru_cache(maxsize=2)
def analyse(path: str, annotation: str = xstages.WINDOW_ANNOTATION) -> dict:
    """:func:`reduce_expand` for one trace file (parsed once a process by
    :func:`xstages.load`, reduced once: four readers ask); the window is
    the annotation's, else the whole trace.  ``{}`` when the file holds no
    device operation."""
    trace = xstages.load(path, annotation)
    return reduce_expand(trace["devices"], trace["ops"], trace["annotation"])


def report(out: dict) -> str:
    total = out["expand_s"]
    rows = [f"xtwin: sr.expand {total:.6f} s = " + " + ".join(
        f"{k} {out['parts'][k]:.6f}" for k in PARTS + (REST,))]
    for part in PARTS + (REST,):
        secs = out["parts"][part]
        share = 100.0 * secs / total if total else 0.0
        rows.append(f"xtwin:   {part:<12} {secs:12.6f} s {share:6.2f}% of sr.expand")
        for label, source, s in out["part_ops"].get(part, []):
            rows.append(f"xtwin:       {s:12.6f} s  {label}  [{source}]")
    return "\n".join(rows)


# -- for the per-layer readers ----------------------------------------------------


def expand_of(ctx: dict, reader_file: str) -> dict:
    """The analysis of the traced check of ``ctx``'s cell, for a reader at
    ``<checkout>/benchmarks/layer_metrics/<metric>.py`` (the harness keeps
    the trace under ``<checkout>/.bench_trace/<cell>/`` until every reader
    has run); ``{}`` when there is no trace.  Prints the table (stderr)
    the first time a process asks for a file."""
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


def part_seconds(ctx: dict, reader_file: str, part: str) -> Optional[float]:
    """A part's device self seconds in the profiled check; 0 where no
    operation carries the scope.  None without a trace."""
    out = expand_of(ctx, reader_file)
    if not out:
        return None
    return float(out["parts"].get(part, 0.0))


def compile_span(ctx: dict) -> Optional[dict]:
    """The ``twin_compile`` span record among the warm-up check's
    flight-recorder records (the first check of a process adopts the
    twin, and with it the span the compiler closed); None where there is
    none: a hand-written twin, or a program that does not emit it."""
    for r in ctx.get("warmup_records") or []:
        if r.get("kind") == "span" and r.get("name") == COMPILE_SPAN:
            return r
    return None


def main(argv: list) -> int:
    if not 1 <= len(argv) <= 2:
        print(__doc__.rsplit("\n\n", 1)[1], file=sys.stderr)
        return 2
    path = xplane.find_xplane(argv[0]) if os.path.isdir(argv[0]) else argv[0]
    out = analyse(path, *argv[1:])
    if not out:
        print(f"xtwin: {path} holds no device operation", file=sys.stderr)
        return 1
    print(report(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

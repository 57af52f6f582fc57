"""Per-stage device time and per-span idle time from a ``jax.profiler`` trace.

``xplane.py`` reads a trace through ``jax.profiler.ProfileData``, which
shows an event's name, start and duration.  What names a device operation's
STAGE is one level further in: the ``event_metadata`` of the device plane,
whose ``tf_op`` stat is the operation's ``jax.named_scope`` path
(``jit(wavefront_run)/while/body/sr.insert/while/body/scatter:``), beside
``source`` (file:line) and XLA's ``bytes_accessed`` estimate.  ProfileData
does not expose it and no protobuf module for the format is installed
without TensorFlow, so :func:`read_xspace` reads the file's wire format
directly (seven message types, ``tsl/profiler/protobuf/xplane.proto``).

Two layers, as in ``xplane.py``:

 - :func:`read_xspace` / :func:`load` turn the file into plain tuples: the
   device operations with their scope, the program's ``sr/*`` host spans
   (``jax.profiler.TraceAnnotation``) and the harness's window annotation,
   all on the trace's one clock;
 - :func:`reduce_stages`, :func:`innermost_segments` and :func:`split_gaps`
   are pure arithmetic over those tuples.

A stage is the first ``sr.<name>`` component of an operation's scope path;
its time is the SELF time of its operations (``xplane.self_times``, control-
flow containers excluded), so the stages and ``unnamed`` add up to the
device's busy time.  An idle gap (``xplane.gaps`` over the leaf operations)
is charged to the innermost ``sr/*`` host span that covers it, the rest to
``unspanned``.  Of the same self times, those of the operations that cross
between chips (``COLLECTIVES``, by HLO opcode) are summed once more as
``collective_s``, whatever stage they are filed under.

On the CPU (the rehearsal) XLA's operations run on host threads and carry
``hlo_op`` / ``program_id`` instead of a scope; the scope is then looked up
in the HLO protos the profiler stores in the ``/host:metadata`` plane.

    python3 benchmarks/srbench/xstages.py <trace.xplane.pb | logdir> [annotation]

prints the tables for any trace, e.g. one an operator recorded with
``.telemetry(profile_steps=N, profile_dir=...)``.
"""

from __future__ import annotations

import bisect
import functools
import os
import struct
import sys
from typing import Iterable, Optional

if __package__ in (None, ""):  # run as a script: find the sibling modules
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from srbench import xplane  # noqa: E402

WINDOW_ANNOTATION = "srbench_traced_check"
STAGE_PREFIX = "sr."
SPAN_PREFIX = "sr/"
# the step program's stages, in program order (the program keeps the same
# list in stateright_tpu/telemetry/spans.py; a test holds the two together)
STAGES = ("sr.pop", "sr.props", "sr.expand", "sr.hash", "sr.insert",
          "sr.append", "sr.bookkeep", "sr.stats")
UNNAMED = "unnamed"
UNSPANNED = "unspanned"
# what crosses between chips: the HLO opcodes GSPMD writes for it (the program
# counts the same five in its ``mesh.program`` record, telemetry/collectives.py)
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


# -- the wire format ----------------------------------------------------------


def _varint(buf, i: int) -> tuple:
    x = buf[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        y = buf[i]
        i += 1
        x |= (y & 0x7F) << shift
        if y < 0x80:
            return x, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` range for a length-delimited field, a float for a
    fixed64 (the format's only one is a double)."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val = struct.unpack_from("<d", buf, i)[0]
            i += 8
        elif wire == 5:
            val = struct.unpack_from("<f", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire} in an xplane file")
        yield key >> 3, val


def _text(buf, rng: tuple) -> str:
    return bytes(buf[rng[0]:rng[1]]).decode("utf-8", "replace")


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf, rng: tuple, stat_names: dict) -> tuple:
    """One XStat as ``(stat name, value)``; a ``ref_value`` is the name of
    the stat metadata it points to, bytes stay a range."""
    name, val = None, None
    for f, v in _fields(buf, *rng):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 5:
            val = _text(buf, v)
        elif f == 7:
            val = stat_names.get(v, "")
        else:  # double (2), uint64 (3), int64 (4), bytes (6)
            val = v
    return name, val


def _map_value(buf, rng: tuple) -> Optional[tuple]:
    """The ``value`` (field 2) of a protobuf map entry."""
    for f, v in _fields(buf, *rng):
        if f == 2:
            return v
    return None


def _hlo_scopes(buf, rng: tuple) -> dict:
    """``{instruction name: (op_name, "file:line")}`` of one HloProto."""
    out = {}
    for f, module in _fields(buf, *rng):
        if f != 1:
            continue
        for f2, comp in _fields(buf, *module):
            if f2 != 3:
                continue
            for f3, instr in _fields(buf, *comp):
                if f3 != 2:
                    continue
                name, scope, src, line = None, "", "", 0
                for f4, v in _fields(buf, *instr):
                    if f4 == 1:
                        name = _text(buf, v)
                    elif f4 == 7:
                        for f5, w in _fields(buf, *v):
                            if f5 == 2:
                                scope = _text(buf, w)
                            elif f5 == 3:
                                src = _text(buf, w)
                            elif f5 == 4:
                                line = w
                out[name] = (scope, f"{src}:{line}" if src else "")
    return out


def read_xspace(path: str) -> list:
    """The planes of an ``.xplane.pb`` as dicts: ``name``, ``event_meta``
    (``{id: {"name", "stats"}}``, stats resolved to a dict) and
    ``lines`` (``[(line name, [(metadata id, start_ns, duration_ns,
    stats range list)])]``).  Event stats are left unparsed; ``buf`` is
    kept on the plane for those who need one (:func:`event_stats`)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_rngs, meta_rngs, stat_names = "", [], [], {}
        for f2, v in _fields(buf, *plane):
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 3:
                line_rngs.append(v)
            elif f2 == 4:
                meta_rngs.append(v)
            elif f2 == 5:
                entry = _map_value(buf, v)
                sid, sname = None, ""
                for f3, w in _fields(buf, *entry):
                    if f3 == 1:
                        sid = w
                    elif f3 == 2:
                        sname = _text(buf, w)
                stat_names[sid] = sname
        event_meta = {}
        for rng in meta_rngs:
            entry = _map_value(buf, rng)
            mid, md = None, {"name": "", "stats": {}}
            for f3, w in _fields(buf, *entry):
                if f3 == 1:
                    mid = w
                elif f3 == 2:
                    md["name"] = _text(buf, w)
                elif f3 == 5:
                    key, val = _stat(buf, w, stat_names)
                    md["stats"][key] = val
            event_meta[mid] = md
        # a device plane's events carry nothing but their timing here, and
        # there are a million of them: their stats are not kept
        keep_stats = not name.startswith("/device:")
        lines = []
        for rng in line_rngs:
            lname, t0_ns, events = "", 0, []
            for f3, w in _fields(buf, *rng):
                if f3 == 2:
                    lname = _text(buf, w)
                elif f3 == 3:
                    t0_ns = _signed(w)
                elif f3 == 4:
                    mid, off_ps, dur_ps, stats = 0, 0, 0, []
                    for f4, x in _fields(buf, *w):
                        if f4 == 1:
                            mid = x
                        elif f4 == 2:
                            off_ps = _signed(x)
                        elif f4 == 3:
                            dur_ps = _signed(x)
                        elif f4 == 4 and keep_stats:
                            stats.append(x)
                    events.append((mid, off_ps, dur_ps, stats))
            # the line's timestamp may follow its events in the file
            lines.append((lname, [
                (mid, t0_ns + off / 1000.0, dur / 1000.0, stats)
                for mid, off, dur, stats in events
            ]))
        planes.append({"name": name, "event_meta": event_meta, "lines": lines,
                       "stat_names": stat_names, "buf": buf})
    return planes


def event_stats(plane: dict, stat_ranges: list) -> dict:
    return dict(_stat(plane["buf"], r, plane["stat_names"]) for r in stat_ranges)


# -- from planes to tuples ------------------------------------------------------


def stage_of(scope: str) -> str:
    """The first ``sr.<stage>`` component of a scope path, else ``unnamed``."""
    for part in scope.split("/"):
        if part.startswith(STAGE_PREFIX):
            return part.rstrip(":")
    return UNNAMED


def is_collective(name: str) -> bool:
    """Whether a device operation's HLO opcode is one of ``COLLECTIVES``; an
    asynchronous one's two halves (``all-reduce-start`` / ``-done``) both
    are, each for its own time."""
    code = xplane.opcode(name)
    for half in ("-start", "-done"):
        code = code.removesuffix(half)
    return code in COLLECTIVES


@functools.lru_cache(maxsize=2)
def load(path: str, annotation: str = WINDOW_ANNOTATION) -> dict:
    """``{"devices": {plane: [(op id, start_ns, duration_ns)]}, "ops":
    {op id: {"name", "scope", "source", "bytes"}}, "spans": [(name,
    start_ns, end_ns)], "annotation": (start_ns, end_ns) | None}``.

    Parsed once a process (every per-layer reader asks for the same file).
    An op id is ``(plane, metadata id)``: two programs of one run may hold
    an instruction of the same name in different stages."""
    planes = read_xspace(path)
    devices: dict = {}
    ops: dict = {}
    spans: list = []
    note = None
    tpu = [p for p in planes if p["name"].startswith(xplane.DEVICE_PLANE_PREFIX)]
    for p in tpu:
        evs = devices.setdefault(p["name"], [])
        for lname, events in p["lines"]:
            if lname != xplane.OPS_LINE:
                continue
            for mid, start, dur, _ in events:
                evs.append(((p["name"], mid), start, dur))
        for mid, md in p["event_meta"].items():
            st = md["stats"]
            ops[(p["name"], mid)] = {
                "name": md["name"], "scope": st.get("tf_op") or "",
                "source": st.get("source") or "",
                "bytes": int(st.get("bytes_accessed") or 0),
            }
    hlo: dict = {}  # program id -> {instruction: (scope, source)}, CPU only
    if not tpu:
        for p in planes:
            if p["name"] != "/host:metadata":
                continue
            for md in p["event_meta"].values():
                proto = md["stats"].get("Hlo Proto")
                prog = md["name"].rpartition("(")[2].rstrip(")")
                if isinstance(proto, tuple) and prog.isdigit():
                    hlo[int(prog)] = _hlo_scopes(p["buf"], proto)
    for p in planes:
        if not p["name"].startswith("/host:") or p["name"] == "/host:metadata":
            continue
        for lname, events in p["lines"]:
            for mid, start, dur, stat_ranges in events:
                name = p["event_meta"].get(mid, {}).get("name", "")
                if name == annotation:
                    if note is None or dur > note[1] - note[0]:
                        note = (start, start + dur)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
                elif not tpu and stat_ranges:
                    st = event_stats(p, stat_ranges)
                    if "hlo_op" not in st:
                        continue
                    # the rehearsal: XLA:CPU's thunks stand in for the chip
                    prog = st.get("program_id")
                    op_id = (prog, st["hlo_op"])
                    if op_id not in ops:
                        scope, src = hlo.get(prog, {}).get(st["hlo_op"], ("", ""))
                        ops[op_id] = {"name": st["hlo_op"], "scope": scope,
                                      "source": src, "bytes": 0}
                    devices.setdefault(f"{p['name']}#{lname}", []).append(
                        (op_id, start, dur)
                    )
    return {"devices": devices, "ops": ops, "spans": sorted(spans, key=lambda s: s[1]),
            "annotation": note}


# -- the arithmetic -------------------------------------------------------------


def reduce_stages(devices: dict, ops: dict, window: Optional[tuple] = None,
                  top: int = 8) -> dict:
    """Self time per stage over the leaf operations inside ``window``.

    Returns seconds (averaged over the chips, as ``xplane.reduce_events``
    does): ``stages`` (every stage seen, plus ``unnamed``), their sum
    ``self_s``, the union of the leaf intervals ``busy_s`` (the two agree
    unless leaf operations overlap), ``unnamed_pct``, XLA's
    ``bytes_accessed`` summed per stage over the executed operations
    (``stage_bytes``: XLA's estimate, not a measurement), the ``top``
    operations of each stage with their source (``stage_ops``:
    ``[label, source, seconds]``), the idle ``gaps`` of the busiest
    chip (``[(start_ns, end_ns)]``, all of them) and, of the same self
    times, the collectives' (``is_collective``, whatever scope they carry):
    ``collective_s`` and its split by the stage each is filed under
    (``collective_stages``) - a part OF the stages, not beside them."""
    if not devices or not any(devices.values()):
        return {}
    if window is None:
        window = (
            min(s for evs in devices.values() for _, s, _ in evs),
            max(s + d for evs in devices.values() for _, s, d in evs),
        )
    w0, w1 = window
    chips = len(devices)
    stage_ns: dict = {}
    stage_bytes: dict = {}
    op_ns: dict = {}
    collective_ns: dict = {}
    crosses = {op_id: is_collective(op["name"]) for op_id, op in ops.items()}
    busy, work_of = [], []
    for plane, events in sorted(devices.items()):
        work = []
        for op_id, s, e, self_ns in xplane.self_times(events):
            op = ops[op_id]
            if xplane.is_container(op["name"]) or e <= w0 or s >= w1:
                continue
            work.append((max(s, w0), min(e, w1)))
            stage = stage_of(op["scope"])
            stage_ns[stage] = stage_ns.get(stage, 0.0) + self_ns
            stage_bytes[stage] = stage_bytes.get(stage, 0) + op["bytes"]
            op_ns[op_id] = op_ns.get(op_id, 0.0) + self_ns
            if crosses[op_id]:
                collective_ns[stage] = collective_ns.get(stage, 0.0) + self_ns
        busy.append(xplane.union_ns(work))
        work_of.append(work)
    busiest = max(range(chips), key=busy.__getitem__)
    stage_ops: dict = {}
    for op_id, ns in sorted(op_ns.items(), key=lambda kv: -kv[1]):
        op = ops[op_id]
        rows = stage_ops.setdefault(stage_of(op["scope"]), [])
        if len(rows) < top:
            rows.append([xplane.op_label(op["name"]), op["source"], ns / chips / 1e9])
    self_ns = sum(stage_ns.values())
    return {
        "stages": {k: v / chips / 1e9 for k, v in stage_ns.items()},
        "self_s": self_ns / chips / 1e9,
        "busy_s": sum(busy) / chips / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "unnamed_pct": 100.0 * stage_ns.get(UNNAMED, 0.0) / self_ns if self_ns else 0.0,
        "stage_bytes": {k: v // chips for k, v in stage_bytes.items()},
        "stage_ops": stage_ops,
        "collective_s": sum(collective_ns.values()) / chips / 1e9,
        "collective_stages": {k: v / chips / 1e9 for k, v in collective_ns.items()},
        "gaps": xplane.gaps(work_of[busiest], window),
        "chips": chips,
    }


def innermost_segments(spans: Iterable[tuple]) -> list:
    """Disjoint ``[(start, end, name)]``, in time order: over each segment
    ``name`` is the innermost of the ``(name, start, end)`` spans covering
    it (the one that started last).  A span that outlives the one it
    started in is cut at that one's end."""
    out: list = []
    stack: list = []  # [name, end]
    cursor = 0.0

    def emit(upto: float) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s)
            e = min(e, stack[-1][1])
        cursor = s if not stack else max(cursor, s)
        if e > s:
            stack.append([name, e])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def split_gaps(gaps_ns: Iterable[tuple], spans: Iterable[tuple]) -> dict:
    """Seconds of idle time per innermost covering span; what no span
    covers is ``unspanned``."""
    segs = innermost_segments(spans)
    starts = [s for s, _, _ in segs]
    sums: dict = {}
    for g0, g1 in gaps_ns:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            s, e, name = segs[i]
            lap = min(e, g1) - max(s, g0)
            if lap > 0:
                sums[name] = sums.get(name, 0.0) + lap
                covered += lap
            i += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            sums[UNSPANNED] = sums.get(UNSPANNED, 0.0) + rest
    return {k: v / 1e9 for k, v in sums.items()}


def analyse(path: str, annotation: str = WINDOW_ANNOTATION) -> dict:
    """Everything above for one trace file; ``{}`` when it holds no device
    operation.  The window is the annotation's, else the whole trace."""
    trace = load(path, annotation)
    out = reduce_stages(trace["devices"], trace["ops"], trace["annotation"])
    if not out:
        return {}
    w0, w1 = trace["annotation"] or (float("-inf"), float("inf"))
    spans = [s for s in trace["spans"] if s[2] > w0 and s[1] < w1]
    span_s: dict = {}
    for name, s, e in spans:
        span_s[name] = span_s.get(name, 0.0) + (e - s) / 1e9
    out["span_s"] = span_s
    out["idle"] = split_gaps(out.pop("gaps"), spans)
    out["windowed"] = trace["annotation"] is not None
    return out


# -- for the per-layer readers ----------------------------------------------------


def trace_of(ctx: dict, reader_file: str) -> dict:
    """The analysis of the traced check of ``ctx``'s cell, for a reader at
    ``<checkout>/benchmarks/layer_metrics/<metric>.py``: the harness keeps
    the trace under ``<checkout>/.bench_trace/<cell>/`` until every reader
    has run.  ``{}`` when there is no trace.  Prints the tables (stderr)
    the first time a process asks."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
    try:
        path = xplane.find_xplane(os.path.join(root, ".bench_trace", ctx["cell"]["name"]))
    except FileNotFoundError:
        return {}
    first = load.cache_info().currsize == 0
    out = analyse(path)
    if first and out:
        print(report(out), file=sys.stderr, flush=True)
    return out


def stage_seconds(ctx: dict, reader_file: str, stage: str) -> Optional[float]:
    """A stage's device self seconds in the profiled check: 0 where no
    operation carries the scope — a program without the names, or an
    executable that lost them, reads 0 here and 100% unnamed, which is
    louder than a metric that goes missing.  None without a trace."""
    out = trace_of(ctx, reader_file)
    if not out:
        return None
    return float(out["stages"].get(stage, 0.0))


def span_seconds(ctx: dict, name: str, marker: str) -> Optional[float]:
    """Seconds per check inside the program's ``name`` spans (flight-recorder
    ``span`` records), median over the window's checks; 0 where a check
    has none.  None where no check holds a ``marker`` span: the program
    does not emit these seams."""
    per_check, seen = [], False
    for c in ctx["checks"]:
        durs = {}
        for r in c.get("records", []):
            if r["kind"] == "span":
                durs[r["name"]] = durs.get(r["name"], 0.0) + float(r["dur"])
        seen = seen or marker in durs
        per_check.append(durs.get(name, 0.0))
    if not seen:
        return None
    return float(ctx["median"](per_check))


def report(out: dict) -> str:
    rows = [f"xstages: device self time {out['self_s']:.6f} s, busy {out['busy_s']:.6f} s, "
            f"window {out['window_s']:.6f} s ({'annotation' if out['windowed'] else 'whole trace'}), "
            f"chips {out['chips']}"]
    order = [s for s in STAGES if s in out["stages"]]
    order += sorted(k for k in out["stages"] if k not in STAGES)
    for stage in order:
        secs = out["stages"][stage]
        rows.append(
            f"xstages:   {stage:<12} {secs:12.6f} s {100.0 * secs / out['self_s']:6.2f}%  "
            f"XLA's bytes_accessed estimate {out['stage_bytes'].get(stage, 0):>16,d}"
        )
        for label, source, s in out["stage_ops"].get(stage, []):
            rows.append(f"xstages:       {s:12.6f} s  {label}  [{source}]")
    if out["collective_stages"]:
        rows.append(f"xstages: collectives {out['collective_s']:.6f} s of that self time, "
                    "by the stage they are filed under: " + ", ".join(
            f"{k} {v:.6f}" for k, v in sorted(out["collective_stages"].items(),
                                              key=lambda kv: -kv[1])))
    rows.append("xstages: host spans (seconds inside, profiler's clock): " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(out["span_s"].items(), key=lambda kv: -kv[1])))
    rows.append("xstages: idle seconds by innermost covering span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(out["idle"].items(), key=lambda kv: -kv[1])))
    return "\n".join(rows)


def main(argv: list) -> int:
    if not 1 <= len(argv) <= 2:
        print(__doc__.rsplit("\n\n", 2)[1], file=sys.stderr)
        return 2
    path = xplane.find_xplane(argv[0]) if os.path.isdir(argv[0]) else argv[0]
    out = analyse(path, *argv[1:])
    if not out:
        print(f"xstages: {path} holds no device operation", file=sys.stderr)
        return 1
    print(report(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

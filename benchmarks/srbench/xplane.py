"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device busy
time, idle gaps and per-operation sums.

Two layers, so the arithmetic is testable without a profiler:

 - :func:`load_trace` reads the file with ``jax.profiler.ProfileData`` and
   returns plain tuples: the device operations of each chip and the host
   annotation the harness emitted to align the clocks;
 - :func:`reduce_events` is pure interval arithmetic over those tuples.

What counts as a device operation: the events of the ``XLA Ops`` line of
every ``/device:TPU:<n>`` plane (read off a v5e trace by hand, PR 23: the
plane also has ``XLA Modules``, one event per executed program, and
``Async XLA Ops``, the copy-start/copy-done pairs that overlap the ops).
XLA nests them (a ``while`` spans its body's operations), so an
operation's time is its SELF time — its duration minus what its children
cover — and the chip is busy exactly where an operation that is not a
control-flow container runs (``while`` / ``conditional`` / ``call`` only
sequence their children; a gap between the children is the device
waiting).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
CONTAINER_OPS = ("while", "conditional", "call")

Event = tuple  # (name, start_ns, duration_ns)


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def load_trace(path: str, annotation: str,
               host_ops_as_device: bool = False) -> dict:
    """``{"devices": {plane: [Event...]}, "annotation": Event | None}``.

    ``host_ops_as_device`` is for the CPU rehearsal only: XLA:CPU runs its
    operations on host threads, so the events that carry an ``hlo_op``
    stat stand in for the device lines (labelled as a rehearsal by the
    caller, never a result)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    note: Optional[Event] = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events
            ]
            devices[plane.name] = ops
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == annotation:
                    ev = (e.name, float(e.start_ns), float(e.duration_ns))
                    if note is None or ev[2] > note[2]:
                        note = ev
                elif host_ops_as_device and any(
                    k == "hlo_op" for k, _ in e.stats
                ):
                    devices.setdefault(f"{plane.name}#{line.name}", []).append(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                    )
    return {"devices": devices, "annotation": note}


_LAYOUT = re.compile(r"\{[^}]*\}")
_RESULT_AND_OPCODE = re.compile(r"^(\(.*?\)|\S+)\s+([\w\-]+)\(")


def op_label(name: str, limit: int = 96) -> str:
    """A device operation's event name is its whole HLO line
    (``%fusion.9 = s32[16384]{0:T(1024)S(1)} fusion(...), kind=...``);
    the label keeps XLA's own name, the opcode and the result type:
    ``fusion.9 fusion->s32[16384]``."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head[:limit]
    m = _RESULT_AND_OPCODE.match(_LAYOUT.sub("", rest))
    if not m:
        return head[:limit]
    return f"{head} {m.group(2)}->{m.group(1)}"[:limit]


def is_container(name: str) -> bool:
    base = name.partition(" = ")[0].lstrip("%").split(".")[0].strip().lower()
    return base in CONTAINER_OPS


def opcode(name: str) -> str:
    """The HLO opcode of a device operation's event name: the word before
    the operands of its whole HLO line (``%all-reduce.44 = u32[..]
    all-reduce(..)`` -> ``all-reduce``; a fusion XLA NAMED after what it
    holds is still a ``fusion``), else - a bare instruction name, as
    XLA:CPU's thunks carry - the name without its number."""
    head, sep, rest = name.partition(" = ")
    m = _RESULT_AND_OPCODE.match(_LAYOUT.sub("", rest)) if sep else None
    if m:
        return m.group(2)
    return head.lstrip("%").split(".")[0].strip()


def self_times(events: Iterable[Event]) -> list:
    """``[(name, start, end, self_ns)]``: each event's duration minus the
    part its nested children cover.  Events nest when one starts inside
    another on the same line and ends no later (XLA's ``while`` and its
    body); partial overlaps are treated as siblings."""
    evs = sorted(
        ((n, s, s + d) for n, s, d in events), key=lambda e: (e[1], -e[2])
    )
    out = []
    stack: list = []  # [name, start, end, covered_by_children]

    def pop() -> None:
        n, s, e, cov = stack.pop()
        out.append((n, s, e, max(e - s - cov, 0.0)))
        if stack:
            stack[-1][3] += e - s

    for n, s, e in evs:
        while stack and stack[-1][2] <= s:
            pop()
        if stack and e > stack[-1][2]:
            pop()  # a partial overlap: the open event is a sibling, not a parent
        stack.append([n, s, e, 0.0])
    while stack:
        pop()
    return out


def union_ns(intervals: Iterable[tuple]) -> float:
    """Total length covered by ``[(start, end)]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple], window: tuple) -> list:
    """Idle gaps ``[(start, end)]`` of ``window`` that no interval covers."""
    w0, w1 = window
    out, cursor = [], w0
    for s, e in sorted(intervals):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        out.append((cursor, w1))
    return out


def reduce_events(devices: dict, window: Optional[tuple] = None,
                  top: int = 10) -> dict:
    """Busy time, idle gaps and per-operation self-time sums.

    ``devices`` maps a chip's plane name to its events; ``window`` is
    ``(start_ns, end_ns)`` on the trace's clock (default: first start to
    last end over all chips).  Returns seconds: ``busy_s`` averaged over
    the chips, ``window_s``, ``idle_pct``, ``device_ops`` (the ``top``
    operations by self time summed over chips, containers excluded) and
    ``gaps`` (the ``top`` longest idle gaps of the busiest chip, as
    ``(start_ns, end_ns)``)."""
    if not devices or not any(devices.values()):
        return {}
    if window is None:
        window = (
            min(s for evs in devices.values() for _, s, _ in evs),
            max(s + d for evs in devices.values() for _, s, d in evs),
        )
    w0, w1 = window
    if w1 <= w0:
        raise ValueError("empty trace window")
    per_chip, op_sums, work_of = [], {}, []
    for plane, events in sorted(devices.items()):
        work = []
        for name, s, e, self_ns in self_times(events):
            if is_container(name) or e <= w0 or s >= w1:
                continue
            work.append((max(s, w0), min(e, w1)))
            label = op_label(name)
            op_sums[label] = op_sums.get(label, 0.0) + self_ns
        per_chip.append(union_ns(work))
        work_of.append(work)
    busy_ns = sum(per_chip) / len(per_chip)
    busiest = max(range(len(per_chip)), key=per_chip.__getitem__)
    longest = sorted(
        gaps(work_of[busiest], window), key=lambda g: g[0] - g[1]
    )[:top]
    ops = sorted(op_sums.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / (w1 - w0)),
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "gaps": longest,
        "chips": len(per_chip),
    }

"""Roofline cost ledger: per-op FLOPs/bytes attribution for the engines.

The observability triad answers *what happened* (flight recorder),
*where the search went* (cartography/health), and *where the memory
goes* (HBM ledger) — this module answers **where the time goes below
stage granularity**: which jaxpr operations in the engine pipeline move
how many bytes and execute how many scalar ops, so the MXU round
(BLEST / "Graph Traversal on Tensor Cores", PAPERS.md) starts from a
ranked, reconciled hot-spot ledger instead of guesses.

The walk reuses the footprint pass's traversal discipline
(``analysis/footprint.py``): materialize the twin's device constants via
``init_rows()`` outside any trace, ``jax.make_jaxpr`` each pipeline
kernel, then one forward pass over the closed jaxpr charging every eqn
with

 - **FLOPs** — one scalar op per output element for elementwise
   primitives, ``n log2 n`` for sorts, the full read for reductions,
   ``2·M·N·K`` for ``dot_general``, zero for pure layout/data movement;
 - **bytes read / bytes written** — the *moved window* for
   data-dependent memory ops (a gather reads the gathered elements, not
   the whole table; a dynamic-update-slice writes the update window, not
   the whole buffer — matching both XLA's charging model and the
   roofline meaning of the number); a ``reshape`` is charged its operand
   read and written, except the flat-to-128-lane-rows view, which is a
   bitcast on the TPU's tiled layouts (``_is_row_view``);
 - an **op class** — ``gather`` / ``scatter`` / ``sort`` / ``dot`` /
   ``elementwise`` / ``reduce`` / ``control``.

Costs aggregate per **engine pipeline stage** — ``property`` /
``expand`` / ``hash`` / ``dedup-insert`` / ``queue``, the five phases of
one wavefront step — and per **action** via the footprint pass's
action-axis decomposition (eqns reachable from exactly one action's
successor stack piece charge to it; the rest charge to ``shared``).

Reconciliation (the memory ledger's ``memory_analysis()`` discipline,
``telemetry/memory.py``): every stage kernel is also compiled and its
``compiled.cost_analysis()`` flops / bytes-accessed recorded next to the
analytic totals.  The two models measure different programs — the walk
charges the *unfused* jaxpr, XLA the *optimized* HLO — so the pinned
contract is a tolerance band, not equality: analytic FLOPs within
``FLOPS_BAND``× of XLA's, analytic bytes never below ``BYTES_LO``× of
XLA's (fusion only ever removes traffic the walk charged) and within
``BYTES_HI``× above.  Exact where exact is possible: a purely
elementwise kernel (the ``hash`` stage) charges bit-identical FLOPs to
XLA's count, pinned by test.

MXU-candidate ranking (rule catalogue ``JX4xx``, docs/roofline.md):

 - ``JX400`` info — a gather/scatter-class op whose shape admits a
   blocked one-hot-matmul recast (the BLEST membership-probe move),
   ranked by charged bytes;
 - ``JX401`` info — a sort-class op recastable as blocked
   compare-exchange / bitonic stages on the MXU;
 - ``JX402`` info — the summary line: which stage owns the largest
   MXU-candidate byte volume.

Everything here is host-side analysis over re-traced kernels: the
engines' own step program is never touched (roofline on or off leaves
the run jaxpr bit-identical and the engine cache unkeyed — pinned,
the ``telemetry/memory.py`` contract in its strongest form).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from .interval import is_literal
from .report import AuditFinding, Severity

# cost-model schema version (the ``roofline`` ring-record / report-block
# ``v`` field rides this)
COSTMODEL_V = 1

# reconciliation tolerance bands (analytic / xla ratios), calibrated on
# the bundled twins (docs/roofline.md "Reconciliation contract"):
#  - FLOPs: both models count scalar ops; they differ on fused selects /
#    gather address math, measured within ~3x either way on the fleet.
#  - bytes: the walk charges the unfused jaxpr (every intermediate
#    read+written), XLA the fused HLO (intermediates fused away), so
#    analytic is an upper bound — bounded above by the longest
#    elementwise chain (BYTES_HI).  The lower side is NOT 1.0: the
#    reconciliation compiles each stage kernel standalone, where an
#    un-donated in-place update (the queue stage's
#    dynamic-update-slice) pays a full-buffer copy XLA prices and the
#    walk — correctly, matching the donated engine carry — does not.
#    Fleet calibration (CPU XLA; the fleet still reconciles inside
#    these bands on jax 0.9.0, tests/test_costmodel.py): bytes ratios span ~0.5
#    (the queue stage's un-donated standalone copy) to ~140 (raft's
#    deeply fused elementwise property chain); the bands leave ~2x
#    margin either side.
FLOPS_BAND = 8.0
BYTES_LO = 0.25
BYTES_HI = 256.0

# MXU-candidate threshold: data-movement ops below this per-step byte
# volume are not worth a matmul recast (one MXU pass costs more)
MXU_MIN_BYTES = 4096
_MXU_TOP = 8  # candidates kept in the ranking / emitted as findings

OP_CLASSES = ("gather", "scatter", "sort", "dot", "elementwise",
              "reduce", "control")

# pure layout / data movement: zero FLOPs, bytes only
_LAYOUT = frozenset({
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "copy",
    "convert_element_type", "transpose", "slice", "concatenate", "iota",
    "rev", "pad", "stop_gradient", "bitcast_convert_type",
})
_GATHER = frozenset({"gather", "dynamic_slice"})
_SCATTER = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter_max", "scatter_min",
    "scatter_mul", "dynamic_update_slice",
})
_REDUCE_PREFIX = "reduce_"
_REDUCE = frozenset({
    "argmax", "argmin", "cumsum", "cummax", "cummin", "cumprod",
    "cumlogsumexp",
})
# NOT "jit": on jax 0.9.0 a jit-wrapped sub-function (jnp.where, take, …)
# is priced as ONE opaque elementwise op — read its inputs, write its
# outputs — exactly as this ledger has priced every run on this install.
# Inlining those bodies is more faithful but MOVES the ledger (the MXU
# expand+queue charged-bytes drop reads 29.6% instead of 33.4%, under the
# 30% gate), so it waits for a PR that may move a metric (ROADMAP).
_CALLS = frozenset({
    "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "remat_call", "checkpoint", "remat",
})
_CONTROL = frozenset({"while", "cond", "scan"})


def classify_primitive(name: str) -> str:
    """Op class of one jaxpr primitive (``OP_CLASSES``)."""
    if name in _GATHER:
        return "gather"
    if name in _SCATTER:
        return "scatter"
    if name == "sort":
        return "sort"
    if name in ("dot_general", "conv_general_dilated"):
        return "dot"
    if name.startswith(_REDUCE_PREFIX) or name in _REDUCE:
        return "reduce"
    if name in _CONTROL or name in _CALLS:
        return "control"
    return "elementwise"


def _nelems(v) -> int:
    shape = tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    item = np.dtype(dt).itemsize if dt is not None else 8
    return _nelems(v) * item


def _itemsize(v) -> int:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return np.dtype(dt).itemsize if dt is not None else 8


@dataclass
class EqnCost:
    """Charged cost of one jaxpr eqn (or one aggregated (prim, shape)
    site)."""

    prim: str
    op_class: str
    flops: int
    bytes_read: int
    bytes_written: int
    count: int = 1
    #: shape of the MOVED data (the roofline-relevant window), for the
    #: MXU ranking's recast check
    shape: tuple = ()
    #: shape of the indexed operand (gather/scatter only)
    operand_shape: tuple = ()

    @property
    def bytes_total(self) -> int:
        return self.bytes_read + self.bytes_written


def _is_row_view(eqn) -> bool:
    """Whether a ``reshape`` is the one view that moves nothing on the
    TPU: a flat array of 32-bit planes against its ``[n / ROW_LANES,
    ROW_LANES]`` rows.  A 1-D plane is tiled ``8 * ROW_LANES`` consecutive
    elements a tile and the row view 8 rows x ``ROW_LANES`` lanes a tile:
    the same elements, so the compiler makes it a bitcast
    (``ops/buckets.py``, "Where the layout is fixed": the visited table's
    membership gather reads through it every step).  That identity holds
    for 4-byte words (and for the 8-byte ones the chip splits into two
    such planes); a packed dtype tiles otherwise and is charged.  Every
    other reshape is charged as the relayout it may be - the ``[nbuckets,
    16]`` view this replaced cost a read and a write of the whole table a
    step."""
    from ..ops.buckets import ROW_LANES

    operand, result = eqn.invars[0].aval, eqn.outvars[0].aval
    flat, rows = sorted((tuple(operand.shape), tuple(result.shape)), key=len)
    return (
        np.dtype(operand.dtype).itemsize in (4, 8)
        and len(flat) == 1 and flat[0] % (8 * ROW_LANES) == 0
        and rows == (flat[0] // ROW_LANES, ROW_LANES)
    )


def _charge_eqn(eqn) -> EqnCost:
    """FLOPs/bytes of one non-call eqn, per the module-docstring rules."""
    name = eqn.primitive.name
    cls = classify_primitive(name)
    out_elems = max((_nelems(v) for v in eqn.outvars), default=0)
    out_bytes = sum(_nbytes(v) for v in eqn.outvars)
    in_bytes = sum(
        _nbytes(v) for v in eqn.invars if not is_literal(v)
    )
    shape = tuple(
        getattr(getattr(eqn.outvars[0], "aval", None), "shape", ()) or ()
    ) if eqn.outvars else ()
    operand_shape: tuple = ()
    flops = 0
    if name == "reshape" and _is_row_view(eqn):
        in_bytes = out_bytes = 0
    if cls == "gather":
        # reads: the gathered window (out-sized elements of the operand)
        # + the index vector; the untouched rest of the operand is free
        operand_shape = tuple(
            getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
            or ()
        )
        idx_bytes = sum(
            _nbytes(v) for v in eqn.invars[1:] if not is_literal(v)
        )
        in_bytes = out_elems * _itemsize(eqn.invars[0]) + idx_bytes
    elif cls == "scatter":
        # moved window = the updates; the operand is updated in place
        # (XLA's aliasing model) — charge the touched region both ways.
        # Operand orders differ: scatter is (operand, indices, updates),
        # dynamic_update_slice is (operand, update, *start_indices).
        operand_shape = tuple(
            getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
            or ()
        )
        if name == "dynamic_update_slice":
            upd = eqn.invars[1]
            idx_vars = eqn.invars[2:]
        else:
            upd = eqn.invars[-1]
            idx_vars = eqn.invars[1:-1]
        upd_bytes = _nbytes(upd)
        idx_bytes = sum(
            _nbytes(v) for v in idx_vars if not is_literal(v)
        )
        in_bytes = upd_bytes + idx_bytes + upd_bytes
        out_bytes = upd_bytes
        shape = tuple(
            getattr(getattr(upd, "aval", None), "shape", ()) or ()
        )
    elif cls == "sort":
        n = max((_nelems(v) for v in eqn.invars if not is_literal(v)),
                default=0)
        flops = int(n * max(math.log2(max(n, 2)), 1.0))
    elif cls == "dot":
        dnums = eqn.params.get("dimension_numbers")
        m_elems = out_elems
        k = 1
        if dnums is not None:
            try:
                (lc, _rc), _ = dnums
                lshape = tuple(
                    getattr(getattr(eqn.invars[0], "aval", None),
                            "shape", ()) or ()
                )
                for d in lc:
                    k *= int(lshape[d])
            except Exception:  # noqa: BLE001 - fall back to out-sized
                k = 1
        flops = 2 * m_elems * k
    elif cls == "reduce":
        flops = sum(
            _nelems(v) for v in eqn.invars if not is_literal(v)
        )
    elif name in _LAYOUT:
        flops = 0
    else:  # elementwise compute
        flops = out_elems
    return EqnCost(
        prim=name, op_class=cls, flops=int(flops),
        bytes_read=int(in_bytes), bytes_written=int(out_bytes),
        shape=shape, operand_shape=operand_shape,
    )


# ---------------------------------------------------------------------------
# jaxpr linearization (call inlining) + the stage walk
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    """Yield every non-call eqn of ``jaxpr``, recursing into call / control
    primitives (loop and branch bodies charge ONE trip — the static model
    prices one wavefront step, trip counts are runtime data)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CALLS:
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is not None:
                yield from _iter_eqns(getattr(inner, "jaxpr", inner))
            continue
        if name in _CONTROL:
            bodies = []
            for key in ("jaxpr", "body_jaxpr", "cond_jaxpr"):
                j = eqn.params.get(key)
                if j is not None:
                    bodies.append(j)
            branches = eqn.params.get("branches")
            if branches:
                bodies.extend(branches)
            for b in bodies:
                yield from _iter_eqns(getattr(b, "jaxpr", b))
            continue
        yield eqn


@dataclass
class StageCost:
    """Aggregated cost of one pipeline stage's traced kernel."""

    name: str
    flops: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    eqns: int = 0
    classes: dict = field(default_factory=dict)  # class -> {flops, bytes}
    #: aggregated data-movement sites for the MXU ranking:
    #: (prim, shape, operand_shape) -> EqnCost (count accumulated)
    movement: dict = field(default_factory=dict)

    @property
    def bytes_total(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def intensity(self) -> Optional[float]:
        """Arithmetic intensity (FLOPs per byte moved); None at 0 bytes."""
        if self.bytes_total <= 0:
            return None
        return self.flops / self.bytes_total

    def charge(self, cost: EqnCost) -> None:
        self.flops += cost.flops
        self.bytes_read += cost.bytes_read
        self.bytes_written += cost.bytes_written
        self.eqns += 1
        c = self.classes.setdefault(
            cost.op_class, {"flops": 0, "bytes": 0, "count": 0}
        )
        c["flops"] += cost.flops
        c["bytes"] += cost.bytes_total
        c["count"] += 1
        if cost.op_class in ("gather", "scatter", "sort"):
            key = (cost.prim, cost.shape, cost.operand_shape)
            site = self.movement.get(key)
            if site is None:
                self.movement[key] = EqnCost(
                    prim=cost.prim, op_class=cost.op_class,
                    flops=cost.flops, bytes_read=cost.bytes_read,
                    bytes_written=cost.bytes_written, shape=cost.shape,
                    operand_shape=cost.operand_shape,
                )
            else:
                site.flops += cost.flops
                site.bytes_read += cost.bytes_read
                site.bytes_written += cost.bytes_written
                site.count += 1

    def to_json(self) -> dict:
        out = {
            "flops": int(self.flops),
            "bytes_read": int(self.bytes_read),
            "bytes_written": int(self.bytes_written),
            "eqns": int(self.eqns),
            "classes": {
                k: dict(v) for k, v in sorted(self.classes.items())
            },
        }
        ai = self.intensity
        if ai is not None:
            out["intensity"] = round(ai, 6)
        return out


def walk_jaxpr(closed, name: str = "kernel") -> StageCost:
    """Charge every eqn of a closed jaxpr into one :class:`StageCost`."""
    stage = StageCost(name=name)
    for eqn in _iter_eqns(closed.jaxpr):
        stage.charge(_charge_eqn(eqn))
    return stage


# ---------------------------------------------------------------------------
# per-action attribution (the footprint pass's action-axis decomposition)
# ---------------------------------------------------------------------------


def _flatten_entries(closed):
    """Linearize the jaxpr with calls inlined: returns ``(entries,
    producer, alias)`` where ``entries`` is ``[(eqn, cost), ...]``,
    ``producer`` maps each var to its entry index, and ``alias`` maps
    call-boundary vars onto their outer/inner twins."""
    entries: list = []
    producer: dict = {}
    alias: dict = {}

    def resolve(v):
        seen = 0
        while v in alias and seen < 64:
            v = alias[v]
            seen += 1
        return v

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CALLS:
                inner = (
                    eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                )
                if inner is None:
                    continue
                ij = getattr(inner, "jaxpr", inner)
                for iv, outer in zip(ij.invars, eqn.invars):
                    if not is_literal(outer):
                        alias[iv] = outer
                walk(ij)
                for outer_ov, inner_ov in zip(eqn.outvars, ij.outvars):
                    if not is_literal(inner_ov):
                        alias[outer_ov] = inner_ov
                continue
            idx = len(entries)
            entries.append((eqn, _charge_eqn(eqn)))
            for ov in eqn.outvars:
                producer[ov] = idx
        return None

    walk(closed.jaxpr)
    return entries, producer, resolve


def _action_pieces(entries, producer, resolve, closed, arity: int):
    """Per-action root vars from the successor stack's action-axis
    concatenate (the footprint pass's decomposition); None when the
    kernel does not decompose (slot-multiset twins)."""
    out_var = resolve(closed.jaxpr.outvars[0])
    ndim = len(
        getattr(getattr(closed.jaxpr.outvars[0], "aval", None), "shape", ())
        or ()
    )
    if ndim < 2:
        return None
    axis = ndim - 2

    def walk_back(v, depth=8):
        for _ in range(depth):
            v = resolve(v)
            idx = producer.get(v)
            if idx is None:
                return v
            eqn = entries[idx][0]
            if eqn.primitive.name not in (
                "reshape", "copy", "convert_element_type",
            ):
                return v
            v = eqn.invars[0]
        return v

    def flatten(v, depth=6):
        v = walk_back(v)
        idx = producer.get(resolve(v))
        if idx is None:
            return None
        eqn = entries[idx][0]
        if eqn.primitive.name != "concatenate" \
                or eqn.params.get("dimension") != axis:
            return None
        pieces = []
        for p in eqn.invars:
            shape = tuple(
                getattr(getattr(p, "aval", None), "shape", ()) or ()
            )
            n = int(shape[axis]) if axis < len(shape) else 1
            sub = flatten(p, depth - 1) if depth > 0 and not is_literal(p) \
                else None
            if sub is not None:
                pieces.extend(sub)
            else:
                pieces.extend([p] * n)
        return pieces

    pieces = flatten(out_var)
    if pieces is None and arity == 1:
        pieces = [out_var]
    if pieces is None or len(pieces) != arity:
        return None
    return pieces


def action_costs(closed, arity: int) -> Optional[list]:
    """Per-action ``{flops, bytes}`` attribution of the expand kernel:
    eqns reachable from exactly one action's successor piece charge to
    it; eqns feeding several actions charge to the trailing ``shared``
    entry (guard-only eqns, reachable from no piece, are out of scope —
    the successor stack is what decomposes).  None when the stack does
    not decompose (JX302 twins)."""
    entries, producer, resolve = _flatten_entries(closed)
    pieces = _action_pieces(entries, producer, resolve, closed, arity)
    if pieces is None:
        return None
    # transitive producer closure per action (memoized per entry)
    reach_memo: dict = {}

    def reach(idx: int) -> frozenset:
        cached = reach_memo.get(idx)
        if cached is not None:
            return cached
        reach_memo[idx] = frozenset()  # cycle guard (none expected)
        eqn = entries[idx][0]
        out = {idx}
        for v in eqn.invars:
            if is_literal(v):
                continue
            p = producer.get(resolve(v))
            if p is not None:
                out |= reach(p)
        result = frozenset(out)
        reach_memo[idx] = result
        return result

    per_action: list = []
    owner: dict = {}
    for a, piece in enumerate(pieces):
        p = producer.get(resolve(piece))
        idxs = reach(p) if p is not None else frozenset()
        per_action.append(idxs)
        for i in idxs:
            owner[i] = a if i not in owner else -1  # -1 = shared
    out = []
    for a in range(arity):
        fl = by = 0
        for i in per_action[a]:
            if owner.get(i) == a:
                c = entries[i][1]
                fl += c.flops
                by += c.bytes_total
        out.append({"action": a, "flops": int(fl), "bytes": int(by)})
    fl = by = 0
    for i, (_, c) in enumerate(entries):
        if owner.get(i) == -1:
            fl += c.flops
            by += c.bytes_total
    out.append({"action": "shared", "flops": int(fl), "bytes": int(by)})
    return out


# ---------------------------------------------------------------------------
# XLA reconciliation (the memory ledger's memory_analysis() discipline)
# ---------------------------------------------------------------------------


def xla_cost(fn: Callable, avals) -> Optional[dict]:
    """``compiled.cost_analysis()`` flops / bytes-accessed for ``fn`` at
    ``avals``, normalized across the list-vs-dict API generations; None
    when the backend does not expose the analysis (never crash — the
    CPU-degradation contract)."""
    import jax

    try:
        ca = jax.jit(fn).lower(*avals).compile().cost_analysis()
    except Exception:  # noqa: BLE001 - absent/unsupported backend
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, name in (("flops", "flops"), ("bytes accessed", "bytes")):
        v = ca.get(key)
        if v is not None:
            try:
                out[name] = int(v)
            except (TypeError, ValueError):
                continue
    return out or None


def reconcile_stage(stage: StageCost, xla: Optional[dict],
                    bytes_lo: float = BYTES_LO) -> dict:
    """One stage's analytic-vs-XLA verdict under the pinned bands.

    ``bytes_lo=0`` exempts the lower byte bound — the ``queue`` stage's
    documented exemption: XLA's cost model charges a dynamic-update-
    slice at FULL-buffer scale (donated or not — measured on this
    backend), so its number grows with ``qcap/batch`` without bound,
    while the walk charges the moved window — the roofline-correct
    traffic, and what a donated in-place engine carry actually pays."""
    out: dict = {
        "analytic_flops": int(stage.flops),
        "analytic_bytes": int(stage.bytes_total),
    }
    if not xla:
        out["ok"] = True  # no XLA analysis on this backend: nothing to
        out["xla"] = None  # reconcile against (pinned never-crash)
        return out
    problems = []
    xf, xb = xla.get("flops"), xla.get("bytes")
    out["xla_flops"], out["xla_bytes"] = xf, xb
    if xf:
        ratio = stage.flops / xf
        out["flops_ratio"] = round(ratio, 4)
        if not (1.0 / FLOPS_BAND <= ratio <= FLOPS_BAND):
            problems.append(
                f"flops ratio {ratio:.3f} outside [{1 / FLOPS_BAND:.3f}, "
                f"{FLOPS_BAND}]"
            )
    if xb:
        ratio = stage.bytes_total / xb
        out["bytes_ratio"] = round(ratio, 4)
        if not (bytes_lo <= ratio <= BYTES_HI):
            problems.append(
                f"bytes ratio {ratio:.3f} outside [{bytes_lo}, {BYTES_HI}]"
            )
    out["ok"] = not problems
    if problems:
        out["problems"] = problems
    return out


# ---------------------------------------------------------------------------
# MXU-candidate ranking (JX4xx)
# ---------------------------------------------------------------------------

_RECAST = {
    "gather": (
        "JX400",
        "blocked one-hot x table matmul (BLEST membership-probe recast: "
        "a [B, K] one-hot selector against the [K, V] table block)",
    ),
    "scatter": (
        "JX400",
        "blocked scatter-as-matmul accumulate (one-hot^T x updates onto "
        "the table block)",
    ),
    "sort": (
        "JX401",
        "bitonic / blocked compare-exchange stages (the MXU-shaped "
        "dedup-rank move)",
    ),
}


# landed escape hatches, the JX305 pattern: (stage, op_class) whose
# recast SHIPPED as an --mxu component.  Pre-flag, the JX400/JX401
# finding names the hatch; with the component armed, the finding goes
# SILENT (the recast is live — re-advertising it would be noise), both
# pinned by test.  The mxu-config attribute names the component that
# retires the site.
_LANDED_HATCH = {
    ("dedup-insert", "gather"): (
        "probe",
        "--mxu / CheckerBuilder.mxu() (BLEST one-hot probe; "
        "docs/roofline.md)",
    ),
    ("expand", "scatter"): (
        "coalesce",
        "--mxu / CheckerBuilder.mxu() (expand-scatter coalescing; "
        "docs/roofline.md)",
    ),
}


def _landed_hatch(stage: str, op_class: str, mxu=None):
    """``(armed, hatch_text)`` for a ranked site: ``hatch_text`` is the
    landed escape hatch (None when no recast shipped for the site),
    ``armed`` whether the resolving component is ON in ``mxu``."""
    entry = _LANDED_HATCH.get((stage, op_class))
    if entry is None:
        return False, None
    component, text = entry
    armed = bool(mxu is not None and getattr(mxu, component, False))
    return armed, text


def mxu_candidates(stages: dict, mxu=None) -> list:
    """Gather/scatter/sort sites whose shapes admit a blocked-matmul
    recast, ranked by charged bytes (the list docs/roofline.md's
    hot-spot table is generated from).  Sites whose landed recast
    component is armed in ``mxu`` carry ``recast_landed: true`` — the
    findings layer goes silent on them (the JX305 pattern)."""
    out = []
    for sname, stage in stages.items():
        for (prim, shape, op_shape), site in stage.movement.items():
            total = site.bytes_total * 1  # per traced call
            if total < MXU_MIN_BYTES:
                continue
            rule, recast = _RECAST[site.op_class]
            armed, hatch = _landed_hatch(sname, site.op_class, mxu)
            entry = {
                "stage": sname,
                "op": prim,
                "op_class": site.op_class,
                "shape": list(shape),
                "operand_shape": list(op_shape),
                "count": int(site.count),
                "bytes": int(total),
                "flops": int(site.flops),
                "rule": rule,
                "recast": recast,
            }
            if hatch:
                entry["escape_hatch"] = hatch
            if armed:
                entry["recast_landed"] = True
            out.append(entry)
    out.sort(key=lambda c: (-c["bytes"], c["stage"], c["op"]))
    for rank, c in enumerate(out, 1):
        c["rank"] = rank
    return out[:_MXU_TOP]


def mxu_findings(candidates: list, stages: dict) -> list:
    """The ranking as ``JX4xx`` informational audit findings.  A site
    whose recast flag is armed (``recast_landed``) emits NO finding —
    the hatch is taken; pre-flag, the message names it (JX305's
    actionable-pointer pattern, pinned by test)."""
    findings = []
    for c in candidates:
        if c.get("recast_landed"):
            continue
        findings.append(AuditFinding(
            c["rule"], Severity.INFO, f"stage:{c['stage']}",
            f"MXU candidate #{c['rank']}: {c['op']} moving "
            f"{c['bytes']} bytes/step (shape {c['shape']}"
            + (
                f" over operand {c['operand_shape']}"
                if c["operand_shape"] else ""
            )
            + f", x{c['count']}) admits a {c['recast']}"
            + (
                f" — landed escape hatch: {c['escape_hatch']}"
                if c.get("escape_hatch") else ""
            ),
        ))
    if candidates:
        by_stage: dict = {}
        for c in candidates:
            by_stage[c["stage"]] = by_stage.get(c["stage"], 0) + c["bytes"]
        top_stage = max(by_stage, key=by_stage.get)
        total = sum(
            s.bytes_total for s in stages.values()
        ) or 1
        findings.append(AuditFinding(
            "JX402", Severity.INFO, "costmodel",
            f"top MXU-candidate stage is '{top_stage}' with "
            f"{by_stage[top_stage]} candidate bytes/step "
            f"({100.0 * by_stage[top_stage] / total:.1f}% of all charged "
            "bytes) — the tensor-core round's first target "
            "(docs/roofline.md)",
        ))
    return findings


# ---------------------------------------------------------------------------
# the model report + engine entry points
# ---------------------------------------------------------------------------


@dataclass
class CostReport:
    """The full cost ledger of one engine configuration."""

    engine: str
    shapes: dict  # batch/cap/qcap/cand/... (JSON-safe ints)
    stages: dict  # name -> StageCost
    reconciliation: dict  # name -> reconcile_stage verdict (+ "ok")
    actions: Optional[list]  # per-action attribution, or None (JX302)
    candidates: list  # mxu_candidates ranking
    findings: list = field(default_factory=list)

    @property
    def total_flops(self) -> int:
        return sum(s.flops for s in self.stages.values())

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes_total for s in self.stages.values())

    def static_block(self) -> dict:
        """The DETERMINISTIC block (run report / regress contract): the
        analytic walk only — no XLA numbers (backend-specific), no
        device spec (machine-local).  Byte-stable for a fixed
        model/config/jax."""
        totals = {
            "flops": int(self.total_flops),
            "bytes": int(self.total_bytes),
        }
        if totals["bytes"]:
            totals["intensity"] = round(
                totals["flops"] / totals["bytes"], 6
            )
        out = {
            "v": COSTMODEL_V,
            "engine": self.engine,
            **{k: int(v) for k, v in sorted(self.shapes.items())},
            "stages": {
                name: s.to_json() for name, s in self.stages.items()
            },
            "totals": totals,
            "mxu_candidates": [dict(c) for c in self.candidates],
        }
        if self.actions is not None:
            out["actions"] = [dict(a) for a in self.actions]
        return out

    def recon_block(self) -> dict:
        """The reconciliation verdict (live surfaces + the bench/regress
        artifact; XLA's numbers are backend-specific and stay out of the
        deterministic block)."""
        ok = all(
            v.get("ok", False)
            for k, v in self.reconciliation.items()
            if isinstance(v, dict)
        )
        return {
            "ok": ok,
            "bands": {
                "flops": [round(1.0 / FLOPS_BAND, 4), FLOPS_BAND],
                "bytes": [BYTES_LO, BYTES_HI],
            },
            "stages": {
                k: dict(v) for k, v in self.reconciliation.items()
            },
        }


def _trace(fn, avals):
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax.make_jaxpr(lambda *a: fn(*a))(*avals)


def _stage_fns(tensor, cap: int, qcap: int, batch: int, cand: int,
               sym: bool, mxu=None):
    """``name -> (fn, avals)`` for the five wavefront pipeline stages at
    these capacities — the same kernels (and shapes) one engine step
    runs, traced standalone so each stage's costs attribute cleanly.

    The insert/queue wiring here MIRRORS ``wavefront._build_engine``'s
    default path (window=batch, compact=eff_cand, qalloc=qcap+m) by
    hand, not derived from ``parallel/carry.carry_avals`` as the memory ledger's
    specs are: the engine's step is one fused jaxpr,
    and standalone stage kernels are the whole point of per-stage
    attribution.  The XLA reconciliation checks each stage against its
    OWN compile, so a drift against the engine would NOT trip it —
    when touching ``_build_engine``'s insert or queue-append wiring,
    update this mirror with it.

    ``mxu`` (``ops/mxu.MxuConfig``, None = off) mirrors the engine's
    MXU-recast knobs (docs/roofline.md "Executing the hot-spot list"):
    ``coalesce`` traces the twin's coalesced expand kernel and ``probe``
    passes ``probe_dot`` into the insert mirror — so the ledger charges
    exactly what the flagged engine program moves.  The queue mirror's
    append is the engine's ``append_novel``: ``qchunk``-row chunks gated
    on a traced ``n_new`` (the walk charges a loop body once, so the
    charged bytes are one chunk's - and, where a row is wider than a
    word, the payload's one ``cand``-row gather before the loop -
    whatever the flags)."""
    import jax
    import jax.numpy as jnp

    from ..ops.buckets import bucket_insert
    from ..ops.hashing import row_hash
    from ..ops.mxu import coalesced_step_fn
    from ..parallel.wavefront import append_novel

    width, arity = tensor.width, tensor.max_actions
    m = batch * arity
    eff_cand = min(cand, m) if cand else m
    qalloc = qcap + m
    probe_dot = bool(mxu is not None and mxu.probe)
    qchunk = min(batch, eff_cand)  # the append's chunk (_build_engine)
    step_rows_fn = coalesced_step_fn(tensor, mxu)
    sds = jax.ShapeDtypeStruct
    rows = sds((batch, width), jnp.uint64)
    succ = sds((batch, arity, width), jnp.uint64)

    def hash_fn(s):
        krows = tensor.representative_rows(s) if sym else s
        return row_hash(krows)

    def insert_fn(tfp, tpl, cfp, cpar):
        return bucket_insert(
            tfp, tpl, cfp, cpar, window=batch, generation_order=sym,
            compact=eff_cand, probe_dot=probe_dot,
        )

    def queue_fn(qrows, qfp, qebits, qdepth, head, tail, crows, cfp,
                 cebt, cdep, sel, n_new):
        # the engine's per-step queue traffic: pop one batch window,
        # append the novel-compacted candidates at the tail in chunks
        out_rows = jax.lax.dynamic_slice(
            qrows, (head, jnp.int32(0)), (batch, width)
        )
        out_fp = jax.lax.dynamic_slice(qfp, (head,), (batch,))
        out_eb = jax.lax.dynamic_slice(qebits, (head,), (batch,))
        out_dp = jax.lax.dynamic_slice(qdepth, (head,), (batch,))
        (qrows, qfp, qebits, qdepth), _ = append_novel(
            (qrows, qfp, qebits, qdepth), tail, sel, n_new,
            (crows, cfp, cebt, cdep), qchunk,
        )
        return (out_rows, out_fp, out_eb, out_dp, qrows, qfp, qebits,
                qdepth)

    def expand_fn(r):
        s, valid = step_rows_fn(r)
        if getattr(tensor, "has_boundary", False):
            valid = valid & tensor.boundary_rows(s)
        return s, valid

    queue_avals = (
        sds((qalloc, width), jnp.uint64), sds((qalloc,), jnp.uint64),
        sds((qalloc,), jnp.uint32), sds((qalloc,), jnp.uint32),
        sds((), jnp.int32), sds((), jnp.int32),
        sds((m, width), jnp.uint64), sds((m,), jnp.uint64),
        sds((m,), jnp.uint32), sds((m,), jnp.uint32),
        sds((eff_cand,), jnp.int32), sds((), jnp.int32),
    )
    return {
        "property": (tensor.property_masks, (rows,)),
        "expand": (expand_fn, (rows,)),
        "hash": (hash_fn, (succ,)),
        "dedup-insert": (
            insert_fn,
            (
                sds((cap,), jnp.uint64), sds((cap,), jnp.uint64),
                sds((m,), jnp.uint64), sds((m,), jnp.uint64),
            ),
        ),
        "queue": (queue_fn, queue_avals),
    }


def _cost_cache(tensor) -> Optional[dict]:
    cache = getattr(tensor, "_cost_cache", None)
    if cache is None:
        cache = {}
        try:
            tensor._cost_cache = cache
        except Exception:  # noqa: BLE001 - __slots__ twins
            return None
    return cache


def wavefront_costs(
    tensor, cap: int, qcap: int, batch: int,
    cand: Optional[int] = None, *, sym: bool = False,
    reconcile: bool = True, mxu=None,
) -> Optional[CostReport]:
    """The wavefront engine's full cost ledger at these capacities
    (cached on the twin — kernels cannot change under a fixed twin).
    ``mxu`` mirrors the engine's MXU-recast knobs into the stage
    kernels (see ``_stage_fns``), so a flagged run's ledger prices the
    flagged program — the before/after evidence ``regress.py --mxu``
    gates on.  Returns None when the twin has no usable width/arity or
    a kernel does not trace (the structural audit already reports
    those)."""
    width = getattr(tensor, "width", None)
    arity = getattr(tensor, "max_actions", None)
    if not isinstance(width, int) or not isinstance(arity, int):
        return None
    cand = cand or max(4 * batch, 4096)
    key = ("wavefront", cap, qcap, batch, min(cand, batch * arity),
           bool(sym), bool(reconcile))
    if mxu is not None:
        key = key + (tuple(mxu),)
    cache = _cost_cache(tensor)
    if cache is not None and key in cache:
        return cache[key]
    try:
        # init_rows first: the documented outside-any-trace moment where
        # compiled twins populate their device-constant caches (the
        # footprint/run_jaxpr_audit discipline — constants materialized
        # inside a make_jaxpr trace would leak tracers into the cache)
        np.asarray(tensor.init_rows())
        fns = _stage_fns(tensor, cap, qcap, batch, cand, sym, mxu=mxu)
    except Exception:  # noqa: BLE001 - JX000 covers trace failures
        return None
    stages: dict = {}
    recon: dict = {}
    expand_closed = None
    for name, (fn, avals) in fns.items():
        try:
            closed = _trace(fn, avals)
        except Exception:  # noqa: BLE001 - a kernel that does not trace
            continue  # is the structural audit's finding, not ours
        if name == "expand":
            expand_closed = closed
        stages[name] = walk_jaxpr(closed, name)
        if reconcile:
            recon[name] = reconcile_stage(
                stages[name], xla_cost(fn, avals),
                bytes_lo=0.0 if name == "queue" else BYTES_LO,
            )
    if not stages:
        return None
    actions = None
    if expand_closed is not None:
        try:
            actions = action_costs(expand_closed, arity)
        except Exception:  # noqa: BLE001 - attribution only, never fatal
            actions = None
    # landed-recast bookkeeping prices what actually traced: coalesce
    # downgrades when the twin has no coalesced kernel (effective_mxu) —
    # a fallen-back component must never silence its JX400 findings
    from ..ops.mxu import effective_mxu

    candidates = mxu_candidates(stages, mxu=effective_mxu(tensor, mxu))
    out = CostReport(
        engine="wavefront",
        shapes={"batch": batch, "capacity": cap, "queue_capacity": qcap,
                "cand": min(cand, batch * arity)},
        stages=stages, reconciliation=recon, actions=actions,
        candidates=candidates,
        findings=mxu_findings(candidates, stages),
    )
    if cache is not None:
        cache[key] = out
    return out


def fold_into_report(cost: CostReport, report) -> None:
    """Merge the JX4xx findings + the summary metrics into an
    ``AuditReport`` — the ``independence.fold_into_report`` pattern:
    the audit tiers deliberately do NOT run the cost walk (it re-traces
    and compiles every pipeline kernel), so this hook exists for
    callers that want the ledger merged into a model's report (the
    verb prints the findings directly instead)."""
    report.extend(cost.findings)
    report.metrics["costmodel"] = {
        "engine": cost.engine,
        "flops": int(cost.total_flops),
        "bytes": int(cost.total_bytes),
        "stages": sorted(cost.stages),
        "mxu_candidates": len(cost.candidates),
        "reconciled": cost.recon_block()["ok"],
    }

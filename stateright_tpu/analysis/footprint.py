"""Per-action read/write footprints of a tensor model, at bit granularity.

The independence pass (``independence.py``) needs to know, for every action
family of a compiled tensor model, which packed row bits the action READS
(to compute its successor), which bits its enabledness GUARD reads, and
which bits it WRITES.  Everything else in the row is a pure copy — and pure
copies are exactly what makes two actions commute.  This module extracts
those footprints *statically* from the traced ``step_rows`` /
``property_masks`` jaxprs, reusing the walking conventions of the interval
sanitizer (``interval.py``) with a different abstract domain:

 - every traced value carries ``deps`` — a :class:`FieldSet` (per-word bit
   masks over the input row) of the input bits its VALUE may depend on,
   beyond any identity copy;
 - values derived from a single input word additionally carry an identity
   channel ``(word, shift, eq, supp)``: the value equals
   ``input_word >> shift`` on the ``eq`` bits (value positions), and only
   the ``supp`` bits can be non-zero.  ``BitPacker.get``-style extraction
   (``(rows[..., w] >> off) & mask``) and the ``set`` idiom
   (``(w & ~m) | (v & m)``) stay exact through this channel, which is what
   makes per-field write masks possible at all;
 - arrays whose LAST axis is the row-word axis are tracked per lane, so
   the engine's word-indexed write-back (``rows.at[..., w].set(v)``, a
   constant-index scatter in the jaxpr) replaces exactly one lane.

Per-action decomposition rides the model idiom: ``step_rows`` assembles
``succ`` by stacking per-action row arrays along the action axis (a
``concatenate`` in the jaxpr) and ``valid`` by stacking per-action guard
columns.  Kernels that assemble successors any other way (the compiled
actor twins' data-dependent slot/destination writes) do NOT decompose —
the extraction then reports every action with a ``TOP`` footprint, which
``independence.py`` conservatively treats as dependent-on-everything
(finding ``JX302``).  Undecidable can cost reduction, never soundness.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Optional

import numpy as np

from .interval import aval_of, is_literal, producers_of
from .report import AuditFinding, Severity

ALL64 = (1 << 64) - 1

_TRANSPARENT = ("reshape", "broadcast_in_dim", "squeeze",
                "convert_element_type", "copy", "expand_dims")


# ---------------------------------------------------------------------------
# field sets: per-word bitmasks over the input row
# ---------------------------------------------------------------------------


class FieldSet:
    """A set of input-row bits: ``{word -> bitmask}``, or TOP (unknown)."""

    __slots__ = ("masks", "top")

    def __init__(self, masks: Optional[dict] = None, top: bool = False):
        self.top = bool(top)
        self.masks: dict = {} if top or not masks else {
            w: m & ALL64 for w, m in masks.items() if m
        }

    @classmethod
    def empty(cls) -> "FieldSet":
        return cls()

    @classmethod
    def of(cls, word: int, mask: int = ALL64) -> "FieldSet":
        return cls({int(word): int(mask)})

    @classmethod
    def top_set(cls) -> "FieldSet":
        return cls(top=True)

    @property
    def is_empty(self) -> bool:
        return not self.top and not self.masks

    def union(self, other: "FieldSet") -> "FieldSet":
        if self.top or other.top:
            return FieldSet.top_set()
        out = dict(self.masks)
        for w, m in other.masks.items():
            out[w] = out.get(w, 0) | m
        return FieldSet(out)

    def minus_word_bits(self, word: int, mask: int) -> "FieldSet":
        """Remove ``mask`` bits of ``word`` (TOP stays TOP)."""
        if self.top:
            return self
        out = dict(self.masks)
        if word in out:
            out[word] &= ~mask
        return FieldSet(out)

    def intersects(self, other: "FieldSet") -> bool:
        """Conservative may-intersect: TOP intersects anything non-empty
        (and another TOP)."""
        if self.top:
            return other.top or bool(other.masks)
        if other.top:
            return bool(self.masks)
        return any(
            self.masks.get(w, 0) & m for w, m in other.masks.items()
        )

    def to_json(self) -> object:
        if self.top:
            return "top"
        return {str(w): hex(m) for w, m in sorted(self.masks.items())}

    def __repr__(self) -> str:  # debugging/report ergonomics
        return f"FieldSet({self.to_json()})"


def union_all(sets) -> FieldSet:
    out = FieldSet.empty()
    for s in sets:
        out = out.union(s)
    return out


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Info:
    """Abstract value of one scalar/lane.

    ``deps`` — non-identity input-bit dependencies of the value.
    ``word``/``shift`` — identity provenance: the value is derived from
    ``input[word] >> shift`` (None = no single-word provenance).
    ``eq`` — value-position bits where the value EQUALS
    ``input[word] >> shift`` (meaningful only with provenance).
    ``supp`` — value-position bits that can be non-zero (None = all).
    ``const`` — exact value when statically known (scalar constants).
    ``acc`` — value-position bits where the value is an OR-ACCUMULATE of
    the identity content: ``(input[word] >> shift) | f(deps)``
    (meaningful only with provenance; always disjoint from ``eq``).
    Written back to its own word, such a bit is a *monotone* write — two
    actions' accumulates commute bit-for-bit, which is what lets the
    compiled twins' saturating poison flag stay out of the conflict
    relation (``independence.py``; the per-channel kernel's
    ``_or_field`` idiom).
    """

    deps: FieldSet = field(default_factory=FieldSet.empty)
    word: Optional[int] = None
    shift: int = 0
    eq: int = 0
    supp: Optional[int] = None
    const: Optional[int] = None
    acc: int = 0

    def as_data(self) -> FieldSet:
        """Full read set when the value is consumed AS DATA (identity
        content included): the identity channel's input bits fold in."""
        out = self.deps
        if self.word is not None:
            s = ALL64 if self.supp is None else self.supp
            out = out.union(FieldSet.of(self.word, (s << self.shift) & ALL64))
        return out


TOP_INFO = Info(deps=FieldSet.top_set())


def _join(a: Info, b: Info) -> Info:
    """Join two infos (select/concat): identity survives only where both
    sides carry it, on the intersection of their eq bits.  A bit stays an
    OR-accumulate when BOTH branches keep it ``old | something`` (eq or
    acc) — ``select(p, old, old | f)`` is still ``old | (p ? f : 0)``."""
    deps = a.deps.union(b.deps)
    if (a.word is not None and a.word == b.word and a.shift == b.shift):
        supp = None if (a.supp is None or b.supp is None) else (
            a.supp | b.supp
        )
        eq = a.eq & b.eq
        safe = (a.eq | a.acc) & (b.eq | b.acc)
        return Info(deps=deps, word=a.word, shift=a.shift,
                    eq=eq, supp=supp, acc=safe & ~eq)
    return Info(deps=a.as_data().union(b.as_data()))


def _const_info(v) -> Info:
    arr = np.asarray(v)
    supp = 0
    const = None
    if arr.dtype == np.bool_:
        supp = int(bool(arr.any()))
        if arr.size == 1:
            const = int(bool(arr.reshape(-1)[0]))
    elif np.issubdtype(arr.dtype, np.integer):
        flat = arr.reshape(-1)
        if flat.size == 0:
            supp = 0
        else:
            # the FULL array: an under-approximated support would let
            # genuinely conflicting actions classify independent
            # (soundness), and the reduce is a single vectorized pass
            supp = int(np.bitwise_or.reduce(flat)) & ALL64
        if arr.size == 1:
            const = int(flat[0]) & ALL64
    else:
        return Info(supp=None)
    return Info(supp=supp, const=const)


@dataclass(frozen=True)
class AVal:
    """Abstract value of one traced array: either one collapsed
    :class:`Info`, or per-lane infos along the LAST axis (``lanes``)."""

    info: Optional[Info] = None
    lanes: Optional[tuple] = None

    @property
    def tracked(self) -> bool:
        return self.lanes is not None

    def collapse(self) -> Info:
        if self.lanes is None:
            return self.info if self.info is not None else TOP_INFO
        if not self.lanes:
            return TOP_INFO
        # join keeps the identity channel when every lane agrees on it
        # (e.g. a batch-axis broadcast mistaken for lanes); mismatching
        # lanes fold to their as_data reads inside _join
        out = self.lanes[0]
        for i in self.lanes[1:]:
            out = _join(out, i)
        return out

    def one(self) -> Info:
        return self.info if self.info is not None else self.collapse()


def _scalar(info: Info) -> AVal:
    return AVal(info=info)


TOP_AVAL = AVal(info=TOP_INFO)


# ---------------------------------------------------------------------------
# footprints
# ---------------------------------------------------------------------------


@dataclass
class ActionFootprint:
    """Static footprint of one action family (action slot)."""

    reads: FieldSet  # successor-value reads (pure copies excluded)
    writes: FieldSet  # row bits the successor may change
    guard: FieldSet  # enabledness-condition reads
    decided: bool  # False when any component collapsed to TOP
    # monotone OR-accumulate writes (``new = old | f(reads)``): commute
    # with each other, conflict with plain writes and with reads of the
    # same bits — the compiled twins' saturating poison flag
    accum: FieldSet = field(default_factory=FieldSet.empty)

    def to_json(self) -> dict:
        return {
            "reads": self.reads.to_json(),
            "writes": self.writes.to_json(),
            "guard": self.guard.to_json(),
            "accum": self.accum.to_json(),
            "decided": self.decided,
        }


@dataclass
class ConjunctInfo:
    """Per-action guard CONJUNCT decomposition — what the POR stubborn-set
    closure needs for disabled actions: a false conjunct's writer set is a
    sound *necessary enabling set* (the action cannot become enabled until
    some writer of that conjunct's read footprint fires).

    ``sets[a]`` — one FieldSet per conjunct of action ``a`` (≥ 1; the
    fallback is the whole guard as a single conjunct).
    ``leaf_idx[a]`` — ``(leaf, lane)`` references of ``a``'s conjuncts
    into the kernel's leaf outputs (``lane`` is None for a scalar ``[B]``
    leaf, else the action's lane within a ``[B, cap]`` guard BLOCK — the
    per-channel kernel stacks one guard array per channel, and lane ``k``
    is slot ``k``'s truth), or None: the single-conjunct fallback, whose
    truth is the action's enabled bit itself (a disabled action's whole
    guard is false by definition — no kernel evaluation needed).
    ``n_leaves`` — total distinct evaluable conjunct leaves.
    """

    sets: list
    leaf_idx: list
    n_leaves: int

    @property
    def max_conjuncts(self) -> int:
        return max((len(s) for s in self.sets), default=1)


@dataclass
class ModelFootprints:
    """Footprints of every action plus per-property read sets."""

    width: int
    n_actions: int
    actions: list  # list[ActionFootprint]
    prop_reads: list  # list[FieldSet], properties() order
    decomposed: bool  # per-action successor decomposition succeeded
    findings: list = field(default_factory=list)
    conjuncts: Optional[ConjunctInfo] = None

    @property
    def undecided_actions(self) -> list:
        return [i for i, a in enumerate(self.actions) if not a.decided]


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _FpInterp:
    """One forward pass over a closed jaxpr with the footprint domain.
    Mirrors ``interval.Interp``'s walking conventions (jit-call inlining via
    aliases, producer maps) with conservative TOP for anything unknown."""

    def __init__(self):
        self.env: dict = {}
        self._alias: dict = {}
        self._producers: dict = {}
        self.input_var = None

    # -- env -----------------------------------------------------------------

    def read(self, x) -> AVal:
        if is_literal(x):
            return _scalar(_const_info(x.val))
        v = self.env.get(x)
        return v if v is not None else TOP_AVAL

    def write(self, var, val: AVal) -> None:
        self.env[var] = val

    def resolve(self, var):
        seen = 0
        while not is_literal(var) and var in self._alias and seen < 32:
            var = self._alias[var]
            seen += 1
        return var

    def walk_back(self, var, prims=_TRANSPARENT, depth: int = 8):
        var = self.resolve(var)
        for _ in range(depth):
            if is_literal(var):
                return var
            eqn = self._producers.get(var)
            if eqn is None or eqn.primitive.name not in prims:
                return var
            var = self.resolve(eqn.invars[0])
        return var

    def const_of(self, x) -> Optional[int]:
        if is_literal(x):
            return _const_info(x.val).const
        v = self.env.get(x)
        return v.one().const if v is not None and v.info is not None else None

    # -- entry ---------------------------------------------------------------

    def run(self, closed, rows_var_lanes: int) -> list:
        jaxpr = closed.jaxpr
        for cv, c in zip(jaxpr.constvars, closed.consts):
            self.write(cv, _scalar(_const_info(np.asarray(c))))
        if jaxpr.invars:
            self.input_var = jaxpr.invars[0]
            self.write(
                jaxpr.invars[0],
                AVal(lanes=tuple(
                    Info(word=w, shift=0, eq=ALL64, supp=None)
                    for w in range(rows_var_lanes)
                )),
            )
        for iv in jaxpr.invars[1:]:
            self.write(iv, TOP_AVAL)
        self._run_eqns(jaxpr)
        return [self.read(ov) for ov in jaxpr.outvars]

    def _run_eqns(self, jaxpr) -> None:
        self._producers.update(producers_of(jaxpr))
        for eqn in jaxpr.eqns:
            try:
                self.eqn(eqn)
            except Exception:  # noqa: BLE001 - a rule bug degrades to TOP,
                for ov in eqn.outvars:  # never to a wrong footprint
                    self.write(ov, TOP_AVAL)

    # -- per-eqn transfer ----------------------------------------------------

    def eqn(self, eqn) -> None:
        name = eqn.primitive.name
        rule = _FP_RULES.get(name)
        ins = [self.read(x) for x in eqn.invars]
        if rule is not None:
            out = rule(self, eqn, ins)
            outs = out if isinstance(out, list) else [out]
        elif name in ("jit", "closed_call", "core_call", "custom_jvp_call",
                      "custom_vjp_call", "remat_call", "checkpoint"):
            outs = self._call(eqn, ins)
        else:
            # unknown primitive: every output depends on every input (as
            # data), lanes lost
            deps = union_all(v.collapse().as_data() for v in ins)
            outs = [_scalar(Info(deps=deps))] * len(eqn.outvars)
        for ov, val in zip(eqn.outvars, outs):
            self.write(ov, val)

    def _call(self, eqn, ins) -> list:
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is None:
            return [TOP_AVAL] * len(eqn.outvars)
        jaxpr = getattr(inner, "jaxpr", inner)
        consts = getattr(inner, "consts", ())
        for cv, c in zip(jaxpr.constvars, consts):
            self.write(cv, _scalar(_const_info(np.asarray(c))))
        for iv, outer, val in zip(jaxpr.invars, eqn.invars, ins):
            self.write(iv, val)
            if not is_literal(outer):
                self._alias[iv] = outer
        self._run_eqns(jaxpr)
        outs = []
        for outer_ov, inner_ov in zip(eqn.outvars, jaxpr.outvars):
            if not is_literal(inner_ov):
                self._alias[outer_ov] = inner_ov
            outs.append(self.read(inner_ov))
        return outs


# -- shape helpers -----------------------------------------------------------


def _shape(x) -> tuple:
    return tuple(getattr(aval_of(x), "shape", ()) or ())


def _last(x) -> int:
    s = _shape(x)
    return int(s[-1]) if s else 1


# -- rules -------------------------------------------------------------------


def _lanewise(fn):
    """Lift a binary Info rule over (AVal, AVal) with last-axis broadcast:
    lanes x lanes (equal length), lanes x scalar, scalar x scalar."""

    def rule(a: AVal, b: AVal) -> AVal:
        if a.tracked and b.tracked and len(a.lanes) == len(b.lanes):
            return AVal(lanes=tuple(
                fn(x, y) for x, y in zip(a.lanes, b.lanes)
            ))
        if a.tracked and not b.tracked:
            bi = b.one()
            return AVal(lanes=tuple(fn(x, bi) for x in a.lanes))
        if b.tracked and not a.tracked:
            ai = a.one()
            return AVal(lanes=tuple(fn(ai, y) for y in b.lanes))
        return _scalar(fn(a.one(), b.one()))

    return rule


def _data_combine(a: Info, b: Info) -> Info:
    return Info(deps=a.as_data().union(b.as_data()))


def _rule_and_info(a: Info, b: Info) -> Info:
    if a.const is not None and b.const is not None:
        v = a.const & b.const
        return Info(supp=v, const=v)
    for x, c in ((a, b.const), (b, a.const)):
        if c is None:
            continue
        supp = (ALL64 if x.supp is None else x.supp) & c
        if x.word is not None:
            # AND-with-const zeroes bits outside c: an accumulate bit
            # masked off is a plain write again, not ``old | f``
            return replace(x, eq=x.eq & c, acc=x.acc & c, supp=supp,
                           const=None)
        return Info(deps=x.deps, supp=supp)
    out = _data_combine(a, b)
    if a.supp is not None or b.supp is not None:
        # no identity survives, but the support still intersects: an AND
        # can only keep bits both operands can carry (what keeps a
        # boolean flag's support at bit 0 through ``occ & poisoned``)
        sa = ALL64 if a.supp is None else a.supp
        sb = ALL64 if b.supp is None else b.supp
        out = replace(out, supp=sa & sb)
    return out


def _rule_or_info(a: Info, b: Info) -> Info:
    if a.const is not None and b.const is not None:
        v = a.const | b.const
        return Info(supp=v, const=v)
    for x, y in ((a, b), (b, a)):
        if x.word is not None and y.word is None and y.supp is not None:
            # value | bounded-support operand: only the operand's support
            # bits stop equalling the input word (the pk.set idiom:
            # cleared | (v & mask) — v's support is the field mask).
            # Those bits become ``old | f`` — OR-accumulates, provided
            # they were still safe (eq or already-acc) before
            eq = x.eq & ~y.supp
            return Info(
                deps=x.deps.union(y.deps),
                word=x.word, shift=x.shift,
                eq=eq,
                supp=None if x.supp is None else (x.supp | y.supp),
                acc=x.acc | (x.eq & y.supp),
            )
    if (a.word is not None and a.word == b.word and a.shift == b.shift):
        sa = ALL64 if a.supp is None else a.supp
        sb = ALL64 if b.supp is None else b.supp
        eq = (a.eq & ~sb) | (b.eq & ~sa) | (a.eq & b.eq)
        # identity bits landing in non-eq output positions become reads
        leak = ((a.eq | b.eq) & ~eq) << a.shift
        deps = a.deps.union(b.deps)
        if leak:
            deps = deps.union(FieldSet.of(a.word, leak & ALL64))
        return Info(deps=deps, word=a.word, shift=a.shift, eq=eq,
                    supp=sa | sb)
    out = _data_combine(a, b)
    if a.supp is not None and b.supp is not None:
        out = replace(out, supp=a.supp | b.supp)
    return out


def _rule_xor_info(a: Info, b: Info) -> Info:
    if a.const is not None and b.const is not None:
        v = a.const ^ b.const
        return Info(supp=v, const=v)
    for x, y in ((a, b), (b, a)):
        if x.word is not None and y.word is None and y.supp is not None:
            # value ^ bounded-support operand: only the support bits flip
            # (a flipped bit is NOT an OR-accumulate — not monotone)
            return Info(
                deps=x.deps.union(y.deps),
                word=x.word, shift=x.shift,
                eq=x.eq & ~y.supp,
                supp=None if x.supp is None else (x.supp | y.supp),
                acc=x.acc & ~y.supp,
            )
    out = _data_combine(a, b)
    if a.supp is not None and b.supp is not None:
        out = replace(out, supp=a.supp | b.supp)
    return out


def _rule_shift_info(left: bool):
    def rule(a: Info, b: Info) -> Info:
        k = b.const
        if k is None or a.deps.top:
            return _data_combine(a, b)
        k = int(k)
        supp = ALL64 if a.supp is None else a.supp
        if a.word is None:
            nsupp = ((supp << k) if left else (supp >> k)) & ALL64
            return Info(deps=a.deps, supp=nsupp,
                        const=None if a.const is None else (
                            ((a.const << k) if left else (a.const >> k))
                            & ALL64))
        if left:
            if k <= a.shift:
                return Info(deps=a.deps, word=a.word, shift=a.shift - k,
                            eq=(a.eq << k) & ALL64, supp=(supp << k) & ALL64,
                            acc=(a.acc << k) & ALL64)
            # over-shift past the origin: identity content moves to higher
            # input positions than it came from — fold to data
            return Info(deps=a.as_data(), supp=(supp << k) & ALL64)
        return Info(deps=a.deps, word=a.word, shift=a.shift + k,
                    eq=a.eq >> k, supp=supp >> k, acc=a.acc >> k)

    return rule


def _rule_cmp_info(a: Info, b: Info) -> Info:
    return Info(deps=a.as_data().union(b.as_data()), supp=1)


def _rule_not_info(a: Info) -> Info:
    if a.const is not None:
        return Info(supp=(~a.const) & ALL64, const=(~a.const) & ALL64)
    return Info(deps=a.as_data())


def _rule_binop(itp, eqn, ins):
    name = eqn.primitive.name
    fn = {
        "and": _rule_and_info,
        "or": _rule_or_info,
        "xor": _rule_xor_info,
        "add": _data_combine,
        "sub": _data_combine,
        "mul": _data_combine,
        "max": _data_combine,
        "min": _data_combine,
        "div": _data_combine,
        "rem": _data_combine,
        "shift_left": _rule_shift_info(True),
        "shift_right_logical": _rule_shift_info(False),
        "shift_right_arithmetic": _rule_shift_info(False),
        "eq": _rule_cmp_info,
        "ne": _rule_cmp_info,
        "lt": _rule_cmp_info,
        "le": _rule_cmp_info,
        "gt": _rule_cmp_info,
        "ge": _rule_cmp_info,
    }[name]
    return _lanewise(fn)(ins[0], ins[1])


def _rule_not(itp, eqn, ins):
    (a,) = ins
    if a.tracked:
        return AVal(lanes=tuple(_rule_not_info(i) for i in a.lanes))
    return _scalar(_rule_not_info(a.one()))


def _rule_select(itp, eqn, ins):
    pred, cases = ins[0], ins[1:]
    out = cases[0]
    for c in cases[1:]:
        out = _lanewise(_join)(out, c)
    pdeps = pred.collapse().as_data()
    if pdeps.is_empty:
        return out
    if out.tracked:
        return AVal(lanes=tuple(
            replace(i, deps=i.deps.union(pdeps)) for i in out.lanes
        ))
    i = out.one()
    return _scalar(replace(i, deps=i.deps.union(pdeps)))


def _rule_slice(itp: _FpInterp, eqn, ins):
    (a,) = ins
    shape = _shape(eqn.invars[0])
    starts = eqn.params.get("start_indices", ())
    limits = eqn.params.get("limit_indices", ())
    strides = eqn.params.get("strides") or (1,) * len(shape)
    if a.tracked and shape and len(starts) == len(shape):
        lo, hi, st = starts[-1], limits[-1], strides[-1]
        lanes = a.lanes[lo:hi:st]
        if len(lanes) == _last(eqn.outvars[0]):
            return AVal(lanes=lanes)
    return _scalar(a.collapse())


def _rule_squeeze(itp, eqn, ins):
    (a,) = ins
    dims = eqn.params.get("dimensions", ())
    in_ndim = len(_shape(eqn.invars[0]))
    if a.tracked and (in_ndim - 1) in dims:
        # the (width-1) lane axis is squeezed away: a single-lane scalar
        if len(a.lanes) == 1:
            return _scalar(a.lanes[0])
        return _scalar(a.collapse())
    if a.tracked and _last(eqn.outvars[0]) == len(a.lanes):
        return a  # lane axis survives
    return _scalar(a.collapse()) if a.tracked else a


def _rule_broadcast(itp, eqn, ins):
    (a,) = ins
    bdims = eqn.params.get("broadcast_dimensions", ())
    out_ndim = len(_shape(eqn.outvars[0]))
    n_out = _last(eqn.outvars[0])
    if a.tracked:
        if bdims and bdims[-1] == out_ndim - 1 and len(a.lanes) == n_out:
            return a  # lane axis preserved
        return _scalar(a.collapse())
    # a scalar broadcast: every output lane carries the same info
    return AVal(lanes=tuple([a.one()] * n_out)) if n_out >= 1 else a


def _rule_reshape(itp, eqn, ins):
    (a,) = ins
    if a.tracked and _last(eqn.outvars[0]) == len(a.lanes):
        in_shape, out_shape = _shape(eqn.invars[0]), _shape(eqn.outvars[0])
        if (int(np.prod(in_shape or (1,))) // max(len(a.lanes), 1)
                == int(np.prod(out_shape or (1,))) // max(len(a.lanes), 1)):
            return a
    return _scalar(a.collapse()) if a.tracked else a


def _rule_convert(itp, eqn, ins):
    return ins[0]


def _rule_concat(itp, eqn, ins):
    dim = eqn.params.get("dimension", 0)
    out_ndim = len(_shape(eqn.outvars[0]))
    if dim == out_ndim - 1:
        lanes = []
        for v, x in zip(ins, eqn.invars):
            n = _last(x)
            if v.tracked and len(v.lanes) == n:
                lanes.extend(v.lanes)
            else:
                lanes.extend([v.collapse()] * n)
        return AVal(lanes=tuple(lanes))
    # non-last-axis concat (the action stack): sound per-lane join; the
    # per-action decomposition walks back through this eqn separately
    out = ins[0]
    for v in ins[1:]:
        out = _lanewise(_join)(out, v)
    return out


def _rule_scatter(itp: _FpInterp, eqn, ins):
    """The word write-back: ``rows.at[..., w].set(v)`` traces as a scatter
    with a constant scatter index onto the last axis.  Recognized form
    replaces exactly one lane; anything else collapses (data-dependent
    writes cannot keep per-field footprints)."""
    operand, updates = ins[0], ins[2] if len(ins) > 2 else TOP_AVAL
    dnums = eqn.params.get("dimension_numbers")
    sdims = tuple(getattr(dnums, "scatter_dims_to_operand_dims", ()) or ())
    op_ndim = len(_shape(eqn.invars[0]))
    idx_src = itp.walk_back(eqn.invars[1])
    idx_const = None
    if is_literal(idx_src):
        idx_const = _const_info(idx_src.val).const
    else:
        prod = itp._producers.get(idx_src)
        if prod is not None and prod.primitive.name == "broadcast_in_dim" \
                and is_literal(prod.invars[0]):
            idx_const = _const_info(prod.invars[0].val).const
    if (operand.tracked and sdims == (op_ndim - 1,)
            and idx_const is not None
            and 0 <= idx_const < len(operand.lanes)):
        lanes = list(operand.lanes)
        lanes[idx_const] = updates.collapse()
        return AVal(lanes=tuple(lanes))
    # unknown target lane: every lane may have been overwritten
    upd = updates.collapse().as_data()
    if operand.tracked:
        return AVal(lanes=tuple(
            Info(deps=i.as_data().union(upd)) for i in operand.lanes
        ))
    return _scalar(Info(deps=operand.collapse().as_data().union(upd)))


def _rule_gather(itp, eqn, ins):
    """Table lookups (``table[idx]``): the output's support is bounded by
    the TABLE's support — every gathered element is one of its entries.
    An all-zero table (a factored predicate that is constant-False for
    this actor) therefore yields a CONSTANT zero with no reads at all,
    which is what lets ``exists_actor(lambda i, s: i == K and ...)``
    read only actor K's field instead of every actor's."""
    operand = ins[0].collapse()
    idx = ins[1].collapse() if len(ins) > 1 else TOP_INFO
    if (operand.supp == 0 and operand.deps.is_empty):
        return _scalar(Info(supp=0, const=0))
    return _scalar(Info(
        deps=operand.as_data().union(idx.as_data()),
        supp=operand.supp,
    ))


def _rule_reduce(itp, eqn, ins):
    return _scalar(Info(deps=ins[0].collapse().as_data()))


def _rule_iota(itp, eqn, ins):
    return _scalar(Info(supp=None))


def _rule_transpose(itp, eqn, ins):
    (a,) = ins
    perm = eqn.params.get("permutation", ())
    in_ndim = len(_shape(eqn.invars[0]))
    if a.tracked and perm and perm[-1] == in_ndim - 1:
        return a
    return _scalar(a.collapse()) if a.tracked else a


_FP_RULES = {
    "and": _rule_binop, "or": _rule_binop, "xor": _rule_binop,
    "add": _rule_binop, "sub": _rule_binop, "mul": _rule_binop,
    "max": _rule_binop, "min": _rule_binop, "div": _rule_binop,
    "rem": _rule_binop,
    "shift_left": _rule_binop,
    "shift_right_logical": _rule_binop,
    "shift_right_arithmetic": _rule_binop,
    "eq": _rule_binop, "ne": _rule_binop, "lt": _rule_binop,
    "le": _rule_binop, "gt": _rule_binop, "ge": _rule_binop,
    "not": _rule_not,
    "select_n": _rule_select,
    "slice": _rule_slice,
    "squeeze": _rule_squeeze,
    "broadcast_in_dim": _rule_broadcast,
    "reshape": _rule_reshape,
    "expand_dims": _rule_reshape,
    "convert_element_type": _rule_convert,
    "copy": _rule_convert,
    "stop_gradient": _rule_convert,
    "concatenate": _rule_concat,
    "gather": _rule_gather,
    "scatter": _rule_scatter,
    "transpose": _rule_transpose,
    "reduce_sum": _rule_reduce, "reduce_max": _rule_reduce,
    "reduce_min": _rule_reduce, "reduce_and": _rule_reduce,
    "reduce_or": _rule_reduce, "argmax": _rule_reduce,
    "argmin": _rule_reduce, "cumsum": _rule_reduce,
    "iota": _rule_iota,
}


# ---------------------------------------------------------------------------
# per-action decomposition + the driver
# ---------------------------------------------------------------------------


def _flatten_stack(itp: _FpInterp, var, axis: int, depth: int = 6) -> list:
    """Flatten nested ``concatenate``s along ``axis`` into per-slot piece
    vars; a piece of axis-size k that is not itself a concat contributes k
    copies of itself.  Returns None when ``var`` is not a concat at all."""
    var = itp.walk_back(var, ("reshape", "copy", "convert_element_type"))
    eqn = itp._producers.get(var)
    if eqn is None or eqn.primitive.name != "concatenate" \
            or eqn.params.get("dimension") != axis:
        return None
    out = []
    for piece in eqn.invars:
        n = _shape(piece)[axis] if axis < len(_shape(piece)) else 1
        sub = (
            _flatten_stack(itp, itp.resolve(piece), axis, depth - 1)
            if depth > 0 and not is_literal(piece)
            else None
        )
        if sub is not None:
            out.extend(sub)
        else:
            out.extend([piece] * int(n))
    return out


def _action_footprint_from_lanes(lanes, guard: FieldSet) -> ActionFootprint:
    """Writes/reads of one action's successor row from its lane infos."""
    writes = FieldSet.empty()
    accum = FieldSet.empty()
    reads = FieldSet.empty()
    decided = not guard.top
    for w, info in enumerate(lanes):
        if info.word == w and info.shift == 0:
            dirty = (~info.eq) & ALL64
            accb = info.acc & dirty
            plain = dirty & ~accb
            if plain:
                writes = writes.union(FieldSet.of(w, plain))
            if accb:
                accum = accum.union(FieldSet.of(w, accb))
            reads = reads.union(info.deps)
            if info.deps.top:
                decided = False
        else:
            # the lane is not a recognizable update of its own word:
            # conservatively a full write fed by everything it touches
            writes = writes.union(FieldSet.of(w, ALL64))
            reads = reads.union(info.as_data())
            if info.as_data().top:
                decided = False
    if writes.top or reads.top:
        decided = False
    return ActionFootprint(reads=reads, writes=writes, guard=guard,
                           decided=decided, accum=accum)


def _trace(fn, avals):
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax.make_jaxpr(lambda *a: fn(*a))(*avals)


# -- guard-conjunct extraction ----------------------------------------------

_MAX_CONJUNCTS = 6  # per action; deeper and-trees fall back to one conjunct


def _flatten_stack_tl(producers_tl: dict, var, axis: int,
                      depth: int = 6) -> Optional[list]:
    """Top-level-only variant of :func:`_flatten_stack`: walks transparent
    prims and nested concatenates through TOP-LEVEL eqns only, so the
    returned piece vars are all evaluable in the top-level jaxpr scope."""
    for _ in range(8):
        if is_literal(var):
            return None
        eqn = producers_tl.get(var)
        if eqn is None or eqn.primitive.name not in (
            "reshape", "copy", "convert_element_type"
        ):
            break
        var = eqn.invars[0]
    eqn = producers_tl.get(var) if not is_literal(var) else None
    if eqn is None or eqn.primitive.name != "concatenate" \
            or eqn.params.get("dimension") != axis:
        return None
    out = []
    for piece in eqn.invars:
        n = _shape(piece)[axis] if axis < len(_shape(piece)) else 1
        sub = (
            _flatten_stack_tl(producers_tl, piece, axis, depth - 1)
            if depth > 0 else None
        )
        if sub is not None:
            out.extend(sub)
        else:
            out.extend([piece] * int(n))
    return out


def _walk_tl(producers_tl: dict, var, depth: int = 8):
    """Walk transparent shape-only prims through top-level eqns."""
    for _ in range(depth):
        if is_literal(var):
            return var
        eqn = producers_tl.get(var)
        if eqn is None or eqn.primitive.name not in _TRANSPARENT:
            return var
        var = eqn.invars[0]
    return var


def _and_leaves(producers_tl: dict, var, depth: int = 16) -> Optional[list]:
    """Leaves of the boolean and-tree rooted at ``var`` (top-level vars
    only); None when the tree is degenerate (literal root)."""
    var = _walk_tl(producers_tl, var)
    if is_literal(var):
        return None
    eqn = producers_tl.get(var)
    if (depth > 0 and eqn is not None and eqn.primitive.name == "and"
            and np.dtype(getattr(aval_of(var), "dtype", np.bool_))
            == np.bool_):
        out = []
        for x in eqn.invars:
            sub = _and_leaves(producers_tl, x, depth - 1)
            if sub is None:
                return None
            out.extend(sub)
        return out
    return [var]


def _guard_vars(closed, producers_tl: dict, arity: int) -> Optional[list]:
    """Per-action ``(guard var, lane)`` pairs from the ``valid`` output's
    action-axis stack (top-level walk); None when it does not decompose.
    A ``[B, cap]`` stack piece covers ``cap`` consecutive actions (the
    per-channel kernel's one-guard-array-per-channel idiom): each gets
    the same var with its lane index within the run."""
    vout = closed.jaxpr.outvars[1]
    ndim = len(_shape(vout))
    pieces = _flatten_stack_tl(producers_tl, vout, ndim - 1)
    if pieces is None or len(pieces) != arity:
        return None
    out = []
    prev, lane = None, 0
    for p in pieces:
        v = _walk_tl(producers_tl, p)
        lane = lane + 1 if (prev is not None and v is prev) else 0
        prev = v
        out.append((v, lane))
    return out


def _conjunct_info(itp: _FpInterp, closed, arity: int,
                   guards: list) -> ConjunctInfo:
    """Assemble :class:`ConjunctInfo` from the traced kernel: the SAME
    leaf selection as :func:`_leaf_vars_of` (one implementation — the
    kernel builder compares its re-derived indices against these, and a
    divergence between two copies of the walk would silently demote
    every run to the imprecise fallback), plus the per-leaf read
    footprints; whole-guard single-conjunct fallback where no and-tree
    extracts.  A laned reference reads the LANE's footprint when the
    leaf is tracked — slot ``k``'s occupancy conjunct reads one region
    word, not the whole region."""

    def conjunct_set(i, ln):
        av = itp.read(leaves[i])
        if ln is not None and av.tracked and ln < len(av.lanes):
            return av.lanes[ln].as_data()
        return av.collapse().as_data()

    leaves, leaf_idx = _leaf_vars_of(closed, arity)
    sets = [
        [guards[a]] if idx is None
        else [conjunct_set(i, ln) for (i, ln) in idx]
        for a, idx in enumerate(leaf_idx)
    ]
    return ConjunctInfo(sets=sets, leaf_idx=leaf_idx,
                        n_leaves=len(leaves))


def _leaf_vars_of(closed, arity: int) -> tuple:
    """(ordered leaf vars, per-action ``(leaf, lane)`` indices) for
    kernel building — re-derivable at any batch size; the derivation is
    deterministic for a deterministic trace (the JX104 retrace-stability
    contract).  A ``[B, cap]`` leaf (the per-channel guard-block idiom)
    carries one lane per action of its block; a ``[B]`` leaf applies to
    the whole block (lane None)."""
    producers_tl = producers_of(closed.jaxpr)
    gvars = _guard_vars(closed, producers_tl, arity)
    leaves: list = []
    leaf_pos: dict = {}
    idx: list = []
    for a in range(arity):
        if gvars is None or is_literal(gvars[a][0]):
            idx.append(None)
            continue
        gv, lane = gvars[a]
        lv = _and_leaves(producers_tl, gv)
        if not lv or len(lv) > _MAX_CONJUNCTS or any(
            is_literal(v) for v in lv
        ):
            idx.append(None)
            continue
        cidx = []
        for v in lv:
            sh = _shape(v)
            if len(sh) == 1:
                ln = None
            elif len(sh) == 2 and lane < sh[-1]:
                ln = lane
            else:  # a shape the kernel cannot index per action
                cidx = None
                break
            if v not in leaf_pos:
                leaf_pos[v] = len(leaves)
                leaves.append(v)
            cidx.append((leaf_pos[v], ln))
        idx.append(cidx)
    return leaves, idx


def conjunct_eval_fn(tensor):
    """A batch-size-polymorphic evaluator of the guard-conjunct leaves:
    ``fn(rows[B, W]) -> [bool[B] | bool[B, cap], ...]`` — the raw leaf
    arrays, indexed by the plan's ``(leaf, lane)`` conjunct references —
    or None when the model has no evaluable leaves.  The step kernel is
    re-traced per batch size and the leaf outputs are exposed as jaxpr
    outputs; under ``jit`` XLA dead-code-eliminates the successor
    computation, so the evaluation costs only the guard bit-ops
    themselves.  Cached per batch size on the twin."""
    import jax
    import jax.numpy as jnp

    fp = extract_footprints(tensor)
    if fp is None or fp.conjuncts is None or fp.conjuncts.n_leaves == 0:
        return None
    expect_idx = fp.conjuncts.leaf_idx
    cache: dict = getattr(tensor, "_conjunct_fn_cache", None)
    if cache is None:
        cache = {}
        try:
            tensor._conjunct_fn_cache = cache
        except Exception:  # noqa: BLE001 - __slots__ twins
            pass
    width, arity = tensor.width, tensor.max_actions

    def fn(rows):
        b = int(rows.shape[0])
        built = cache.get(b)
        if built is None:
            closed = _trace(
                tensor.step_rows,
                (jax.ShapeDtypeStruct((b, width), jnp.uint64),),
            )
            leaves, idx = _leaf_vars_of(closed, arity)
            if idx != expect_idx or not leaves:
                cache[b] = False  # retrace drifted: caller falls back
                return None
            sub = closed.jaxpr.replace(outvars=list(leaves))
            built = functools.partial(
                jax.core.eval_jaxpr, sub, closed.consts
            )
            cache[b] = built
        if built is False:
            return None
        return list(built(rows))

    return fn


def extract_footprints(tensor, batch: int = 4) -> Optional[ModelFootprints]:
    """Extract :class:`ModelFootprints` for ``tensor`` (cached on the twin
    instance — kernels cannot change under a fixed twin).  Returns None when
    the twin has no usable ``width``/``max_actions`` or a kernel does not
    trace (the structural audit already reports those)."""
    cached = getattr(tensor, "_footprint_cache", None)
    if cached is not None:
        return cached
    import jax
    import jax.numpy as jnp

    width = getattr(tensor, "width", None)
    arity = getattr(tensor, "max_actions", None)
    if not isinstance(width, int) or not isinstance(arity, int):
        return None
    rows_aval = jax.ShapeDtypeStruct((batch, width), jnp.uint64)
    findings: list = []
    try:
        # init_rows first — the documented outside-any-trace moment where
        # compiled twins populate their device-constant caches (the same
        # discipline as run_jaxpr_audit: constants materialized inside a
        # make_jaxpr trace would leak tracers into the cache and poison
        # the later engine trace)
        np.asarray(tensor.init_rows())
        closed = _trace(tensor.step_rows, (rows_aval,))
    except Exception:  # noqa: BLE001 - JX000 covers trace failures
        return None

    itp = _FpInterp()
    try:
        succ_v, valid_v = itp.run(closed, width)[:2]
    except Exception as e:  # noqa: BLE001 - degrade to all-TOP, loudly
        findings.append(AuditFinding(
            "JX300", Severity.WARNING, "step_rows",
            f"footprint pass crashed ({type(e).__name__}: {e}); every "
            "action is conservatively dependent on everything",
        ))
        succ_v = valid_v = None

    top_fp = ActionFootprint(
        reads=FieldSet.top_set(), writes=FieldSet.top_set(),
        guard=FieldSet.top_set(), decided=False,
    )
    actions = [top_fp] * arity
    decomposed = False

    # guards: valid [B, A] — the action axis IS the last axis, so the lane
    # machinery already carries per-action guard infos
    guards = [FieldSet.top_set()] * arity
    if valid_v is not None:
        gv = itp.read(closed.jaxpr.outvars[1])
        if gv.tracked and len(gv.lanes) == arity:
            guards = [i.as_data() for i in gv.lanes]
        else:
            guards = [gv.collapse().as_data()] * arity

    # boundary filter participates in enabledness on every action
    if getattr(tensor, "has_boundary", False):
        try:
            b_closed = _trace(
                tensor.boundary_rows,
                (jax.ShapeDtypeStruct((batch, arity, width), jnp.uint64),),
            )
            b_itp = _FpInterp()
            b_out = b_itp.run(b_closed, width)
            b_deps = b_out[0].collapse().as_data() if b_out else (
                FieldSet.top_set()
            )
        except Exception:  # noqa: BLE001
            b_deps = FieldSet.top_set()
        guards = [g.union(b_deps) for g in guards]

    # successors: walk the stacked succ [B, A, W] back to its action-axis
    # concatenate; each piece is one action's row array
    if succ_v is not None:
        out_var = itp.resolve(closed.jaxpr.outvars[0])
        ndim = len(_shape(closed.jaxpr.outvars[0]))
        pieces = _flatten_stack(itp, out_var, ndim - 2) if ndim >= 2 else None
        if pieces is None and arity == 1:
            # a single-action stack emits no concatenate: the whole
            # successor array IS the one action's row array
            pieces = [out_var]
        if pieces is not None and len(pieces) == arity:
            decomposed = True
            actions = []
            for a, piece in enumerate(pieces):
                pv = itp.read(itp.walk_back(piece))
                if pv.tracked and len(pv.lanes) == width:
                    fp = _action_footprint_from_lanes(pv.lanes, guards[a])
                else:
                    info = pv.collapse()
                    fp = ActionFootprint(
                        reads=info.as_data(),
                        writes=FieldSet.top_set(),
                        guard=guards[a], decided=False,
                    )
                actions.append(fp)
        else:
            actions = [
                replace(top_fp, guard=guards[a]) for a in range(arity)
            ]

    # properties: property_masks [B, P] — per-property lane deps
    prop_reads: list = []
    try:
        p_closed = _trace(tensor.property_masks, (rows_aval,))
        p_itp = _FpInterp()
        p_out = p_itp.run(p_closed, width)
        pv = p_out[0] if p_out else TOP_AVAL
        n_props = _last(p_closed.jaxpr.outvars[0])
        if pv.tracked and len(pv.lanes) == n_props:
            prop_reads = [i.as_data() for i in pv.lanes]
        else:
            prop_reads = [pv.collapse().as_data()] * n_props
    except Exception:  # noqa: BLE001 - structural audit reports this
        prop_reads = []

    conjuncts = None
    if succ_v is not None:
        try:
            conjuncts = _conjunct_info(itp, closed, arity, guards)
        except Exception:  # noqa: BLE001 - whole-guard fallback
            conjuncts = ConjunctInfo(
                sets=[[g] for g in guards],
                leaf_idx=[None] * arity, n_leaves=0,
            )

    out = ModelFootprints(
        width=width, n_actions=arity, actions=actions,
        prop_reads=prop_reads, decomposed=decomposed, findings=findings,
        conjuncts=conjuncts,
    )
    try:
        tensor._footprint_cache = out
    except Exception:  # noqa: BLE001 - __slots__ twins
        pass
    return out

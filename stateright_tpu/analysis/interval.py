"""Integer interval abstract interpretation over closed jaxprs.

The value-level half of the static auditor (the structural half is
``jaxpr_audit.py``): a forward pass that tracks a ``[lo, hi]`` integer
interval per traced value through the kernel's arithmetic
(add/sub/mul/shift/and/or/mod/select/concat/iota, widening on
``scan``/``while``), seeded from the model's *declared domain bounds*
(:class:`~stateright_tpu.parallel.tensor_model.RowDomain`: per-word packed
bounds, per-field widths, sentinel-carrying words).  The sanitizer
(``sanitizer.py``) drives it and turns site verdicts into JX2xx findings.

Three design points carry the precision the real kernels need:

 - **Sentinel outliers.**  A slot word's domain is ``[0, max_code] ∪
   {EMPTY}`` — a plain interval would collapse to top.  Abstract values
   carry up to two exact *outlier points* beside the interval; unary
   arithmetic maps them exactly (``EMPTY >> 6`` stays one point), and the
   guard refinement below deletes them, which is how
   ``where(slots != EMPTY, f(slots), 0)`` proves ``f``'s gather in range.
 - **Guard refinement.**  ``select_n`` whose predicate is a comparison of a
   traced value against a constant re-evaluates each branch with the
   compared value's interval refined by the branch condition (depth-bounded
   walk of the producing sub-DAG).  This covers both the sentinel idiom and
   jnp's machine-generated negative-index normalization
   (``select_n(x < 0, x, x + N)``) without flagging either.
 - **Field provenance.**  A value sliced from a row word remembers
   ``(word, accumulated right-shift)``; a subsequent ``& mask`` with a
   contiguous mask is a ``BitPacker.get`` field extraction and intersects
   with the field's *declared* bound — tighter than the mask when a field's
   width over-allocates its domain (state codes, queue indices).

Every transfer function is deliberately conservative: unknown primitives
and undecidable cases widen to the dtype hull, never narrower — the
sanitizer treats "top" as *undecided* (route to checked mode), so a missing
rule can cost precision but never soundness of an "in range" verdict.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Optional

import numpy as np

_MAX_OUTLIERS = 2
_REFINE_DEPTH = 48  # guarded re-evaluation walk budget (eqns per branch)


def dtype_hull(dtype) -> Optional[tuple]:
    """``(lo, hi)`` of an integer/bool dtype, None for floats/complex."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return (0, 1)
    if np.issubdtype(dt, np.signedinteger):
        b = dt.itemsize * 8
        return (-(1 << (b - 1)), (1 << (b - 1)) - 1)
    if np.issubdtype(dt, np.unsignedinteger):
        return (0, (1 << (dt.itemsize * 8)) - 1)
    return None


def _wrap(v: int, dtype) -> int:
    """Exact dtype wrap of a python int (what a convert/overflow does)."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return int(bool(v))
    bits = dt.itemsize * 8
    v &= (1 << bits) - 1
    if np.issubdtype(dt, np.signedinteger) and v >= (1 << (bits - 1)):
        v -= 1 << bits
    return v


@dataclass(frozen=True)
class IVal:
    """Abstract value: interval + exact outlier points + provenance flags.

    ``lo``/``hi`` are python ints (None = untracked, e.g. float dataflow).
    ``outliers`` are exact points the value may ALSO take, kept outside the
    interval (the EMPTY-sentinel machinery).  ``arith`` marks derivation
    through real arithmetic (feeds the JX203 overflow-before-mask rule);
    ``word``/``shift`` are the BitPacker field-extraction provenance.
    """

    lo: Optional[int]
    hi: Optional[int]
    outliers: frozenset = frozenset()
    arith: bool = False
    word: Optional[int] = None  # input row word this value derives from
    shift: int = 0  # accumulated logical right-shift since the word

    # -- constructors --------------------------------------------------------

    @staticmethod
    def top(dtype) -> "IVal":
        h = dtype_hull(dtype)
        if h is None:
            return IVal(None, None)
        return IVal(h[0], h[1])

    @staticmethod
    def const(v) -> "IVal":
        a = np.asarray(v)
        if a.dtype == np.bool_:
            vs = {int(bool(x)) for x in a.reshape(-1)[:4097].tolist()} or {0}
            return IVal(min(vs), max(vs))
        if not np.issubdtype(a.dtype, np.integer):
            return IVal(None, None)
        if a.size == 0:
            return IVal(0, 0)
        return IVal(int(a.min()), int(a.max()))

    @staticmethod
    def point(v: int) -> "IVal":
        return IVal(int(v), int(v))

    # -- queries -------------------------------------------------------------

    @property
    def tracked(self) -> bool:
        return self.lo is not None

    def hull(self) -> Optional[tuple]:
        """``(lo, hi)`` including outliers (what a check must assume)."""
        if not self.tracked:
            return None
        pts = [self.lo, self.hi, *self.outliers]
        return (min(pts), max(pts))

    def is_top_for(self, dtype) -> bool:
        """Nothing learned beyond the dtype itself (=> 'undecided')."""
        h = dtype_hull(dtype)
        if h is None or not self.tracked:
            return True
        lo, hi = self.hull()
        return lo <= h[0] and hi >= h[1]

    def may_contain(self, v: int) -> bool:
        if not self.tracked:
            return True
        return (self.lo <= v <= self.hi) or v in self.outliers

    def singleton(self) -> Optional[int]:
        if self.tracked and self.lo == self.hi and not self.outliers:
            return self.lo
        return None

    # -- algebra -------------------------------------------------------------

    def _norm(self) -> "IVal":
        """Fold outliers into the interval when they stop being outliers
        (inside it, or too many to track exactly)."""
        if not self.tracked:
            return IVal(None, None)
        outs = {o for o in self.outliers if not self.lo <= o <= self.hi}
        if len(outs) > _MAX_OUTLIERS:
            pts = [self.lo, self.hi, *outs]
            return replace(self, lo=min(pts), hi=max(pts),
                           outliers=frozenset())
        return replace(self, outliers=frozenset(outs))

    def join(self, other: "IVal") -> "IVal":
        if not self.tracked or not other.tracked:
            return IVal(None, None)
        return IVal(
            min(self.lo, other.lo),
            max(self.hi, other.hi),
            self.outliers | other.outliers,
            self.arith or other.arith,
        )._norm()

    def clip(self, lo: Optional[int], hi: Optional[int]) -> Optional["IVal"]:
        """Meet with ``[lo, hi]`` (None = unbounded side); None if empty."""
        if not self.tracked:
            return self
        nlo = self.lo if lo is None else max(self.lo, lo)
        nhi = self.hi if hi is None else min(self.hi, hi)
        outs = frozenset(
            o for o in self.outliers
            if (lo is None or o >= lo) and (hi is None or o <= hi)
        )
        if nlo > nhi:
            if not outs:
                return None
            vals = sorted(outs)
            return IVal(vals[0], vals[-1], frozenset(vals[1:-1]),
                        self.arith)._norm()
        return IVal(nlo, nhi, outs, self.arith, self.word, self.shift)._norm()

    def drop_point(self, v: int) -> "IVal":
        """Refine under a ``!= v`` guard: exact only for outliers/endpoints."""
        if not self.tracked:
            return self
        if v in self.outliers:
            return replace(self, outliers=self.outliers - {v})
        if self.lo == self.hi == v:
            # contradiction; caller treats as dead, give the empty-ish point
            return self
        if v == self.lo:
            return replace(self, lo=self.lo + 1)
        if v == self.hi:
            return replace(self, hi=self.hi - 1)
        return self

    def map_exact(self, fn: Callable[[int], int],
                  *, arith: Optional[bool] = None) -> "IVal":
        """Apply a MONOTONE exact unary function to the interval endpoints
        and each outlier (shift/and-mask/add-const class).  Drops field
        provenance; callers that preserve it rebuild explicitly."""
        if not self.tracked:
            return IVal(None, None)
        a, b = fn(self.lo), fn(self.hi)
        return IVal(
            min(a, b), max(a, b),
            frozenset(fn(o) for o in self.outliers),
            self.arith if arith is None else arith,
        )._norm()


TOP64 = IVal(0, (1 << 64) - 1)


def _is_contiguous_mask(m: int) -> Optional[tuple]:
    """``m == (2^bits - 1) << off``?  Returns ``(off, bits)`` or None."""
    if m <= 0:
        return None
    off = (m & -m).bit_length() - 1
    run = m >> off
    if run & (run + 1):
        return None
    return off, run.bit_length()


# ---------------------------------------------------------------------------
# jaxpr walking helpers (shared with sanitizer.py)
# ---------------------------------------------------------------------------


def aval_of(x):
    return getattr(x, "aval", None)


def is_literal(x) -> bool:
    return hasattr(x, "val")


def producers_of(jaxpr) -> dict:
    return {ov: eqn for eqn in jaxpr.eqns for ov in eqn.outvars}


def walk_transparent(var, producers, prims=("reshape", "broadcast_in_dim",
                                            "squeeze", "convert_element_type",
                                            "copy", "expand_dims"),
                     depth: int = 8):
    """Follow shape-only/convert producers back from ``var``."""
    for _ in range(depth):
        eqn = producers.get(var)
        if eqn is None or eqn.primitive.name not in prims:
            return var
        var = eqn.invars[0]
    return var


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class Interp:
    """One forward pass over a (sub-)jaxpr with an interval environment.

    ``hooks`` (the sanitizer) receives ``site(eqn, kind, ...)`` callbacks
    at gather/scatter/dynamic-slice/mask/select sites.  ``row_domain``
    seeds last-axis columns of the designated input var.
    """

    def __init__(self, hooks=None, row_domain=None):
        self.hooks = hooks
        self.row_domain = row_domain
        self.env: dict = {}
        self.input_var = None  # the rows var the domain seeds
        # jit-call bodies are INLINED into this flat environment; the alias map
        # links an inner jaxpr's invars to the outer vars that feed them
        # (and call outvars to the body's outvars), so guard recognition
        # and refinement walk straight through jnp's where/clip wrappers.
        self._alias: dict = {}
        self._producers: dict = {}
        # False while _refine_eval re-walks a sub-DAG: rules that report
        # through hooks (mask_site, dead_branch) must stay silent there or
        # every guarded re-evaluation duplicates findings under fresh
        # site numbers
        self._checking = True

    # -- env -----------------------------------------------------------------

    def read(self, x) -> IVal:
        if is_literal(x):
            return IVal.const(x.val)
        v = self.env.get(x)
        if v is None:
            v = IVal.top(getattr(aval_of(x), "dtype", np.int64))
            self.env[x] = v
        return v

    def write(self, var, val: IVal) -> None:
        self.env[var] = val._norm() if val.tracked else val

    # -- entry ---------------------------------------------------------------

    def run(self, closed, in_vals=None) -> list:
        """Interpret a ClosedJaxpr; returns output IVals."""
        jaxpr = closed.jaxpr
        for cv, c in zip(jaxpr.constvars, closed.consts):
            self.write(cv, IVal.const(np.asarray(c)))
            self._note_const(cv, c)
        if in_vals is None:
            in_vals = []
            for iv in jaxpr.invars:
                in_vals.append(IVal.top(getattr(aval_of(iv), "dtype",
                                                np.int64)))
        for iv, val in zip(jaxpr.invars, in_vals):
            self.write(iv, val)
        if self.row_domain is not None and jaxpr.invars:
            self.input_var = jaxpr.invars[0]
        self._run_eqns(jaxpr)
        return [self.read(ov) for ov in jaxpr.outvars]

    def _note_const(self, var, c) -> None:
        if self.hooks is not None:
            self.hooks.note_const(var, c)

    def _run_eqns(self, jaxpr) -> None:
        self._producers.update(producers_of(jaxpr))
        self._cur_jaxpr = jaxpr  # hooks (JX204 post-pass) read this
        for eqn in jaxpr.eqns:
            try:
                self.eqn(eqn)
            except Exception:  # noqa: BLE001 - a rule bug must not kill the
                # audit: fall back to top for this eqn's outputs
                for ov in eqn.outvars:
                    self.write(ov, IVal.top(getattr(aval_of(ov), "dtype",
                                                    np.int64)))

    # -- alias-aware structural walks ----------------------------------------

    def resolve(self, var):
        """Follow inlined-call aliases to the canonical var."""
        seen = 0
        while var in self._alias and seen < 32:
            var = self._alias[var]
            seen += 1
        return var

    def walk_back(self, var, prims=("reshape", "broadcast_in_dim",
                                    "squeeze", "convert_element_type",
                                    "copy", "expand_dims"),
                  depth: int = 8):
        """Alias-resolving :func:`walk_transparent`."""
        var = self.resolve(var)
        for _ in range(depth):
            eqn = self._producers.get(var)
            if eqn is None or eqn.primitive.name not in prims:
                return var
            var = self.resolve(eqn.invars[0])
        return var

    # -- guarded re-evaluation ----------------------------------------------

    def _refine_eval(self, var, base_var, refined: IVal,
                     depth: int = _REFINE_DEPTH) -> IVal:
        """Interval of ``var`` re-derived with ``base_var``'s value replaced
        by ``refined`` (memoized, depth-bounded walk of producers)."""
        memo: dict = {}

        base_var = self.resolve(base_var)
        saved_checking, self._checking = self._checking, False

        def go(v, d):
            if is_literal(v):
                return IVal.const(v.val)
            v = self.resolve(v)
            if v is base_var:
                return refined
            if v in memo:
                return memo[v]
            eqn = self._producers.get(v)
            if eqn is None or d <= 0:
                return self.read(v)
            memo[v] = self.read(v)  # cycle/width guard: current value
            ins = [go(x, d - 1) for x in eqn.invars]
            outs = self._transfer(eqn, ins, check=False)
            for ov, o in zip(eqn.outvars, outs):
                if self.resolve(ov) is v:
                    memo[v] = o
            return memo[v]

        try:
            return go(var, depth)
        finally:
            self._checking = saved_checking

    def _side_const(self, x) -> Optional[int]:
        """Exact constant value of one comparison side, if any."""
        if is_literal(x):
            return IVal.const(x.val).singleton()
        v = self.env.get(x)
        return v.singleton() if v is not None else None

    def _guard_of(self, pred_var):
        """``(base_var, op, const)`` when the predicate is a comparison of a
        traced value against a constant (either side), else None."""
        eqn = self._producers.get(self.walk_back(pred_var))
        if eqn is None or eqn.primitive.name not in (
            "eq", "ne", "lt", "le", "gt", "ge"
        ):
            return None
        a, b = eqn.invars
        op = eqn.primitive.name
        cb = self._side_const(b)
        if cb is not None and not is_literal(a):
            return a, op, cb
        ca = self._side_const(a)
        if ca is not None and not is_literal(b):
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                    "eq": "eq", "ne": "ne"}
            return b, flip[op], ca
        return None

    @staticmethod
    def _apply_guard(val: IVal, op: str, c: int, truth: bool):
        """Refine ``val`` under ``val <op> c == truth``; None = dead."""
        eff = {  # (op, truth) -> constraint
            ("lt", True): ("hi", c - 1), ("lt", False): ("lo", c),
            ("le", True): ("hi", c), ("le", False): ("lo", c + 1),
            ("gt", True): ("lo", c + 1), ("gt", False): ("hi", c),
            ("ge", True): ("lo", c), ("ge", False): ("hi", c - 1),
        }
        if (op, truth) in eff:
            side, bound = eff[(op, truth)]
            return val.clip(bound if side == "lo" else None,
                            bound if side == "hi" else None)
        if (op, truth) in (("eq", True), ("ne", False)):
            if not val.may_contain(c):
                return None
            return IVal.point(c)
        # != c: exact for outliers/endpoints, else unchanged
        if val.tracked and val.lo == val.hi == c and not val.outliers:
            return None
        return val.drop_point(c)

    # -- per-eqn transfer -----------------------------------------------------

    def eqn(self, eqn) -> None:
        ins = [self.read(x) for x in eqn.invars]
        outs = self._transfer(eqn, ins, check=True)
        for ov, val in zip(eqn.outvars, outs):
            self.write(ov, val)

    def _transfer(self, eqn, ins, *, check: bool) -> list:
        name = eqn.primitive.name
        rule = _RULES.get(name)
        if check and self.hooks is not None:
            self.hooks.site(self, eqn, ins)
        if rule is not None:
            out = rule(self, eqn, ins)
            return out if isinstance(out, list) else [out]
        if name in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr",
                    "remat_call", "checkpoint"):
            return self._call(eqn, ins)
        if name == "cond":
            return self._cond(eqn, ins)
        if name in ("while", "scan"):
            return self._loop(eqn, ins)
        # unknown: top per output dtype
        return [IVal.top(getattr(aval_of(ov), "dtype", np.int64))
                for ov in eqn.outvars]

    # -- HOPs ----------------------------------------------------------------

    def _sub(self, closed, in_vals) -> list:
        sub = Interp(hooks=self.hooks, row_domain=None)
        sub._producers = {}
        out = sub.run(closed, in_vals=in_vals)
        return out

    def _call(self, eqn, ins) -> list:
        """INLINE a jit/call body into the flat environment (alias-linked),
        so guards recognized outside a ``jnp.where`` wrapper refine values
        inside it and vice versa."""
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is None:
            return [IVal.top(getattr(aval_of(ov), "dtype", np.int64))
                    for ov in eqn.outvars]
        jaxpr = getattr(inner, "jaxpr", inner)
        consts = getattr(inner, "consts", ())
        for cv, c in zip(jaxpr.constvars, consts):
            self.write(cv, IVal.const(np.asarray(c)))
            self._note_const(cv, c)
        for iv, outer, val in zip(jaxpr.invars, eqn.invars, ins):
            self.write(iv, val)
            if not is_literal(outer):
                self._alias[iv] = outer
        saved = getattr(self, "_cur_jaxpr", None)
        self._run_eqns(jaxpr)
        self._cur_jaxpr = saved
        outs = []
        for outer_ov, inner_ov in zip(eqn.outvars, jaxpr.outvars):
            if not is_literal(inner_ov):
                self._alias[outer_ov] = inner_ov
            outs.append(self.read(inner_ov))
        return outs

    def _cond(self, eqn, ins) -> list:
        branches = eqn.params.get("branches", ())
        pred, args = ins[0], ins[1:]
        outs = None
        live = []
        for i, br in enumerate(branches):
            if pred.tracked and not pred.may_contain(i) and len(branches) > 1:
                continue  # interval proves this branch dead
            live.append(i)
            o = self._sub(br, args)
            outs = o if outs is None else [a.join(b) for a, b in zip(outs, o)]
        if self.hooks is not None and len(live) < len(branches):
            self.hooks.dead_branch(eqn, pred)
        if outs is None:  # defensive: evaluate branch 0
            outs = self._sub(branches[0], args)
        return outs

    def _fix_carry(self, body, consts, carry, tail, outvars):
        """Sound widening fixpoint for a loop carry: iterate the body; any
        carry component that keeps moving widens to its dtype hull (top is
        absorbing, so this terminates); 'stable' components are only
        trusted once the WHOLE carry has stabilized — a component stable
        under narrow inputs must be re-checked under the widened ones."""

        def same(a, b):
            return (a.tracked == b.tracked and a.lo == b.lo
                    and a.hi == b.hi and a.outliers == b.outliers)

        for _ in range(6):
            out = self._sub(body, consts + carry + tail)[:len(carry)]
            nxt = []
            moved = False
            for c, o, ov in zip(carry, out, outvars):
                j = c.join(o)
                if same(j, c):
                    nxt.append(c)
                else:
                    moved = True
                    nxt.append(
                        IVal.top(getattr(aval_of(ov), "dtype", np.int64))
                    )
            carry = nxt
            if not moved:
                return carry
        return [IVal.top(getattr(aval_of(ov), "dtype", np.int64))
                for ov in outvars]

    def _loop(self, eqn, ins) -> list:
        """Widening on while/scan (see :meth:`_fix_carry`).  scan's ys are
        evaluated ONCE at the post-fixpoint carries — joining ys from the
        narrow pre-widening iterations would under-approximate them."""
        name = eqn.primitive.name
        if name == "while":
            body = eqn.params["body_jaxpr"]
            b_consts = eqn.params.get("body_nconsts", 0)
            c_consts = eqn.params.get("cond_nconsts", 0)
            consts = ins[c_consts:c_consts + b_consts]
            carry = ins[c_consts + b_consts:]
            return self._fix_carry(body, consts, carry, [], eqn.outvars)
        # scan: [consts..., carry..., xs...] -> [carry..., ys...]
        n_consts = eqn.params.get("num_consts", 0)
        n_carry = eqn.params.get("num_carry", 0)
        body = eqn.params["jaxpr"]
        consts = ins[:n_consts]
        carry = ins[n_consts:n_consts + n_carry]
        xs = ins[n_consts + n_carry:]
        carry = self._fix_carry(body, consts, carry, xs,
                                eqn.outvars[:n_carry])
        ys = self._sub(body, consts + carry + xs)[n_carry:]
        return carry + ys


# ---------------------------------------------------------------------------
# primitive rules
# ---------------------------------------------------------------------------


def _binop(fn_exact, widen_wrap=True):
    """Exact interval combine via ``fn_exact`` on endpoint pairs; wraps to
    the output dtype hull when the result escapes it."""

    def rule(itp: Interp, eqn, ins):
        a, b = ins[0], ins[1]
        dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
        hull = dtype_hull(dt)
        if hull is None or not a.tracked or not b.tracked:
            return IVal(None, None) if hull is None else IVal.top(dt)
        cands = [fn_exact(x, y)
                 for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
        lo, hi = min(cands), max(cands)
        arith = True
        # exact outlier propagation when ONE side is a single point
        outs = frozenset()
        bs, as_ = b.singleton(), a.singleton()
        if bs is not None and a.outliers:
            outs = frozenset(_wrap(fn_exact(o, bs), dt) for o in a.outliers)
        elif as_ is not None and b.outliers:
            outs = frozenset(_wrap(fn_exact(as_, o), dt) for o in b.outliers)
        elif a.outliers or b.outliers:
            pts = ([fn_exact(o, y) for o in a.outliers
                    for y in (b.lo, b.hi)]
                   + [fn_exact(x, o) for o in b.outliers
                      for x in (a.lo, a.hi)])
            lo, hi = min([lo, *pts]), max([hi, *pts])
        if widen_wrap and (lo < hull[0] or hi > hull[1]):
            return IVal(hull[0], hull[1], frozenset(), arith)
        return IVal(lo, hi, outs, arith)._norm()

    return rule


def _rule_add(itp, eqn, ins):
    return _binop(lambda x, y: x + y)(itp, eqn, ins)


def _rule_sub(itp, eqn, ins):
    return _binop(lambda x, y: x - y)(itp, eqn, ins)


def _rule_mul(itp, eqn, ins):
    return _binop(lambda x, y: x * y)(itp, eqn, ins)


def _rule_and(itp: Interp, eqn, ins):
    a, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if np.dtype(dt) == np.bool_:
        if a.singleton() == 0 or b.singleton() == 0:
            return IVal.point(0)
        if a.singleton() == 1 and b.singleton() == 1:
            return IVal.point(1)
        return IVal(0, 1)
    if not a.tracked or not b.tracked:
        return IVal.top(dt)
    # mask-extraction hook: one side a constant contiguous mask
    mask_side, val_side, mval = None, None, 0
    for m, v in ((a, b), (b, a)):
        ms = m.singleton()
        if ms is not None and ms > 0 and _is_contiguous_mask(ms):
            mask_side, val_side, mval = m, v, ms
            break
    if a.lo < 0 or b.lo < 0:
        return IVal.top(dt)
    hi = min(a.hull()[1], b.hull()[1])
    out = IVal(0, hi)
    if mask_side is not None:
        out = IVal(0, min(hi, mval))
        off, bits = _is_contiguous_mask(mval)
        # field-provenance: declared bound for word bits [shift+off, +bits)
        if (itp.row_domain is not None and val_side.word is not None
                and off == 0):
            declared = itp.row_domain.field_hi(val_side.word,
                                               val_side.shift, bits)
            if declared is not None:
                out = IVal(0, min(out.hi, declared))
        if itp.hooks is not None and itp._checking:
            itp.hooks.mask_site(itp, eqn, val_side, mval)
    return replace(out, arith=False)


def _rule_or(itp, eqn, ins):
    a, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if np.dtype(dt) == np.bool_:
        if a.singleton() == 1 or b.singleton() == 1:
            return IVal.point(1)
        if a.singleton() == 0 and b.singleton() == 0:
            return IVal.point(0)
        return IVal(0, 1)
    if not a.tracked or not b.tracked or a.lo < 0 or b.lo < 0:
        return IVal.top(dt)
    ah, bh = a.hull()[1], b.hull()[1]
    hi = (1 << max(ah.bit_length(), bh.bit_length())) - 1
    return IVal(max(a.lo, b.lo), max(hi, ah, bh))


def _rule_xor(itp, eqn, ins):
    a, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if np.dtype(dt) == np.bool_:
        return IVal(0, 1)
    if not a.tracked or not b.tracked or a.lo < 0 or b.lo < 0:
        return IVal.top(dt)
    hi = (1 << max(a.hull()[1].bit_length(), b.hull()[1].bit_length())) - 1
    return IVal(0, hi)


def _rule_shr(itp: Interp, eqn, ins):
    a, s = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if not a.tracked or not s.tracked or a.lo < 0 or s.lo < 0:
        return IVal.top(dt)
    ss = s.singleton()
    if ss is not None:
        out = a.map_exact(lambda v: v >> ss)
        if a.word is not None:  # field provenance survives a const rshift
            out = replace(out, word=a.word, shift=a.shift + ss)
        return out
    return IVal(a.lo >> s.hi, a.hull()[1] >> s.lo)


def _rule_shl(itp, eqn, ins):
    a, s = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    hull = dtype_hull(dt)
    if (hull is None or not a.tracked or not s.tracked or a.lo < 0
            or s.lo < 0):
        return IVal.top(dt)
    ss = s.singleton()
    if ss is not None:
        out = a.map_exact(lambda v: v << ss, arith=a.arith)
    else:
        out = IVal(a.lo << s.lo, a.hull()[1] << s.hi, frozenset(), a.arith)
    oh = out.hull()
    if oh[0] < hull[0] or oh[1] > hull[1]:
        return IVal(hull[0], hull[1], frozenset(), a.arith)
    return out


def _rule_cmp(name):
    def rule(itp, eqn, ins):
        a, b = ins
        out = IVal(0, 1)
        if a.tracked and b.tracked:
            al, ah = a.hull()
            bl, bh = b.hull()
            verdict = None
            if name == "lt":
                verdict = True if ah < bl else (False if al >= bh else None)
            elif name == "le":
                verdict = True if ah <= bl else (False if al > bh else None)
            elif name == "gt":
                verdict = True if al > bh else (False if ah <= bl else None)
            elif name == "ge":
                verdict = True if al >= bh else (False if ah < bl else None)
            elif name == "eq":
                if ah < bl or al > bh:
                    verdict = False
                elif a.singleton() is not None and a.singleton() == b.singleton():
                    verdict = True
            elif name == "ne":
                if ah < bl or al > bh:
                    verdict = True
                elif (a.singleton() is not None
                      and a.singleton() == b.singleton()):
                    verdict = False
            if verdict is not None:
                out = IVal.point(int(verdict))
        return out

    return rule


def _rule_select(itp: Interp, eqn, ins):
    pred, cases = ins[0], ins[1:]
    pred_var = eqn.invars[0]
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    guard = itp._guard_of(pred_var) if len(cases) == 2 else None
    # machine-generated negative-index normalization: never a model smell
    is_norm = bool(guard and guard[1] == "lt" and guard[2] == 0)
    taken = []
    for i, (cvar, cval) in enumerate(zip(eqn.invars[1:], cases)):
        if pred.tracked and not pred.may_contain(i):
            continue  # interval proves this case dead
        if guard is not None:
            base, op, c = guard
            refined = Interp._apply_guard(itp.read(base), op, c,
                                          truth=bool(i))
            if refined is None:
                continue  # guard contradiction: case unreachable
            cval = itp._refine_eval(cvar, base, refined) if not is_literal(
                cvar) else cval
        taken.append(cval)
    if (itp.hooks is not None and itp._checking
            and len(taken) < len(cases) and not is_norm):
        itp.hooks.dead_branch(eqn, pred)
    if not taken:
        return IVal.top(dt)
    out = taken[0]
    for t in taken[1:]:
        out = out.join(t)
    return out


def _rule_convert(itp, eqn, ins):
    (a,) = ins
    dt = np.dtype(eqn.params.get("new_dtype", np.int64))
    hull = dtype_hull(dt)
    if hull is None:
        return IVal(None, None)
    if not a.tracked:
        return IVal.top(dt)
    if hull[0] <= a.lo and a.hi <= hull[1]:
        outs = frozenset(_wrap(o, dt) for o in a.outliers)
        return IVal(a.lo, a.hi, outs, a.arith, a.word, a.shift)._norm()
    return IVal.top(dt)


def _rule_identity(itp, eqn, ins):
    return ins[0]


def _rule_slice(itp: Interp, eqn, ins):
    (a,) = ins
    var = itp.resolve(eqn.invars[0])
    # last-axis column selection on the seeded input row var
    if (itp.row_domain is not None and var is itp.input_var):
        shape = getattr(aval_of(var), "shape", ())
        starts = eqn.params.get("start_indices", ())
        limits = eqn.params.get("limit_indices", ())
        if len(shape) >= 1 and len(starts) == len(shape):
            full_front = all(
                s == 0 and l == d
                for s, l, d in zip(starts[:-1], limits[:-1], shape[:-1])
            )
            if full_front:
                return itp.row_domain.words_ival(starts[-1], limits[-1])
    return a


def _rule_iota(itp, eqn, ins):
    shape = eqn.params.get("shape", ())
    dim = eqn.params.get("dimension", 0)
    n = shape[dim] if shape else 1
    return IVal(0, max(0, int(n) - 1))


def _rule_concat(itp, eqn, ins):
    out = ins[0]
    for v in ins[1:]:
        out = out.join(v)
    return out


def _rule_gather(itp: Interp, eqn, ins):
    # value interval: whatever the operand holds (plus, silently on TPU,
    # clamp artifacts — the hooks' JX201 covers the index side)
    return replace(ins[0], word=None, shift=0)


def _rule_scatter(itp, eqn, ins):
    return ins[0].join(ins[2]) if len(ins) >= 3 else ins[0]


def _rule_dus(itp, eqn, ins):
    return ins[0].join(ins[1])


def _rule_reduce_sum(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    hull = dtype_hull(dt)
    if hull is None or not a.tracked:
        return IVal(None, None) if hull is None else IVal.top(dt)
    n = 1
    in_elems = int(np.prod(getattr(aval_of(eqn.invars[0]), "shape", ()) or
                           (1,)))
    out_elems = int(np.prod(getattr(aval_of(eqn.outvars[0]), "shape", ()) or
                            (1,)))
    n = max(1, in_elems // max(out_elems, 1))
    lo, hi = a.hull()
    lo, hi = min(lo * n, lo), max(hi * n, hi)
    if lo < hull[0] or hi > hull[1]:
        return IVal.top(dt)
    return IVal(lo, hi, frozenset(), True)


def _rule_minmax(fn):
    def rule(itp, eqn, ins):
        a, b = ins
        if not a.tracked or not b.tracked:
            dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
            return IVal.top(dt)
        return IVal(fn(a.lo, b.lo), fn(a.hull()[1], b.hull()[1]))

    return rule


def _rule_clamp(itp, eqn, ins):
    # clamp(a, x, b) = max(a, min(x, b)) elementwise, min/max monotone
    a, x, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if not (a.tracked and x.tracked and b.tracked):
        return IVal.top(dt)
    t_lo = min(x.hull()[0], b.hull()[0])
    t_hi = min(x.hull()[1], b.hull()[1])
    return IVal(max(a.hull()[0], t_lo), max(a.hull()[1], t_hi))


def _rule_rem(itp, eqn, ins):
    a, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if (a.tracked and b.tracked and a.lo >= 0 and b.lo > 0):
        return IVal(0, min(a.hull()[1], b.hull()[1] - 1), frozenset(), True)
    return IVal.top(dt)


def _rule_div(itp, eqn, ins):
    a, b = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if dtype_hull(dt) is None:
        return IVal(None, None)
    if a.tracked and b.tracked and a.lo >= 0 and b.lo > 0:
        return IVal(a.lo // b.hull()[1], a.hull()[1] // b.lo,
                    frozenset(), True)
    return IVal.top(dt)


def _rule_argextreme(itp, eqn, ins):
    axes = eqn.params.get("axes", ())
    shape = getattr(aval_of(eqn.invars[0]), "shape", ())
    n = 1
    for ax in axes:
        if ax < len(shape):
            n *= shape[ax]
    return IVal(0, max(0, n - 1))


def _rule_argsort_like(itp, eqn, ins):
    # sort: per-operand identity intervals (argsort handled via iota operand)
    return [replace(v, word=None, shift=0) for v in ins]


def _rule_neg(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    hull = dtype_hull(dt)
    if hull is None or not a.tracked:
        return IVal(None, None) if hull is None else IVal.top(dt)
    lo, hi = -a.hull()[1], -a.hull()[0]
    if lo < hull[0] or hi > hull[1]:
        return IVal.top(dt)
    return IVal(lo, hi, frozenset(), True)


def _rule_not(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if np.dtype(dt) == np.bool_:
        s = a.singleton()
        return IVal.point(1 - s) if s in (0, 1) else IVal(0, 1)
    if a.tracked:
        return a.map_exact(lambda v: _wrap(~v, dt))
    return IVal.top(dt)


def _rule_cumsum(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    hull = dtype_hull(dt)
    if hull is None or not a.tracked:
        return IVal(None, None) if hull is None else IVal.top(dt)
    shape = getattr(aval_of(eqn.invars[0]), "shape", ())
    ax = eqn.params.get("axis", 0)
    n = int(shape[ax]) if ax < len(shape) else 1
    lo, hi = a.hull()
    lo, hi = min(lo, lo * n), max(hi, hi * n)
    if lo < hull[0] or hi > hull[1]:
        return IVal.top(dt)
    return IVal(lo, hi, frozenset(), True)


def _rule_bool01(itp, eqn, ins):
    return IVal(0, 1)


def _rule_reduce_keep(itp, eqn, ins):
    return replace(ins[0], word=None, shift=0)


def _rule_pad(itp, eqn, ins):
    return ins[0].join(ins[1])


def _rule_abs(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if dtype_hull(dt) is None:
        return IVal(None, None)
    if not a.tracked:
        return IVal.top(dt)
    lo, hi = a.hull()
    if lo >= 0:
        return IVal(lo, hi)
    if hi <= 0:
        return IVal(-hi, -lo)
    return IVal(0, max(-lo, hi))


def _rule_sign(itp, eqn, ins):
    (a,) = ins
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    if dtype_hull(dt) is None:
        return IVal(None, None)
    if not a.tracked:
        return IVal(-1, 1)
    lo, hi = a.hull()
    if lo > 0:
        return IVal.point(1)
    if hi < 0:
        return IVal.point(-1)
    return IVal(-1 if lo < 0 else 0, 1 if hi > 0 else 0)


def _rule_integer_pow(itp, eqn, ins):
    (a,) = ins
    y = eqn.params.get("y", 1)
    dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.int64)
    hull = dtype_hull(dt)
    if hull is None or not a.tracked or a.lo < 0 or y < 0:
        return IVal.top(dt) if hull else IVal(None, None)
    lo, hi = a.lo ** y, a.hull()[1] ** y
    if hi > hull[1]:
        return IVal.top(dt)
    return IVal(lo, hi, frozenset(), True)


_RULES = {
    "add": _rule_add,
    "sub": _rule_sub,
    "mul": _rule_mul,
    "and": _rule_and,
    "or": _rule_or,
    "xor": _rule_xor,
    "not": _rule_not,
    "neg": _rule_neg,
    "shift_right_logical": _rule_shr,
    "shift_right_arithmetic": _rule_shr,
    "shift_left": _rule_shl,
    "eq": _rule_cmp("eq"),
    "ne": _rule_cmp("ne"),
    "lt": _rule_cmp("lt"),
    "le": _rule_cmp("le"),
    "gt": _rule_cmp("gt"),
    "ge": _rule_cmp("ge"),
    "select_n": _rule_select,
    "convert_element_type": _rule_convert,
    "reshape": _rule_identity,
    "broadcast_in_dim": _rule_identity,
    "squeeze": _rule_identity,
    "expand_dims": _rule_identity,
    "transpose": _rule_identity,
    "rev": _rule_identity,
    "copy": _rule_identity,
    "stop_gradient": _rule_identity,
    "slice": _rule_slice,
    "iota": _rule_iota,
    "concatenate": _rule_concat,
    "gather": _rule_gather,
    "scatter": _rule_scatter,
    "scatter-add": _rule_scatter,
    "scatter_add": _rule_scatter,
    "scatter_min": _rule_scatter,
    "scatter_max": _rule_scatter,
    "scatter_mul": _rule_scatter,
    "dynamic_slice": _rule_reduce_keep,
    "dynamic_update_slice": _rule_dus,
    "reduce_sum": _rule_reduce_sum,
    "cumsum": _rule_cumsum,
    "reduce_max": _rule_reduce_keep,
    "reduce_min": _rule_reduce_keep,
    "cummax": _rule_reduce_keep,
    "cummin": _rule_reduce_keep,
    "reduce_and": _rule_bool01,
    "reduce_or": _rule_bool01,
    "argmax": _rule_argextreme,
    "argmin": _rule_argextreme,
    "sort": _rule_argsort_like,
    "max": _rule_minmax(max),
    "min": _rule_minmax(min),
    "clamp": _rule_clamp,
    "rem": _rule_rem,
    "div": _rule_div,
    "pad": _rule_pad,
    "integer_pow": _rule_integer_pow,
    "abs": _rule_abs,
    "sign": _rule_sign,
    "population_count": lambda i, e, ins: IVal(0, 64),
    "clz": lambda i, e, ins: IVal(0, 64),
}

"""Soundness sanitizer: interval bounds over transition kernels + the
checkify-instrumented checked execution mode.

Why this pass exists: on TPU an out-of-bounds gather silently CLAMPS and an
out-of-bounds scatter silently DROPS — a buggy ``step_rows`` encoding does
not crash, it silently prunes successors, and the checker reports "no
counterexample" for a space it never explored.  The GPU/accelerator
model-checking literature (GPUexplore's scalability analysis, the
tensor-core BFS line) identifies exactly this silent hash/indexing
corruption as the class that decides whether an accelerator checker's
verdicts can be trusted.  PR 1's auditor lints trace-level structure
(JX000–JX107); this pass proves *value-level* facts: every index stays on
its operand's axis, every packed field stays inside its declared width.

Two halves, one contract:

 - **Static** (:func:`run_sanitizer`): forward interval abstract
   interpretation (``interval.py``) over the traced ``step_rows`` /
   ``property_masks`` jaxprs, seeded from declared domain bounds
   (``RowDomain`` / discovered ``BitPacker`` field widths).  Decidable
   violations are findings (JX201/JX202 errors, JX203/JX204 warnings,
   JX205 info).
 - **Dynamic** (:func:`checkify_kernels` + ``CheckerBuilder.checked()``):
   where the interval domain can't decide, the verdict is *not* a false
   positive — the site is counted ``undecided`` (info) and routed to
   checked mode: a ``jax.experimental.checkify``-instrumented twin of the
   step kernels (index/nan/div checks) that runs the same exploration and
   fails loudly, with :func:`localize_checked_failure` re-running the
   failing batch row-by-row to name the offending row and decoded state.

Rule catalogue (``docs/analysis.md``):

 - ``JX201`` error — gather/dynamic-slice index interval escapes the
   operand axis (silent TPU clamp ⇒ dropped/duplicated successors);
 - ``JX202`` error — scatter/dynamic-update-slice index may exceed the
   target (silent drop — the ``buckets.insert`` failure class);
 - ``JX203`` warning — packed-field arithmetic provably overflows its
   declared bit width before the mask (info when the escape is marginal
   and reachability could bound it: checked mode decides);
 - ``JX204`` warning — a gather may read an ``EMPTY``-sentinel slot and
   feed it into arithmetic unguarded (uninitialized-read class);
 - ``JX205`` info — the interval proves a branch dead (model smell; jnp's
   machine-generated negative-index normalization is exempted).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .interval import (
    Interp,
    IVal,
    aval_of,
    dtype_hull,
    is_literal,
)
from .report import AuditFinding, Severity

EMPTY_SENTINEL = (1 << 64) - 1

_ARITH_PRIMS = frozenset(
    {"add", "sub", "mul", "div", "rem", "integer_pow", "cumsum",
     "reduce_sum", "shift_left", "neg"}
)

_TRANSPARENT = ("reshape", "broadcast_in_dim", "squeeze",
                "convert_element_type", "copy", "expand_dims")


# ---------------------------------------------------------------------------
# the hooks object interval.Interp calls back into
# ---------------------------------------------------------------------------


class _Hooks:
    """Collects site verdicts for one kernel trace."""

    def __init__(self, kernel: str, domain=None):
        self.kernel = kernel
        self.findings: list = []
        self.sites = 0
        self.proved = 0
        self.undecided = 0
        self.dead_branches = 0
        self._site_no = 0
        self._empty_consts: set = set()  # vars of consts containing EMPTY
        self._jx204_candidates: list = []  # (out_var, loc)
        self._jaxprs: list = []

    # -- wiring ---------------------------------------------------------------

    def note_const(self, var, c) -> None:
        try:
            a = np.asarray(c)
        except Exception:  # noqa: BLE001
            return
        if (a.dtype == np.uint64 and a.size > 1
                and bool((a == np.uint64(EMPTY_SENTINEL)).any())
                and bool((a != np.uint64(EMPTY_SENTINEL)).any())):
            self._empty_consts.add(var)

    def _loc(self, prim: str) -> str:
        self._site_no += 1
        return f"{self.kernel}:{prim}#{self._site_no}"

    # -- site checks ----------------------------------------------------------

    def site(self, itp: Interp, eqn, ins) -> None:
        name = eqn.primitive.name
        jaxpr = getattr(itp, "_cur_jaxpr", None)
        if jaxpr is not None and jaxpr not in self._jaxprs:
            self._jaxprs.append(jaxpr)
        if name == "gather":
            self._check_gather(itp, eqn, ins)
        elif name == "dynamic_slice":
            self._check_dynamic(itp, eqn, ins, rule="JX201",
                                what="dynamic-slice start")
        elif name.startswith("scatter"):
            self._check_scatter(itp, eqn, ins)
        elif name == "dynamic_update_slice":
            self._check_dynamic(itp, eqn, ins, rule="JX202",
                                what="dynamic-update start", skip=2)

    # gather ------------------------------------------------------------------

    def _index_ivals(self, itp: Interp, eqn, n_dims: int):
        """Per-mapped-dim index intervals: a single mapped dim uses the
        whole index array's interval; multiple dims walk the indices back
        to a last-axis ``concatenate`` whose pieces partition the dims
        (jnp advanced indexing / take_along_axis build exactly that)."""
        idx_var = eqn.invars[1]
        whole = itp.read(idx_var)
        if n_dims == 1:
            return [whole]
        src = itp.walk_back(idx_var, _TRANSPARENT)
        prod = itp._producers.get(src)
        if prod is not None and prod.primitive.name == "concatenate":
            pieces = []
            for pv in prod.invars:
                width = getattr(aval_of(pv), "shape", (1,))[-1] or 1
                val = itp.read(pv)
                pieces.extend([val] * int(width))
            if len(pieces) == n_dims:
                return pieces
        return [whole] * n_dims

    def _verdict(self, idx: IVal, bound: int, dtype) -> str:
        """'proved' | 'escape' | 'undecided' for an index vs [0, bound].

        An escape verdict (-> JX201/JX202 ERROR) requires a *learned*
        bound: an interval still covering half its dtype's range is the
        domain saying "I know nothing" (e.g. an int32 wrap join), and per
        the sanitizer contract an undecidable site routes to checked mode
        instead of becoming a false positive."""
        if not idx.tracked:
            return "undecided"
        lo, hi = idx.hull()
        if 0 <= lo and hi <= bound:
            return "proved"
        dh = dtype_hull(dtype)
        # counting widths inclusively: [0, 2^31-1] — the nonnegative half
        # of int32, i.e. "nothing known beyond the sign" — must land on
        # the undecided side of the threshold
        if dh is None or (hi - lo + 1) * 2 >= (dh[1] - dh[0] + 1):
            return "undecided"
        return "escape"

    def _check_gather(self, itp: Interp, eqn, ins) -> None:
        dnums = eqn.params.get("dimension_numbers")
        slice_sizes = eqn.params.get("slice_sizes", ())
        operand = eqn.invars[0]
        shape = getattr(aval_of(operand), "shape", ())
        smap = tuple(getattr(dnums, "start_index_map", ()) or ())
        if not smap or not shape:
            return
        self.sites += 1
        idx_dtype = getattr(aval_of(eqn.invars[1]), "dtype", np.int64)
        idxs = self._index_ivals(itp, eqn, len(smap))
        verdicts = []
        details = []
        for d, idx in zip(smap, idxs):
            ss = slice_sizes[d] if d < len(slice_sizes) else 1
            bound = int(shape[d]) - int(ss)
            v = self._verdict(idx, bound, idx_dtype)
            verdicts.append(v)
            if v != "proved":
                hull = idx.hull()
                details.append(
                    f"dim {d}: index in "
                    f"{'[%d, %d]' % hull if hull else '<untracked>'} vs "
                    f"valid [0, {bound}] (axis {shape[d]})"
                )
        self._finish_site("JX201", eqn, verdicts, details,
                          "gather index interval escapes the operand axis: "
                          "on TPU the access silently clamps, so successors "
                          "are dropped or duplicated and the space is "
                          "under-explored")
        # JX204: the gather may READ the EMPTY sentinel
        op_val = ins[0]
        may_empty = (op_val.tracked and op_val.may_contain(EMPTY_SENTINEL)
                     and not op_val.is_top_for(
                         getattr(aval_of(operand), "dtype", np.uint64)))
        src = itp.walk_back(operand, _TRANSPARENT)
        if may_empty or src in self._empty_consts:
            self._jx204_candidates.append(
                (eqn.outvars[0], self._loc("gather"))
            )

    def _check_dynamic(self, itp: Interp, eqn, ins, *, rule: str,
                       what: str, skip: int = 1) -> None:
        operand = eqn.invars[0]
        shape = getattr(aval_of(operand), "shape", ())
        starts = eqn.invars[skip:]
        if len(starts) != len(shape):
            return
        if rule == "JX202":
            sizes = getattr(aval_of(eqn.invars[1]), "shape", ())
        else:
            sizes = eqn.params.get("slice_sizes", ())
        self.sites += 1
        verdicts, details = [], []
        for d, sv in enumerate(starts):
            idx = itp.read(sv)
            ss = sizes[d] if d < len(sizes) else 1
            bound = int(shape[d]) - int(ss)
            dt = getattr(aval_of(sv), "dtype", np.int64)
            v = self._verdict(idx, bound, dt)
            verdicts.append(v)
            if v != "proved":
                hull = idx.hull()
                details.append(
                    f"dim {d}: start in "
                    f"{'[%d, %d]' % hull if hull else '<untracked>'} vs "
                    f"valid [0, {bound}]"
                )
        msg = (f"{what} may escape the operand: the device silently clamps, "
               "reading/writing the wrong rows")
        self._finish_site(rule, eqn, verdicts, details, msg)

    def _check_scatter(self, itp: Interp, eqn, ins) -> None:
        dnums = eqn.params.get("dimension_numbers")
        operand = eqn.invars[0]
        updates = eqn.invars[2] if len(eqn.invars) > 2 else None
        shape = getattr(aval_of(operand), "shape", ())
        smap = tuple(getattr(dnums, "scatter_dims_to_operand_dims", ())
                     or ())
        if not smap or not shape:
            return
        self.sites += 1
        inserted = set(getattr(dnums, "inserted_window_dims", ()) or ())
        upd_window = list(getattr(dnums, "update_window_dims", ()) or ())
        upd_shape = getattr(aval_of(updates), "shape", ()) if updates is not None else ()
        # full window extent per operand dim: 1 for inserted dims, the
        # matching update window size otherwise
        window: dict = {}
        wpos = 0
        for d in range(len(shape)):
            batching = set(getattr(dnums, "operand_batching_dims", ()) or ())
            if d in inserted or d in batching:
                window[d] = 1
            else:
                if wpos < len(upd_window) and upd_window[wpos] < len(upd_shape):
                    window[d] = int(upd_shape[upd_window[wpos]])
                else:
                    window[d] = 1
                wpos += 1
        idx_dtype = getattr(aval_of(eqn.invars[1]), "dtype", np.int64)
        idxs = self._index_ivals(itp, eqn, len(smap))
        verdicts, details = [], []
        for d, idx in zip(smap, idxs):
            bound = int(shape[d]) - window.get(d, 1)
            v = self._verdict(idx, bound, idx_dtype)
            verdicts.append(v)
            if v != "proved":
                hull = idx.hull()
                details.append(
                    f"dim {d}: index in "
                    f"{'[%d, %d]' % hull if hull else '<untracked>'} vs "
                    f"valid [0, {bound}] (axis {shape[d]})"
                )
        self._finish_site("JX202", eqn, verdicts, details,
                          "scatter index may exceed the target: on TPU the "
                          "write silently drops (the buckets.insert failure "
                          "class) — table/row updates vanish without a trace")

    def _finish_site(self, rule: str, eqn, verdicts, details, why) -> None:
        prim = eqn.primitive.name
        if all(v == "proved" for v in verdicts):
            self.proved += 1
            return
        loc = self._loc(prim)
        if any(v == "escape" for v in verdicts):
            self.findings.append(AuditFinding(
                rule, Severity.ERROR, loc,
                f"{why} ({'; '.join(details)})",
            ))
        else:
            self.undecided += 1
            self.findings.append(AuditFinding(
                rule, Severity.INFO, loc,
                "interval domain cannot bound this index "
                f"({'; '.join(details)}); not flagged as an error — run "
                "checked mode (CheckerBuilder.checked() / --checked) to "
                "guard it dynamically",
            ))

    # mask / JX203 ------------------------------------------------------------

    def mask_site(self, itp: Interp, eqn, val: IVal, mask: int) -> None:
        if not val.arith or not val.tracked:
            return  # extraction of a raw/packed word, not packing arithmetic
        dt = getattr(aval_of(eqn.outvars[0]), "dtype", np.uint64)
        if val.is_top_for(dt):
            return  # nothing learned: a mask over an unknown word is the
            # extraction idiom, not overflowing arithmetic
        lo, hi = val.hull()
        if hi <= mask and lo >= 0:
            return
        # provable only when even the MINIMUM escapes the field: every
        # input wraps, reachability cannot save it.  A partial escape
        # (lo inside, hi outside) is the reachability-undecidable case —
        # info + the dynamic guard, never a fleet-breaking warning.
        blatant = lo > mask
        self.findings.append(AuditFinding(
            "JX203",
            Severity.WARNING if blatant else Severity.INFO,
            self._loc("and"),
            f"packed-field arithmetic in [{lo}, {hi}] "
            f"{'provably overflows (for every input)' if blatant else 'may overflow'} "
            f"its declared width before the mask 0x{mask:x}: high bits are "
            "silently truncated and the packed field wraps"
            + ("" if blatant else
               " — if reachability bounds it, checked mode "
               "(CheckerBuilder.checked()) can confirm dynamically"),
        ))

    # dead branches / JX205 ---------------------------------------------------

    def dead_branch(self, eqn, pred: IVal) -> None:
        self.dead_branches += 1
        if self.dead_branches > 4:  # cap the noise; count rides the metrics
            return
        self.findings.append(AuditFinding(
            "JX205", Severity.INFO, self._loc(eqn.primitive.name),
            f"interval proves a branch dead (predicate is constantly "
            f"{pred.singleton()}): dead model logic, or a guard made "
            "redundant by the declared domain — worth a look",
        ))

    # JX204 post-pass ---------------------------------------------------------

    def finish(self) -> None:
        """Resolve JX204 candidates: fire when the sentinel-carrying gather
        output reaches arithmetic without an EMPTY-comparison guard."""
        uses: dict = {}
        for jaxpr in self._jaxprs:
            for eqn in jaxpr.eqns:
                for iv in eqn.invars:
                    if not is_literal(iv):
                        uses.setdefault(iv, []).append(eqn)
        for var, loc in self._jx204_candidates:
            frontier, seen, hit, guarded = [var], set(), False, False
            for _ in range(6):
                nxt = []
                for v in frontier:
                    for eqn in uses.get(v, ()):
                        name = eqn.primitive.name
                        if name in ("eq", "ne"):
                            other = [x for x in eqn.invars if x is not v]
                            if other and is_literal(other[0]) and int(
                                np.asarray(other[0].val).reshape(-1)[0]
                            ) == EMPTY_SENTINEL:
                                guarded = True
                                continue
                        if name in _ARITH_PRIMS:
                            hit = True
                        if name in _TRANSPARENT or name in ("slice",
                                                            "select_n"):
                            for ov in eqn.outvars:
                                if ov not in seen:
                                    seen.add(ov)
                                    nxt.append(ov)
                frontier = nxt
                if hit or not frontier:
                    break
            if hit and not guarded:
                self.findings.append(AuditFinding(
                    "JX204", Severity.WARNING, loc,
                    "gather may read an EMPTY-sentinel (uninitialized) "
                    "slot and feed it into arithmetic with no EMPTY "
                    "comparison in sight: the sentinel's bit pattern "
                    "(2^64-1) silently poisons the derived values",
                ))


# ---------------------------------------------------------------------------
# domain discovery + the static driver
# ---------------------------------------------------------------------------


def resolve_row_domain(tensor):
    """The twin's declared :class:`RowDomain` — its ``row_domain()`` hook
    when defined, else synthesized from a discovered ``BitPacker``
    attribute (field widths as bounds), else None (all words top)."""
    from ..parallel.tensor_model import BitPacker, RowDomain

    fn = getattr(tensor, "row_domain", None)
    if callable(fn):
        try:
            dom = fn()
        except Exception:  # noqa: BLE001 - a broken hook must not kill audit
            dom = None
        if dom is not None:
            return dom
    width = getattr(tensor, "width", None)
    packers = [
        v for v in vars(tensor).values()
        if isinstance(v, BitPacker) and v.width <= (width or v.width)
    ]
    if len(packers) != 1 or not isinstance(width, int):
        return None
    dom = RowDomain.from_packer(packers[0])
    if dom.width < width:
        wide = RowDomain(width)
        wide._words[: dom.width] = dom._words
        wide._fields = dom._fields
        return wide
    return dom


def _trace_kernel(fn, avals):
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax.make_jaxpr(lambda *a: fn(*a))(*avals)


def run_sanitizer(tensor, report, model=None, batch: int = 4) -> None:
    """Interval-sanitize ``tensor``'s kernels into ``report`` (findings +
    ``metrics['sanitizer']``).  Cached on the twin instance, like the
    structural jaxpr audit: kernels cannot change under a fixed twin."""
    cache = getattr(tensor, "_sanitizer_cache", None)
    if cache is not None:
        report.extend(cache[0])
        report.metrics["sanitizer"] = dict(cache[1])
        return
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    width = getattr(tensor, "width", None)
    if not isinstance(width, int):
        return  # JX103 (structural audit) already reports this
    domain = resolve_row_domain(tensor)
    rows_aval = jax.ShapeDtypeStruct((batch, width), jnp.uint64)
    findings: list = []
    summary = {"sites": 0, "proved": 0, "undecided": 0,
               "dead_branches": 0, "seeded": domain is not None,
               "kernels": {}}
    for kernel in ("step_rows", "property_masks"):
        fn = getattr(tensor, kernel, None)
        if fn is None:
            continue
        try:
            closed = _trace_kernel(fn, (rows_aval,))
        except Exception:  # noqa: BLE001 - JX000 already covers trace fails
            continue
        hooks = _Hooks(kernel)
        itp = Interp(hooks=hooks, row_domain=domain)
        try:
            itp.run(closed)
            hooks.finish()
        except Exception as e:  # noqa: BLE001 - the sanitizer must never
            # take down an audit the structural pass would survive — but a
            # crash may NOT read as a clean verdict either: the kernel went
            # unchecked, and a silent pass here makes the fleet soundness
            # gate vacuous.  JX200 is warning-severity so the fleet-clean
            # tests catch it loudly without aborting spawns.
            findings.append(AuditFinding(
                "JX200", Severity.WARNING, kernel,
                f"sanitizer pass crashed ({type(e).__name__}: {e}); this "
                "kernel's indices are UNCHECKED — treat every site as "
                "undecided and use checked mode; please report the crash",
            ))
            summary.setdefault("crashed", []).append(kernel)
            continue
        findings.extend(hooks.findings)
        summary["sites"] += hooks.sites
        summary["proved"] += hooks.proved
        summary["undecided"] += hooks.undecided
        summary["dead_branches"] += hooks.dead_branches
        summary["kernels"][kernel] = {
            "sites": hooks.sites, "proved": hooks.proved,
            "undecided": hooks.undecided,
        }
    rules = sorted({f.rule_id for f in findings})
    summary["rules"] = rules
    summary["clean"] = not any(
        f.severity == Severity.ERROR for f in findings
    )
    try:
        tensor._sanitizer_cache = (tuple(findings), dict(summary))
    except Exception:  # noqa: BLE001 - __slots__ twins
        pass
    report.extend(findings)
    report.metrics["sanitizer"] = summary


# ---------------------------------------------------------------------------
# checked execution mode (the dynamic guard)
# ---------------------------------------------------------------------------


class CheckedExecutionError(RuntimeError):
    """A checkify-instrumented kernel check failed during a checked run.
    Carries the offending batch row (index, raw words, decoded state when
    the twin can decode it) and the underlying checkify message."""

    def __init__(self, message: str, row_index: Optional[int] = None,
                 row=None, state=None):
        self.row_index = row_index
        self.row = row
        self.state = state
        super().__init__(message)


def checkify_errors():
    from jax.experimental import checkify

    return checkify.index_checks | checkify.float_checks


def checkify_kernels(tensor):
    """``rows -> (err, (masks, succ, valid))``: the model kernels under
    checkify's index/nan/div instrumentation.  Only the MODEL kernels are
    wrapped — the engine's own insert deliberately scatters out-of-range
    with ``mode='drop'`` (the dead-lane discard), which the OOB check
    would (correctly, but uselessly) flag."""
    from jax.experimental import checkify

    def kernels(rows):
        masks = tensor.property_masks(rows)
        succ, valid = tensor.step_rows(rows)
        return masks, succ, valid

    return checkify.checkify(kernels, errors=checkify_errors())


def error_flag(err):
    """Traced scalar bool: does ``err`` record any failed check?  (The
    engine threads only this flag through its loop carry — checkify Error
    pytrees mint fresh error codes per trace, so the full Error cannot
    cross jit boundaries; per-row replay rebuilds the message.)

    Reads checkify's ``Error._pred`` (private; present on jax 0.9.0).
    If a jax upgrade renames it this RAISES at engine build time —
    a checked mode that silently reports all-clear would be worse than no
    checked mode at all."""
    import jax.numpy as jnp

    preds = getattr(err, "_pred", None)
    if preds is None:
        raise RuntimeError(
            "jax.experimental.checkify.Error no longer exposes _pred; "
            "checked mode's failure flag needs porting to this jax "
            "version (stateright_tpu/analysis/sanitizer.py::error_flag)"
        )
    flag = jnp.bool_(False)
    for p in preds.values():
        flag = flag | jnp.any(p)
    return flag


def localize_checked_failure(tensor, rows_np, base_exc=None):
    """Re-run the checkified kernels one batch row at a time to name the
    offending row, then raise :class:`CheckedExecutionError`.  Always
    raises (falls back to the block-level message when per-row replay
    cannot reproduce — e.g. a check that needs batch context)."""
    import jax.numpy as jnp

    checked = checkify_kernels(tensor)
    rows_np = np.asarray(rows_np, np.uint64)
    for i in range(rows_np.shape[0]):
        try:
            err, _ = checked(jnp.asarray(rows_np[i:i + 1]))
            msg = err.get()
        except Exception:  # noqa: BLE001 - replay crash: report this row
            msg = "kernel crashed during per-row replay"
        if msg:
            state = None
            try:
                state = tensor.decode_state(rows_np[i])
            except Exception:  # noqa: BLE001 - decode is best-effort
                pass
            raise CheckedExecutionError(
                "checked mode: a kernel check failed at batch row "
                f"{i} (state={state!r}, row words="
                f"{[hex(int(w)) for w in rows_np[i]]}):\n{msg}",
                row_index=i, row=rows_np[i], state=state,
            ) from base_exc
    raise CheckedExecutionError(
        "checked mode: a kernel check failed inside the device block but "
        "per-row replay did not reproduce it "
        f"(underlying: {base_exc})",
    ) from base_exc

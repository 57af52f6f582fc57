"""Jaxpr kernel audit: statically verify a ``TensorModel``'s device kernels.

Every device-engine defect this repo has hit (empty-envelope crashes,
poison-row surprises, mixed fingerprint schemes, divergent closures) was
found minutes into a wavefront run.  The accelerator-checker literature
(GPUexplore's scalability work, the tensor-core BFS line) says the same
thing from the perf side: these engines live or die on kernels staying
statically shaped, pure, and integer-typed.  This pass verifies those
invariants *before launch* by abstractly tracing ``step_rows`` /
``property_masks`` once (``jax.make_jaxpr`` — no XLA compile, no device)
and walking the resulting ``ClosedJaxpr``:

 - ``JX000`` error — the kernel does not trace at all (the exception the
   engine would hit at launch, surfaced preflight with the same message);
 - ``JX101`` error — side-effecting / host-callback primitives (``jax.debug``
   prints, ``pure_callback``/``io_callback``): the wavefront engine runs
   kernels inside ``lax.while_loop`` where callbacks reorder or deadlock,
   and any host round-trip destroys MXU pipelining;
 - ``JX102`` warning — floating-point dataflow inside ``step_rows``: rows
   are u64 fingerprint words; a float round-trip silently truncates to 53
   bits of mantissa and corrupts fingerprints;
 - ``JX103`` error — output contract violation: ``step_rows`` must produce
   ``uint64[B, A, W]`` successors + ``bool[B, A]`` validity for the declared
   ``max_actions``/``width`` (the static shape XLA tiles onto the MXU), and
   ``property_masks`` must produce ``bool[B, P]``;
 - ``JX104`` error — retrace instability: tracing twice yields different
   jaxprs or different embedded constants, i.e. the kernel closes over
   mutable host state.  The engine retraces on every growth event (new
   capacities = new shapes), so an unstable kernel silently forks the
   transition relation mid-run;
 - ``JX105`` info — data-dependent gathers/scatters (indices that are traced
   values, not constants): correct, but each one is a random-access HBM
   fetch the MXU cannot tile — the measured latency bottleneck on hardware
   (see ``ops/buckets.py``);
 - ``JX106`` info — per-row FLOPs/bytes estimate from the jaxpr, so the
   report doubles as a perf preflight (also in ``report.metrics``).
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from .report import AuditFinding, Severity

# Host-callback primitives (flagged even when jax reports no effect).
_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "outside_call",
        "host_callback_call",
    }
)

# Elementwise primitives: 1 flop per output element.
_ELEMENTWISE = frozenset(
    {
        "add", "sub", "mul", "div", "rem", "pow", "integer_pow",
        "max", "min", "and", "or", "xor", "not", "neg", "sign", "abs",
        "shift_left", "shift_right_logical", "shift_right_arithmetic",
        "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
        "exp", "log", "tanh", "sqrt", "rsqrt", "floor", "ceil", "round",
        "nextafter", "cumsum", "cummax", "cummin", "cumprod",
    }
)

_REDUCE = frozenset(
    {
        "reduce_sum", "reduce_max", "reduce_min", "reduce_and",
        "reduce_or", "reduce_prod", "argmax", "argmin", "reduce_precision",
    }
)


def _aval_elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", ())
    return int(np.prod(shape)) if shape else 1


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    itemsize = getattr(dtype, "itemsize", 8) if dtype is not None else 8
    return _aval_elems(v) * itemsize


def _walk_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (jit bodies, cond branches, while cond/body, scan, custom calls)."""
    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        inner = getattr(j, "jaxpr", j)  # ClosedJaxpr -> Jaxpr
        if any(inner is s for s in seen):
            continue
        seen.append(inner)
        yield inner
        for eqn in inner.eqns:
            for p in eqn.params.values():
                cands = p if isinstance(p, (list, tuple)) else (p,)
                for c in cands:
                    if hasattr(c, "eqns") or hasattr(c, "jaxpr"):
                        stack.append(c)


def _iter_eqns(closed):
    for j in _walk_jaxprs(closed):
        for eqn in j.eqns:
            yield eqn


def _is_var(x) -> bool:
    """A traced value (not a compile-time literal)."""
    return not hasattr(x, "val")


# Shape-only ops a value passes through unchanged: walking back through
# these from a narrowing cast, reaching the raw kernel input means the
# cast truncates full-width row words.
_TRANSPARENT_PRIMS = frozenset(
    {"slice", "squeeze", "reshape", "broadcast_in_dim", "transpose", "copy",
     "rev", "concatenate", "expand_dims"}
)


def _narrow_escape_count(closed) -> int:
    """JX107: count ``uint64 -> <=32-bit integer`` casts whose input is a
    raw row word (the kernel input reached through shape-only ops).  A
    masked/shifted field extraction (``(rows >> off) & mask``) narrows
    provably-small values and stays quiet; casting the word itself zeroes
    its top bits and corrupts fingerprints."""
    count = 0
    for j in _walk_jaxprs(closed):
        producers = {}
        for eqn in j.eqns:
            for ov in eqn.outvars:
                producers[ov] = eqn
        invars = set(j.invars)
        for eqn in j.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0]
            src_dt = getattr(getattr(src, "aval", None), "dtype", None)
            new_dt = np.dtype(eqn.params.get("new_dtype", np.int64))
            if (
                src_dt is None
                or np.dtype(src_dt) != np.dtype(np.uint64)
                or np.issubdtype(new_dt, np.floating)  # JX102's territory
                or new_dt.itemsize > 4
            ):
                continue
            v, depth = src, 0
            while depth < 8:
                if v in invars:
                    count += 1
                    break
                p = producers.get(v)
                if p is None or p.primitive.name not in _TRANSPARENT_PRIMS:
                    break  # computed/masked value: not provably full-width
                v = p.invars[0]
                depth += 1
    return count


def _index_operands(eqn):
    """The index operands of a gather/scatter-family eqn (the invars whose
    tracedness makes the access data-dependent), per primitive signature:
    ``gather(operand, indices)``, ``scatter*(operand, indices, updates)``,
    ``dynamic_slice(operand, *starts)``,
    ``dynamic_update_slice(operand, update, *starts)``."""
    name = eqn.primitive.name
    if name == "gather" or name.startswith("scatter"):
        return eqn.invars[1:2]
    if name == "dynamic_slice":
        return eqn.invars[1:]
    if name == "dynamic_update_slice":
        return eqn.invars[2:]
    return ()


def _consts_equal(c1, c2) -> bool:
    if len(c1) != len(c2):
        return False
    for a, b in zip(c1, c2):
        if a is b:
            continue
        try:
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                return False
        except Exception:  # noqa: BLE001 - non-array consts: fall back to ==
            if a != b:
                return False
    return True


def _flops_bytes(closed) -> dict:
    """Rough per-trace cost model: flops from primitive arithmetic, bytes
    as the sum of all intermediate outputs written (a memory-traffic
    proxy; gathers/scatters additionally pay random-access latency)."""
    flops = 0
    out_bytes = 0
    eqns = 0
    for eqn in _iter_eqns(closed):
        eqns += 1
        out_elems = sum(_aval_elems(v) for v in eqn.outvars)
        out_bytes += sum(_aval_bytes(v) for v in eqn.outvars)
        name = eqn.primitive.name
        if name in _ELEMENTWISE:
            flops += out_elems
        elif name in _REDUCE:
            flops += sum(_aval_elems(v) for v in eqn.invars)
        elif name == "dot_general":
            dims = eqn.params.get("dimension_numbers", (((), ()), ((), ())))
            contract = dims[0][0] if dims and dims[0] else ()
            k = 1
            for axis in contract:
                shape = getattr(eqn.invars[0].aval, "shape", ())
                if axis < len(shape):
                    k *= shape[axis]
            flops += 2 * out_elems * k
        elif name in ("sort", "argsort"):
            n = max(out_elems, 2)
            flops += int(n * math.log2(n))
        elif name == "convert_element_type":
            flops += out_elems
    return {"flops": flops, "bytes": out_bytes, "eqns": eqns}


def _trace(fn, avals):
    import jax

    jax.config.update("jax_enable_x64", True)
    # Fresh wrapper identity per call: jax memoizes traces on function
    # identity, and a cache hit would return the FIRST jaxpr without
    # re-running the Python body — silently defeating the retrace diff
    # (JX104) that exists to catch impure kernels.
    return jax.make_jaxpr(lambda *args: fn(*args))(*avals)


def _audit_one_kernel(
    fn,
    avals,
    name: str,
    findings: list,
    *,
    retrace: bool,
    flag_floats: bool,
) -> Optional[object]:
    """Trace ``fn`` (twice when ``retrace``), run the structural rules,
    and return the ClosedJaxpr (None when tracing failed)."""
    try:
        closed = _trace(fn, avals)
    except Exception as e:  # noqa: BLE001 - surfaced as a finding
        findings.append(
            AuditFinding(
                "JX000",
                Severity.ERROR,
                name,
                f"kernel does not trace: {type(e).__name__}: {e}",
            )
        )
        return None

    # JX104 retrace instability: same inputs, second trace must be
    # bit-identical (structure AND embedded constants).
    if retrace:
        try:
            closed2 = _trace(fn, avals)
        except Exception as e:  # noqa: BLE001
            findings.append(
                AuditFinding(
                    "JX104",
                    Severity.ERROR,
                    name,
                    f"kernel traced once but not twice ({type(e).__name__}: "
                    f"{e}); it mutates host state while tracing",
                )
            )
            closed2 = None
        if closed2 is not None:
            if str(closed.jaxpr) != str(closed2.jaxpr):
                findings.append(
                    AuditFinding(
                        "JX104",
                        Severity.ERROR,
                        name,
                        "retrace instability: two traces produced different "
                        "jaxprs — the kernel closes over mutable host state "
                        "(the engine retraces on every growth event, forking "
                        "the transition relation mid-run)",
                    )
                )
            elif not _consts_equal(closed.consts, closed2.consts):
                findings.append(
                    AuditFinding(
                        "JX104",
                        Severity.ERROR,
                        name,
                        "retrace instability: identical jaxpr structure but "
                        "different embedded constants — the kernel closes "
                        "over a mutated host container",
                    )
                )

    # JX101 side effects / callbacks.
    effects = set(map(str, getattr(closed, "effects", ()) or ()))
    callback_prims = sorted(
        {
            e.primitive.name
            for e in _iter_eqns(closed)
            if e.primitive.name in _CALLBACK_PRIMS
            or getattr(e, "effects", None)
        }
    )
    if effects or callback_prims:
        detail = ", ".join(callback_prims) or ", ".join(sorted(effects))
        findings.append(
            AuditFinding(
                "JX101",
                Severity.ERROR,
                name,
                f"side-effecting/callback primitives in the kernel ({detail}); "
                "device kernels must be pure — callbacks reorder or deadlock "
                "inside the engine's while_loop and stall the MXU pipeline",
            )
        )

    # JX102 float dataflow (fingerprint-corrupting in step_rows).
    if flag_floats:
        float_prims = sorted(
            {
                e.primitive.name
                for e in _iter_eqns(closed)
                if any(
                    np.issubdtype(
                        getattr(getattr(v, "aval", None), "dtype", np.int32),
                        np.floating,
                    )
                    for v in e.outvars
                )
            }
        )
        if float_prims:
            findings.append(
                AuditFinding(
                    "JX102",
                    Severity.WARNING,
                    name,
                    "floating-point dataflow in a u64 row kernel "
                    f"({', '.join(float_prims)}): floats silently truncate "
                    "row words past 53 bits and corrupt fingerprints",
                )
            )

    # JX107 integer-narrowing escape (the other fingerprint-corrupting
    # dtype class from the float rule above): u64 row words cast to a
    # 32-bit integer lose their top bits.
    if flag_floats:
        narrows = _narrow_escape_count(closed)
        if narrows:
            findings.append(
                AuditFinding(
                    "JX107",
                    Severity.WARNING,
                    name,
                    f"{narrows} uint64->int32/uint32 cast(s) of raw row "
                    "words: the top 32 bits are silently zeroed, corrupting "
                    "fingerprints (mask or shift the field out first — "
                    "BitPacker.get — instead of casting whole words)",
                )
            )

    # JX105 data-dependent gathers/scatters (perf note).  Only the INDEX
    # operands count: update/operand arrays are always traced, and
    # classifying them would flag every static-offset slice update.
    dyn = 0
    for e in _iter_eqns(closed):
        if any(_is_var(v) for v in _index_operands(e)):
            dyn += 1
    if dyn:
        findings.append(
            AuditFinding(
                "JX105",
                Severity.INFO,
                name,
                f"{dyn} data-dependent gather/scatter site(s): random-access "
                "HBM fetches the MXU cannot tile (the measured latency "
                "bottleneck class on hardware; fine if intended)",
            )
        )
    return closed


def run_jaxpr_audit(
    tensor,
    report,
    model=None,
    *,
    deep: bool = False,
    batch: int = 4,
) -> None:
    """Audit ``tensor``'s device kernels into ``report`` (findings +
    ``metrics['step_rows'|'property_masks']``).  Results are cached on the
    tensor instance: respawns and engine growth events re-enter the
    preflight, and the kernels cannot change under a fixed twin."""
    cache = getattr(tensor, "_jaxpr_audit_cache", None)
    if cache is not None and cache[0] >= bool(deep):
        report.extend(cache[1])
        report.metrics.update(cache[2])
        return
    findings: list = []
    metrics: dict = {}
    _run_jaxpr_audit_uncached(
        tensor, findings, metrics, model=model, deep=deep, batch=batch
    )
    try:
        tensor._jaxpr_audit_cache = (bool(deep), tuple(findings), metrics)
    except Exception:  # noqa: BLE001 - __slots__ twins: just skip caching
        pass
    report.extend(findings)
    report.metrics.update(metrics)


def _run_jaxpr_audit_uncached(
    tensor, findings, metrics, *, model, deep, batch
) -> None:
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    width = getattr(tensor, "width", None)
    arity = getattr(tensor, "max_actions", None)
    if not isinstance(width, int) or not isinstance(arity, int):
        findings.append(
            AuditFinding(
                "JX103",
                Severity.ERROR,
                type(tensor).__name__,
                "tensor model must declare integer width/max_actions "
                f"(got width={width!r}, max_actions={arity!r})",
            )
        )
        return

    # init_rows first: it is the documented outside-any-trace moment where
    # compiled twins populate their device-constant caches (see
    # CompiledActorTensor.init_rows) — and its output is part of the
    # contract too.
    try:
        init = np.asarray(tensor.init_rows())
        if init.dtype != np.uint64 or init.ndim != 2 or init.shape[1] != width:
            findings.append(
                AuditFinding(
                    "JX103",
                    Severity.ERROR,
                    "init_rows",
                    f"init_rows must return uint64[I, {width}], got "
                    f"{init.dtype}{list(init.shape)}",
                )
            )
    except Exception as e:  # noqa: BLE001 - surfaced as a finding
        findings.append(
            AuditFinding(
                "JX000",
                Severity.ERROR,
                "init_rows",
                f"init_rows failed: {type(e).__name__}: {e}",
            )
        )
        return

    rows_aval = jax.ShapeDtypeStruct((batch, width), jnp.uint64)

    closed = _audit_one_kernel(
        tensor.step_rows,
        (rows_aval,),
        "step_rows",
        findings,
        retrace=True,
        flag_floats=True,
    )
    if closed is not None:
        out = list(closed.out_avals)
        if len(out) != 2:
            findings.append(
                AuditFinding(
                    "JX103",
                    Severity.ERROR,
                    "step_rows",
                    f"must return (succ, valid); traced {len(out)} outputs",
                )
            )
        else:
            succ, valid = out
            want = (batch, arity, width)
            if tuple(succ.shape) != want or succ.dtype != jnp.uint64:
                findings.append(
                    AuditFinding(
                        "JX103",
                        Severity.ERROR,
                        "step_rows",
                        f"successors must be uint64{list(want)} "
                        f"(B, max_actions, width), got "
                        f"{succ.dtype}{list(succ.shape)} — a non-u64 row "
                        "dtype corrupts fingerprints; a shape mismatch "
                        "breaks the engine's static MXU tiling",
                    )
                )
            if tuple(valid.shape) != (batch, arity) or valid.dtype != jnp.bool_:
                findings.append(
                    AuditFinding(
                        "JX103",
                        Severity.ERROR,
                        "step_rows",
                        f"validity mask must be bool[{batch}, {arity}], got "
                        f"{valid.dtype}{list(valid.shape)}",
                    )
                )
        m = _flops_bytes(closed)
        m["flops_per_row"] = m["flops"] / batch
        m["bytes_per_row"] = m["bytes"] / batch
        metrics["step_rows"] = m
        findings.append(
            AuditFinding(
                "JX106",
                Severity.INFO,
                "step_rows",
                "perf preflight: ~{:.0f} flops/row, ~{:.0f} intermediate "
                "bytes/row over {} eqns".format(
                    m["flops_per_row"], m["bytes_per_row"], m["eqns"]
                ),
            )
        )

    n_props = None
    if model is not None:
        try:
            n_props = len(model.properties())
        except Exception:  # noqa: BLE001 - model may be partially built
            n_props = None
    closed_pm = _audit_one_kernel(
        tensor.property_masks,
        (rows_aval,),
        "property_masks",
        findings,
        retrace=deep,
        flag_floats=False,
    )
    if closed_pm is not None:
        out = list(closed_pm.out_avals)
        bad = (
            len(out) != 1
            or out[0].dtype != jnp.bool_
            or len(out[0].shape) != 2
            or out[0].shape[0] != batch
            or (n_props is not None and out[0].shape[1] != n_props)
        )
        if bad:
            got = (
                f"{out[0].dtype}{list(out[0].shape)}"
                if len(out) == 1
                else f"{len(out)} outputs"
            )
            want_p = n_props if n_props is not None else "P"
            findings.append(
                AuditFinding(
                    "JX103",
                    Severity.ERROR,
                    "property_masks",
                    f"must return bool[{batch}, {want_p}] (one column per "
                    f"property, in properties() order), got {got}",
                )
            )
        m = _flops_bytes(closed_pm)
        m["flops_per_row"] = m["flops"] / batch
        m["bytes_per_row"] = m["bytes"] / batch
        metrics["property_masks"] = m

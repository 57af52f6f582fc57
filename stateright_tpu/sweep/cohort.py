"""Shape cohorts: ONE compiled step program per family of compatible
instances (docs/sweep.md).

The sweep engine wants to run many instances of one model family as a
single wavefront.  The twins' kernels are pure functions of the row
words *and their closed-over constants* (bounds, tables, seeds baked at
twin construction), so two instances of the same family trace to
jaxprs that are **structurally identical** and differ only in constant
values.  This module makes that a capability:

 1. every instance's ``step_rows`` / ``property_masks`` is traced at a
    one-row batch (``[1, W]``);
 2. the traced jaxprs are unified: equal constants stay shared, and
    constants (and literals — Python-int bounds trace as jaxpr
    literals) that DIFFER across instances are lifted into arguments
    stacked ``[K, ...]`` across the cohort;
 3. the cohort kernel evaluates the unified jaxpr per row under
    ``jax.vmap``, gathering each row's constants by its instance tag —
    so one XLA program serves every member, and the engine pays ONE
    compile for the cohort instead of K.

Instances whose kernels do not unify (different shapes, different
network semantics, genuinely different code paths) split into separate
cohorts — grouping only affects how many programs compile, never
correctness.  A build-time verification pass backstops the unifier:
the cohort kernel is evaluated on every instance's init rows and
compared against the instance's own kernels; any mismatch demotes the
group to singleton cohorts instead of ever running a wrong program.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..fingerprint import SWEEP_NS_SEED, fold64, mix64, sweep_ns_bits

_KERNELS = ("step_rows", "property_masks")


def shape_signature(instance) -> tuple:
    """The coarse cohort grouping key: twin class + row layout + the
    property list.  Instances that disagree here can never share a
    program (different carry shapes)."""
    tensor = instance.model._tensor_cached()
    props = tuple(
        (p.name, getattr(p.expectation, "name", str(p.expectation)))
        for p in instance.model.properties()
    )
    return (
        type(tensor).__name__,
        int(tensor.width),
        int(tensor.max_actions),
        props,
    )


def _params_eq(a, b) -> bool:
    """Robust eqn-params comparison: dict/tuple recursion, numpy arrays
    by value, nested jaxprs by identity-or-== (the tracing cache makes
    identical inner functions share one jaxpr object; anything else is
    honestly 'different' and the group falls back)."""
    if a is b:
        return True
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(
            _params_eq(a[k], b[k]) for k in a
        )
    if isinstance(a, (tuple, list)) and isinstance(b, (tuple, list)):
        return len(a) == len(b) and all(
            _params_eq(x, y) for x, y in zip(a, b)
        )
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        try:
            return (
                np.asarray(a).dtype == np.asarray(b).dtype
                and np.array_equal(np.asarray(a), np.asarray(b))
            )
        except Exception:  # noqa: BLE001 - exotic params: not equal
            return False
    try:
        return bool(a == b)
    except Exception:  # noqa: BLE001 - ambiguous/odd __eq__
        return False


def _var_index_maps(jaxprs) -> list:
    """Per-jaxpr ``Var -> ordinal`` maps in definition order (constvars,
    invars, then eqn outvars): two jaxprs are graph-isomorphic in our
    sense iff every eqn reads vars of equal ordinals."""
    maps = []
    for j in jaxprs:
        m = {}
        for v in list(j.constvars) + list(j.invars):
            m[v] = len(m)
        for e in j.eqns:
            for ov in e.outvars:
                m[ov] = len(m)
        maps.append(m)
    return maps


def unify_jaxprs(closed_list):
    """Unify structurally identical ClosedJaxprs into one jaxpr whose
    differing constants/literals are lifted to (stacked) arguments.

    Returns ``(jaxpr, const_spec)`` where ``const_spec`` is an ordered
    list of ``(shared, value)`` pairs matching the unified jaxpr's
    constvars — ``shared=True`` values are identical across instances
    and passed as-is; ``shared=False`` values are stacked ``[K, ...]``
    and gathered by instance tag at evaluation time.  Returns ``None``
    when the jaxprs do not unify (the caller splits the cohort)."""
    from jax.extend.core import Literal, Var

    k = len(closed_list)
    j0 = closed_list[0].jaxpr
    jaxprs = [c.jaxpr for c in closed_list]
    for j in jaxprs[1:]:
        if (
            len(j.eqns) != len(j0.eqns)
            or len(j.invars) != len(j0.invars)
            or len(j.constvars) != len(j0.constvars)
            or len(j.outvars) != len(j0.outvars)
        ):
            return None
        if [v.aval for v in j.invars] != [v.aval for v in j0.invars]:
            return None
        if [v.aval for v in j.constvars] != [
            v.aval for v in j0.constvars
        ]:
            return None
    maps = _var_index_maps(jaxprs)

    lifted_vars: list = []
    lifted_vals: list = []
    new_eqns = []
    for ei, eqn in enumerate(j0.eqns):
        eqns_k = [j.eqns[ei] for j in jaxprs]
        if any(e.primitive is not eqn.primitive for e in eqns_k[1:]):
            return None
        if any(
            not _params_eq(e.params, eqn.params) for e in eqns_k[1:]
        ):
            return None
        if any(len(e.invars) != len(eqn.invars) for e in eqns_k[1:]):
            return None
        if any(len(e.outvars) != len(eqn.outvars) for e in eqns_k[1:]):
            return None
        invars = list(eqn.invars)
        changed = False
        for vi, v in enumerate(eqn.invars):
            vs = [e.invars[vi] for e in eqns_k]
            if isinstance(v, Literal):
                if any(not isinstance(x, Literal) for x in vs[1:]):
                    return None
                if any(x.aval != v.aval for x in vs[1:]):
                    return None
                vals = [x.val for x in vs]
                if all(
                    np.array_equal(vals[0], x) for x in vals[1:]
                ):
                    continue
                nv = Var(v.aval)
                invars[vi] = nv
                changed = True
                lifted_vars.append(nv)
                lifted_vals.append(vals)
            else:
                if any(isinstance(x, Literal) for x in vs[1:]):
                    return None
                if any(
                    maps[i][vs[i]] != maps[0][vs[0]] for i in range(1, k)
                ):
                    return None
        for oi, ov in enumerate(eqn.outvars):
            if any(
                e.outvars[oi].aval != ov.aval for e in eqns_k[1:]
            ):
                return None
        new_eqns.append(
            eqn.replace(invars=invars) if changed else eqn
        )
    for oi, ov in enumerate(j0.outvars):
        ovs = [j.outvars[oi] for j in jaxprs]
        if isinstance(ov, Literal):
            if any(not isinstance(x, Literal) for x in ovs[1:]):
                return None
            if any(
                not np.array_equal(ov.val, x.val) for x in ovs[1:]
            ):
                return None  # differing literal outputs: not worth lifting
        else:
            if any(isinstance(x, Literal) for x in ovs[1:]):
                return None
            if any(
                maps[i][ovs[i]] != maps[0][ovs[0]] for i in range(1, k)
            ):
                return None

    const_spec: list = []
    for ci in range(len(closed_list[0].consts)):
        vals = [np.asarray(c.consts[ci]) for c in closed_list]
        if any(
            v.dtype != vals[0].dtype or v.shape != vals[0].shape
            for v in vals[1:]
        ):
            return None
        if all(np.array_equal(vals[0], v) for v in vals[1:]):
            const_spec.append((True, vals[0]))
        else:
            const_spec.append((False, np.stack(vals)))
    for vals in lifted_vals:
        const_spec.append((False, np.stack([np.asarray(v) for v in vals])))

    new_jaxpr = j0.replace(
        constvars=list(j0.constvars) + lifted_vars, eqns=new_eqns
    )
    return new_jaxpr, const_spec


def _trace_kernel(tensor, name: str):
    """ClosedJaxpr of ``tensor.<name>`` at a one-row batch.  The twin's
    device-const caches are pre-warmed via ``init_rows()`` first (the
    ``run_jaxpr_audit`` discipline: compiled twins materialize lazy
    tables on first use, and tracing must never leak a tracer into
    them)."""
    import jax
    import jax.numpy as jnp

    np.asarray(tensor.init_rows())
    aval = jax.ShapeDtypeStruct((1, int(tensor.width)), jnp.uint64)
    return jax.make_jaxpr(getattr(tensor, name))(aval)


def _unified_kernel(jaxpr, const_spec):
    """The cohort kernel over a unified jaxpr: per-row evaluation under
    ``vmap``, shared constants captured, per-instance constants gathered
    by the row's tag."""
    import jax
    import jax.numpy as jnp
    from jax import core

    shared = [jnp.asarray(v) for s, v in const_spec if s]
    stacked = [jnp.asarray(v) for s, v in const_spec if not s]
    flags = [s for s, _ in const_spec]

    def kernel(rows, tags):
        def one(row, tag):
            consts = []
            si = di = 0
            for s in flags:
                if s:
                    consts.append(shared[si])
                    si += 1
                else:
                    consts.append(stacked[di][tag])
                    di += 1
            outs = core.eval_jaxpr(jaxpr, consts, row[None, :])
            return tuple(o[0] for o in outs)

        return jax.vmap(one)(rows, tags)

    return kernel


class CohortProgram:
    """One shape cohort: the unified kernels + per-instance metadata the
    sweep engine consumes.

    ``instances`` keep their SPEC order; ``tags`` are local (0..K-1)
    row tags, ``global_index[i]`` maps a local tag back to the
    instance's position in the whole sweep (which, with the instance
    seed, derives its namespace word — so cohort grouping never changes
    any instance's fingerprints)."""

    def __init__(self, instances: Sequence, global_index: Sequence[int],
                 ns_bits: int):
        self.instances = list(instances)
        self.global_index = [int(g) for g in global_index]
        self.K = len(self.instances)
        self.twins = [i.model._tensor_cached() for i in self.instances]
        t0 = self.twins[0]
        self.width = int(t0.width)
        self.max_actions = int(t0.max_actions)
        self.props = list(self.instances[0].model.properties())
        self.n_props = len(self.props)
        # namespace parameters (fingerprint.ns_fingerprint): the low
        # ``ns_bits`` key bits carry the GLOBAL tag; a nonzero seed
        # additionally scrambles the high key bits (table-seed fuzzing)
        self.ns_bits = int(ns_bits)
        self.ns_low_np = np.asarray(self.global_index, np.uint64)
        self.ns_xor_np = np.asarray(
            [
                0 if not inst.seed
                else mix64(fold64(SWEEP_NS_SEED, inst.seed))
                for inst in self.instances
            ],
            np.uint64,
        )
        # per-instance target (unique-count early termination); -1 = none
        self.targets_np = np.asarray(
            [
                -1 if inst.target is None else int(inst.target)
                for inst in self.instances
            ],
            np.int64,
        )
        self.unified = True  # False once _build falls back to twin 0
        self._step = None
        self._masks = None
        self._build()

    # -- kernel construction -------------------------------------------------

    def _build(self) -> None:
        if self.K == 1:
            # a singleton cohort runs the twin's own kernels directly —
            # zero unification overhead, exactly the sequential program
            t = self.twins[0]
            self._step = lambda rows, tags: t.step_rows(rows)
            self._masks = lambda rows, tags: t.property_masks(rows)
            return
        traced = {
            name: [_trace_kernel(t, name) for t in self.twins]
            for name in _KERNELS
        }
        unified = {
            name: unify_jaxprs(traced[name]) for name in _KERNELS
        }
        if any(u is None for u in unified.values()):
            raise CohortSplit("kernels do not unify")
        if all(
            not any(not s for s, _ in u[1]) for u in unified.values()
        ):
            # every constant is shared: the twins' programs are
            # literally identical (seed-only sweeps) — run twin 0's own
            # kernels and skip the per-row gather entirely
            t = self.twins[0]
            self._step = lambda rows, tags: t.step_rows(rows)
            self._masks = lambda rows, tags: t.property_masks(rows)
        else:
            sj, sc = unified["step_rows"]
            pj, pc = unified["property_masks"]
            self._step = _unified_kernel(sj, sc)
            mk = _unified_kernel(pj, pc)
            self._masks = lambda rows, tags: mk(rows, tags)[0]
        self._verify()

    def _verify(self) -> None:
        """Build-time backstop: the cohort kernel must reproduce every
        instance's own kernels on that instance's init rows — valid
        masks and property masks exactly, successors exactly on valid
        lanes.  A mismatch raises :class:`CohortSplit` and the group
        demotes to singleton cohorts (correct, just more compiles)."""
        import jax.numpy as jnp

        for tag, twin in enumerate(self.twins):
            rows = jnp.asarray(
                np.asarray(twin.init_rows(), np.uint64)
            )
            tags = jnp.full((rows.shape[0],), tag, jnp.int32)
            succ_c, valid_c = self._step(rows, tags)
            succ_t, valid_t = twin.step_rows(rows)
            if not np.array_equal(
                np.asarray(valid_c), np.asarray(valid_t)
            ):
                raise CohortSplit(
                    f"validity mismatch for {self.instances[tag].key!r}"
                )
            v = np.asarray(valid_t)
            if not np.array_equal(
                np.asarray(succ_c)[v], np.asarray(succ_t)[v]
            ):
                raise CohortSplit(
                    f"successor mismatch for {self.instances[tag].key!r}"
                )
            if not np.array_equal(
                np.asarray(self._masks(rows, tags)),
                np.asarray(twin.property_masks(rows)),
            ):
                raise CohortSplit(
                    f"property mismatch for {self.instances[tag].key!r}"
                )

    # -- engine-facing -------------------------------------------------------

    def step_rows(self, rows, tags):
        return self._step(rows, tags)

    def property_masks(self, rows, tags):
        return self._masks(rows, tags)

    def init_data(self):
        """Concatenated init rows + local tags across the cohort, in
        spec order (the engine inserts them as one batch)."""
        rows, tags = [], []
        for t, twin in enumerate(self.twins):
            r = np.asarray(twin.init_rows(), np.uint64)
            rows.append(r)
            tags.append(np.full((r.shape[0],), t, np.int32))
        return np.concatenate(rows), np.concatenate(tags)


class CohortSplit(Exception):
    """Internal: a candidate group cannot share one program."""


def build_cohorts(spec) -> list:
    """Group the spec's instances into shape cohorts, in order of first
    appearance; groups whose kernels fail to unify (or fail the
    build-time verification) split into singleton cohorts — LOUDLY, so
    a sweep that silently compiles K programs never masquerades as one
    program."""
    import sys

    ns_bits = sweep_ns_bits(len(spec.instances))
    groups: dict = {}
    order: list = []
    for gi, inst in enumerate(spec.instances):
        tensor = inst.model._tensor_cached()
        if tensor is None:
            raise TypeError(
                f"sweep instance {inst.key!r}: "
                f"{type(inst.model).__name__} has no tensor twin — "
                "sweeps run on the device engine only (docs/sweep.md)"
            )
        sig = shape_signature(inst)
        if sig not in groups:
            groups[sig] = []
            order.append(sig)
        groups[sig].append((gi, inst))
    cohorts = []
    for sig in order:
        members = groups[sig]
        insts = [i for _, i in members]
        gidx = [g for g, _ in members]
        try:
            cohorts.append(CohortProgram(insts, gidx, ns_bits))
        except CohortSplit as e:
            print(
                f"stateright-tpu: sweep: {len(insts)} instances of "
                f"{sig[0]} do not share one program ({e}); compiling "
                "separately (docs/sweep.md)",
                file=sys.stderr,
            )
            for g, inst in members:
                cohorts.append(CohortProgram([inst], [g], ns_bits))
    return cohorts

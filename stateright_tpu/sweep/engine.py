"""The sweep wavefront engine: one device BFS per shape cohort, all
instances concurrently over a shared, namespace-partitioned visited
table (docs/sweep.md).

A purpose-built sibling of ``parallel/wavefront.py``: the same FIFO
queue / bucketized-table / clean-boundary-growth discipline, with three
structural differences —

 - every queue row carries an **instance tag** (a parallel ``q_tag``
   buffer, like ``q_ebits``); successors inherit their parent's tag, and
   the step kernel gathers per-instance constants by it
   (``sweep/cohort.py``);
 - fingerprints are **namespaced** per instance before touching the
   shared table (``ops.hashing.ns_hash``: the instance tag replaces the
   LOW bits of the table sort key ``mix64(fp)``, inverted back through
   ``unmix64``), so instances can never collide — and parent chains stay
   within one instance by construction;
 - every counter is **per instance**: unique/total/max-depth vectors,
   a ``[K, P]`` discovery matrix with per-instance first-hit recording,
   and per-instance done/target early termination — a finished instance
   masks its rows out of expansion without stalling the cohort.

Exactness argument (pinned by tests/test_sweep.py): queue appends are
in TABLE order — sorted by the candidates' sort key — and the
namespacing is deliberately ORDER-PRESERVING within an instance (the
tag lands in the key's low bits; the high bits keep the sequential
run's order), so an instance's rows keep exactly the relative FIFO
order its own sequential run produces, its candidate lanes keep their
relative order (row-major expansion), and novelty is a pure function of
its own namespaced fingerprint set — hence each instance's BFS order,
unique/total counts, per-property first-hit states (discovery traces),
and parent pointers are identical to its own sequential wavefront run.
The one caveat: the sweep's per-instance depth histogram is derived as
an exact bincount at extraction, while the wavefront's live histogram
is the sorted-prefix approximation — two estimators of the same
quantity (docs/sweep.md).

Per-instance targets terminate an instance once its unique count
crosses the target at a batch boundary; because batches interleave
instances, the cut point can differ from a sequential run's (the same
"roughly count" semantics as ``target_states``) — full-enumeration
instances reconcile bit-identically.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..checker.base import Checker, CheckerBuilder
from ..checker.path import Path
from ..core import Expectation
from ..fingerprint import ns_fingerprint
from ..ops.buckets import SLOTS, bucket_insert, host_bucket_rehash
from ..ops.hashing import EMPTY, ns_hash, row_hash
from ..ops.por import candidate_novelty
from ..parallel.prewarm import CompileWatch
from ..parallel.wavefront import _pow2
from .cohort import build_cohorts
from .spec import SWEEP_V

_STATUS_OK = 0
_STATUS_QUEUE_FULL = 1
_STATUS_TABLE_FULL = 2
_STATUS_CAND_FULL = 3
_STATUS_POISON = 4

_STATUS_NAMES = {
    _STATUS_OK: "ok",
    _STATUS_QUEUE_FULL: "queue_full",
    _STATUS_TABLE_FULL: "table_full",
    _STATUS_CAND_FULL: "cand_full",
    _STATUS_POISON: "poison",
}

# carry indices (base tuple; the cartography tail follows when enabled)
(_TFP, _TPL, _QROWS, _QFP, _QTAG, _QEBITS, _QDEPTH, _HEAD, _TAIL,
 _UNIQK, _SCNTK, _DISC, _MAXDK, _STATUS) = range(14)
_CART_START = 14

_SNAPSHOT_KEYS = (
    "table_fp", "table_parent", "q_rows", "q_fp", "q_tag", "q_ebits",
    "q_depth", "head", "tail", "unique_k", "scount_k", "disc",
    "maxdepth_k", "status",
)


def _build_sweep_engine(cohort, cap: int, qcap: int, batch: int,
                        steps: int, cand: Optional[int],
                        cartography: bool = False):
    """``(init_fn, run_fn)`` for one cohort at fixed capacities — the
    sweep analogue of ``wavefront._build_engine`` (no POR/spill/MXU/
    checked tails; the builder rejects those for sweeps)."""
    width, arity, K = cohort.width, cohort.max_actions, cohort.K
    m = batch * arity
    eff_cand = min(cand, m) if cand else m
    qalloc = qcap + m
    props = cohort.props
    n_props = cohort.n_props
    p_dim = max(n_props, 1)
    ev_idx = [
        i for i, p in enumerate(props)
        if p.expectation is Expectation.EVENTUALLY
    ]
    ebit_of = {i: e for e, i in enumerate(ev_idx)}
    if len(ev_idx) > 32:
        raise ValueError("at most 32 eventually properties are supported")
    init_ebits = jnp.uint32((1 << len(ev_idx)) - 1)

    init_rows_np, init_tags_np = cohort.init_data()
    n_init = init_rows_np.shape[0]

    ns_low = jnp.asarray(cohort.ns_low_np)
    ns_xor = jnp.asarray(cohort.ns_xor_np)
    ns_bits = cohort.ns_bits
    # -1 = no target: substitute a sentinel no count reaches
    tg = cohort.targets_np.copy()
    tg[tg < 0] = np.int64(1) << 62
    targets = jnp.asarray(tg)

    twin0 = cohort.twins[0]
    boundary_fn = (
        twin0.boundary_rows
        if getattr(twin0, "has_boundary", False)
        else None
    )
    poison_fn = getattr(twin0, "poison_rows", None)

    def cand_ns(fps, tags):
        """Namespaced candidate fingerprints: the lane's instance tag
        lands in the low sort-key bits (order-preserving; its seed
        scramble, if any, in the high bits) — see ops/hashing.ns_hash."""
        return ns_hash(fps, ns_low[tags], ns_xor[tags], ns_bits)

    def done_of(disc2, uniq_k):
        """bool[K]: all properties discovered, or target reached."""
        tgt = uniq_k >= targets
        if n_props == 0:
            return tgt
        return jnp.all(disc2 != jnp.uint64(0), axis=1) | tgt

    def record_first_k(disc2, i, hit, fps, tags):
        """First-hit-per-instance discovery of property ``i``."""
        b = hit.shape[0]
        order = jnp.where(
            hit, jnp.arange(b, dtype=jnp.int32), jnp.int32(b)
        )
        first = jax.ops.segment_min(order, tags, num_segments=K)
        has = first < b
        fp_first = fps[jnp.clip(first, 0, b - 1)]
        take = has & (disc2[:, i] == jnp.uint64(0))
        return disc2.at[:, i].set(
            jnp.where(take, fp_first, disc2[:, i])
        )

    def eval_props(masks, fps, act, ebits, disc2, tags):
        for i, p in enumerate(props):
            if p.expectation is Expectation.ALWAYS:
                disc2 = record_first_k(
                    disc2, i, act & ~masks[..., i], fps, tags
                )
            elif p.expectation is Expectation.SOMETIMES:
                disc2 = record_first_k(
                    disc2, i, act & masks[..., i], fps, tags
                )
            else:
                clear = jnp.uint32(~(1 << ebit_of[i]) & 0xFFFFFFFF)
                ebits = jnp.where(masks[..., i], ebits & clear, ebits)
        return ebits, disc2

    def flush_terminal(terminal, fps, ebits, disc2, tags):
        for i in ev_idx:
            bit = (ebits >> jnp.uint32(ebit_of[i])) & jnp.uint32(1)
            disc2 = record_first_k(
                disc2, i, terminal & (bit == jnp.uint32(1)), fps, tags
            )
        return disc2

    def step(carry):
        (tfp, tpl, qrows, qfp, qtag, qebits, qdepth, head, tail,
         uniq_k, scnt_k, disc2, maxd_k, status) = carry[:_CART_START]
        cart = carry[_CART_START:]
        n_avail = tail - head
        rows = jax.lax.dynamic_slice(
            qrows, (head, jnp.int32(0)), (batch, width)
        )
        fps = jax.lax.dynamic_slice(qfp, (head,), (batch,))
        tags = jax.lax.dynamic_slice(qtag, (head,), (batch,)).astype(
            jnp.int32
        )
        ebits = jax.lax.dynamic_slice(qebits, (head,), (batch,))
        depths = jax.lax.dynamic_slice(qdepth, (head,), (batch,))
        live = jnp.arange(batch, dtype=jnp.int32) < n_avail

        masks = cohort.property_masks(rows, tags)  # [B, P]
        # per-instance early termination: rows of a done instance are
        # popped but neither evaluated nor expanded (disc is first-wins,
        # so late evaluation could not change verdicts anyway — the mask
        # keeps the evaluated tallies reconciling per instance)
        done_k = done_of(disc2, uniq_k)
        act = live & ~done_k[tags]
        ebits, disc2 = eval_props(masks, fps, act, ebits, disc2, tags)
        d32 = jnp.where(act, depths, 0).astype(jnp.int32)
        maxd_k = jnp.maximum(
            maxd_k,
            jnp.maximum(
                jax.ops.segment_max(d32, tags, num_segments=K), 0
            ),
        )
        done_k = done_of(disc2, uniq_k)
        elive = act & ~done_k[tags]

        succ, valid = cohort.step_rows(rows, tags)  # [B, A, W], [B, A]
        if boundary_fn is not None:
            valid = valid & boundary_fn(succ)
        valid = valid & elive[:, None]
        terminal = elive & ~jnp.any(valid, axis=-1)
        disc2 = flush_terminal(terminal, fps, ebits, disc2, tags)

        tag_la = jnp.broadcast_to(tags[:, None], (batch, arity))
        cand_fp = jnp.where(
            valid, cand_ns(row_hash(succ), tag_la), EMPTY
        ).reshape(m)
        cand_tag = tag_la.reshape(m)
        cand_rows = succ.reshape(m, width)
        cand_par = jnp.broadcast_to(
            fps[:, None], (batch, arity)
        ).reshape(-1)
        cand_ebt = jnp.broadcast_to(
            ebits[:, None], (batch, arity)
        ).reshape(-1)
        cand_dep = jnp.broadcast_to(
            depths[:, None] + jnp.uint32(1), (batch, arity)
        ).reshape(-1)

        tfp, tpl, sel, n_new, toverflow, coverflow = bucket_insert(
            tfp, tpl, cand_fp, cand_par, window=batch,
            compact=eff_cand,
        )
        qrows = jax.lax.dynamic_update_slice(
            qrows, cand_rows[sel], (tail, jnp.int32(0))
        )
        qfp = jax.lax.dynamic_update_slice(qfp, cand_fp[sel], (tail,))
        qtag = jax.lax.dynamic_update_slice(
            qtag, cand_tag[sel].astype(jnp.uint32), (tail,)
        )
        qebits = jax.lax.dynamic_update_slice(
            qebits, cand_ebt[sel], (tail,)
        )
        qdepth = jax.lax.dynamic_update_slice(
            qdepth, cand_dep[sel], (tail,)
        )

        overflow = toverflow | coverflow
        novel = candidate_novelty(m, sel, n_new)
        zero_k = jnp.zeros((K,), jnp.int64)
        d_uniq = jax.ops.segment_sum(
            novel.astype(jnp.int64), cand_tag, num_segments=K
        )
        d_scnt = jax.ops.segment_sum(
            valid.reshape(m).astype(jnp.int64), cand_tag,
            num_segments=K,
        )
        uniq_k = uniq_k + jnp.where(overflow, zero_k, d_uniq)
        scnt_k = scnt_k + jnp.where(overflow, zero_k, d_scnt)
        head = jnp.where(
            overflow, head, head + jnp.minimum(n_avail, batch)
        )
        n_new = jnp.where(overflow, 0, n_new)
        tail = tail + n_new
        unique_tot = jnp.sum(uniq_k)
        status = jnp.where(
            toverflow | (unique_tot * 4 > cap) | (eff_cand * 4 > cap),
            jnp.int32(_STATUS_TABLE_FULL),
            jnp.where(
                coverflow,
                jnp.int32(_STATUS_CAND_FULL),
                jnp.where(
                    tail > qcap,
                    jnp.int32(_STATUS_QUEUE_FULL),
                    status,
                ),
            ),
        )
        if poison_fn is not None:
            status = jnp.where(
                jnp.any(poison_fn(rows) & live),
                jnp.int32(_STATUS_POISON),
                status,
            )
        if cartography:
            act_hist, p_evals, p_hits = cart
            gen = valid.astype(jnp.int64)  # [B, A]
            ev = act.astype(jnp.int64)
            hits = (act[:, None] & masks).astype(jnp.int64)
            zero = jnp.int64(0)
            act_hist = act_hist.at[tags].add(
                jnp.where(overflow, zero, gen)
            )
            p_evals = p_evals.at[tags].add(
                jnp.where(
                    overflow, zero,
                    jnp.broadcast_to(ev[:, None], (batch, p_dim)),
                )
            )
            p_hits = p_hits.at[tags].add(
                jnp.where(overflow, zero, _pad_props(hits, p_dim))
            )
            cart = (act_hist, p_evals, p_hits)
        out = (tfp, tpl, qrows, qfp, qtag, qebits, qdepth, head, tail,
               uniq_k, scnt_k, disc2, maxd_k, status)
        return out + tuple(cart)

    def cond(state):
        k, carry = state
        go = (carry[_STATUS] == jnp.int32(_STATUS_OK)) & (k < steps)
        go = go & (carry[_TAIL] > carry[_HEAD])
        go = go & ~jnp.all(done_of(carry[_DISC], carry[_UNIQK]))
        return go

    def stats_of(carry):
        parts = [
            jnp.stack([
                carry[_HEAD].astype(jnp.uint64),
                carry[_TAIL].astype(jnp.uint64),
                carry[_STATUS].astype(jnp.uint64),
            ]),
            carry[_UNIQK].astype(jnp.uint64),
            carry[_SCNTK].astype(jnp.uint64),
            carry[_MAXDK].astype(jnp.uint64),
            carry[_DISC].reshape(-1),
        ]
        if cartography:
            parts += [
                c.astype(jnp.uint64).reshape(-1)
                for c in carry[_CART_START:]
            ]
        return jnp.concatenate(parts)

    def _run_impl(carry):
        _, carry = jax.lax.while_loop(
            cond, lambda s: (s[0] + 1, step(s[1])), (jnp.int32(0), carry)
        )
        return carry, stats_of(carry)

    run_fn = jax.jit(_run_impl)

    @jax.jit
    def init_fn():
        tfp = jnp.full((cap,), EMPTY, jnp.uint64)
        tpl = jnp.zeros((cap,), jnp.uint64)
        qrows = jnp.zeros((qalloc, width), jnp.uint64)
        qfp = jnp.full((qalloc,), EMPTY, jnp.uint64)
        qtag = jnp.zeros((qalloc,), jnp.uint32)
        qebits = jnp.zeros((qalloc,), jnp.uint32)
        qdepth = jnp.zeros((qalloc,), jnp.uint32)

        irows = jnp.asarray(init_rows_np)
        itags = jnp.asarray(init_tags_np)
        ifp = cand_ns(row_hash(irows), itags)
        tfp, tpl, sel, n_new, overflow, _ = bucket_insert(
            tfp, tpl, ifp,
            jnp.zeros((n_init,), jnp.uint64),
            window=n_init,
        )
        qrows = jax.lax.dynamic_update_slice(
            qrows, irows[sel], (jnp.int32(0), jnp.int32(0))
        )
        qfp = jax.lax.dynamic_update_slice(qfp, ifp[sel], (jnp.int32(0),))
        qtag = jax.lax.dynamic_update_slice(
            qtag, itags[sel].astype(jnp.uint32), (jnp.int32(0),)
        )
        qebits = jax.lax.dynamic_update_slice(
            qebits,
            jnp.full((n_init,), init_ebits, jnp.uint32),
            (jnp.int32(0),),
        )
        novel = candidate_novelty(n_init, sel, n_new)
        uniq_k = jax.ops.segment_sum(
            novel.astype(jnp.int64), itags, num_segments=K
        )
        scnt_k = jax.ops.segment_sum(
            jnp.ones((n_init,), jnp.int64), itags, num_segments=K
        )
        status = jnp.where(
            overflow
            | (n_new.astype(jnp.int64) * 4 > cap)
            | (eff_cand * 4 > cap),
            jnp.int32(_STATUS_TABLE_FULL),
            jnp.where(
                n_new > qcap,
                jnp.int32(_STATUS_QUEUE_FULL),
                jnp.int32(_STATUS_OK),
            ),
        )
        carry = (tfp, tpl, qrows, qfp, qtag, qebits, qdepth,
                 jnp.int32(0), n_new, uniq_k, scnt_k,
                 jnp.zeros((K, p_dim), jnp.uint64),
                 jnp.zeros((K,), jnp.int32),
                 status)
        if cartography:
            carry = carry + (
                jnp.zeros((K, max(arity, 1)), jnp.int64),
                jnp.zeros((K, p_dim), jnp.int64),
                jnp.zeros((K, p_dim), jnp.int64),
            )
        return carry, stats_of(carry)

    return init_fn, run_fn


def _pad_props(hits, p_dim: int):
    """[B, P] -> [B, max(P, 1)] (models with zero properties still carry
    one tally column so the carry shapes stay static)."""
    if hits.shape[-1] == p_dim:
        return hits
    return jnp.zeros(hits.shape[:-1] + (p_dim,), hits.dtype)


class InstanceResult:
    """Per-instance outcome of a joined sweep (JSON-safe scalars + the
    discovery fingerprints; trace chains walked at cohort end)."""

    def __init__(self, instance, global_index, cohort_index):
        self.key = instance.key
        self.params = dict(instance.params)
        self.seed = instance.seed
        self.target = instance.target
        self.global_index = int(global_index)
        self.cohort = int(cohort_index)
        self.unique = 0
        self.states = 0
        self.max_depth = 0
        self.disc = np.zeros(0, np.uint64)
        self.chains: dict = {}  # prop name -> [ns'd fp chain]
        self.cartography: Optional[dict] = None

    def as_dict(self) -> dict:
        return {
            "key": self.key,
            "params": self.params,
            "seed": self.seed,
            "cohort": self.cohort,
            "unique": int(self.unique),
            "states": int(self.states),
            "max_depth": int(self.max_depth),
            "discoveries": sorted(self.chains),
        }


class SweepChecker(Checker):
    """One device run checking a whole model family.

    Spawned by ``CheckerBuilder.sweep(SPEC).spawn_tpu(...)``: cohorts
    run back to back on the device (one compiled engine per cohort),
    every instance's counters/verdicts/traces extract independently at
    join, and — when a run registry is configured — one record per
    instance archives under this sweep's ``sweep_id``.
    """

    _engine_tag = "sweep"

    def __init__(
        self,
        options: CheckerBuilder,
        spec,
        capacity: int = 1 << 17,
        batch: Optional[int] = None,
        queue_capacity: Optional[int] = None,
        steps_per_call: int = 64,
        cand: Optional[int] = None,
        sync: bool = False,
        resume: Optional[dict] = None,
    ):
        self.model = options.model
        self.spec = spec
        self._options = options
        self._reject_unsupported(options)
        self._cap = max(_pow2(capacity), 4 * SLOTS)
        self._batch = max(8, batch or (1 << 11))
        self._cand = cand or max(4 * self._batch, 4096)
        self._qcap = queue_capacity or max(self._cap // 2, 4 * self._batch)
        self._steps = steps_per_call
        self._resume = resume
        self._telemetry_opts = options.telemetry_opts or {}
        self._cartography = bool(self._telemetry_opts.get("cartography"))
        self._report_path = getattr(options, "report_path", None)
        self._run_dir = getattr(options, "run_dir", None)
        self._span_parent = getattr(options, "_span_ctx", None)
        self.flight_recorder = options._make_recorder("sweep")
        self.cohorts = build_cohorts(spec)
        self.results: dict = {}
        for ci, cohort in enumerate(self.cohorts):
            for t, inst in enumerate(cohort.instances):
                self.results[inst.key] = InstanceResult(
                    inst, cohort.global_index[t], ci
                )
        self.engine_compiles = 0
        self.growth_events: list = []
        self._instance_run_ids: dict = {}
        self._done = threading.Event()
        self._stop = threading.Event()
        self._ckpt_req: Optional[threading.Event] = None
        self._ckpt_out: Optional[dict] = None
        self._ckpt_ready = threading.Event()
        self._ckpt_lock = threading.Lock()
        self._live = (0, 0)
        self._cohort_idx = 0
        self._timed_out = False
        self._run_error: Optional[BaseException] = None
        if resume is not None:
            self._check_resume_sig(resume)
        if options.timeout_secs is not None:
            timer = threading.Timer(
                options.timeout_secs, self._deadline_stop
            )
            timer.daemon = True
            timer.start()
        self._thread = None
        if sync:
            self._run_guarded()
            if self._run_error is not None:
                err, self._run_error = self._run_error, None
                raise err
            self._maybe_write_report()
        else:
            self._thread = threading.Thread(
                target=self._run_guarded, daemon=True
            )
            self._thread.start()

    # -- configuration fences ------------------------------------------------

    @staticmethod
    def _reject_unsupported(options: CheckerBuilder) -> None:
        from ..parallel.prewarm import (
            ENV_POR,
            ENV_PREDEDUP,
            ENV_SPILL,
            resolve_flag,
        )

        rejects = []
        if options.checked_mode:
            rejects.append("checked()")
        if resolve_flag(getattr(options, "por_mode", None), ENV_POR):
            rejects.append("por()")
        if resolve_flag(getattr(options, "spill_mode", None), ENV_SPILL):
            rejects.append("spill()")
        if resolve_flag(
            getattr(options, "prededup_mode", None), ENV_PREDEDUP
        ):
            rejects.append("prededup()")
        from ..ops.mxu import resolve_mxu

        if resolve_mxu(getattr(options, "mxu_opts", None)) is not None:
            rejects.append("mxu()")
        if options.symmetry_fn is not None:
            rejects.append("symmetry()")
        if options.visitor_obj is not None:
            rejects.append("visitor()")
        if getattr(options, "autosave_opts", None) is not None:
            rejects.append("autosave()")
        if rejects:
            raise NotImplementedError(
                "sweep mode does not compose with "
                + "/".join(rejects)
                + " yet — run those per instance on the plain wavefront "
                "engine (docs/sweep.md)"
            )

    def _deadline_stop(self) -> None:
        if not self._done.is_set():
            self._timed_out = True
        self._stop.set()

    @property
    def timed_out(self) -> bool:
        return self._timed_out

    # -- resume protocol -----------------------------------------------------

    def _sweep_sig(self) -> np.ndarray:
        """Sweep identity for resume: per-instance (ns word, model init
        fingerprints, twin shape) — a different spec (order, seeds,
        members, layouts) must refuse a foreign snapshot."""
        import hashlib
        import json

        src = []
        for ci, cohort in enumerate(self.cohorts):
            for t, inst in enumerate(cohort.instances):
                fps = sorted(
                    int(inst.model.fingerprint_state(s))
                    for s in inst.model.init_states()
                )
                src.append([
                    inst.key, cohort.global_index[t], inst.seed,
                    cohort.ns_bits, fps,
                    cohort.width, cohort.max_actions, cohort.n_props,
                ])
        digest = hashlib.sha256(
            json.dumps(src, sort_keys=True).encode()
        ).digest()[:8]
        return np.frombuffer(digest, np.uint64).copy()

    def _check_resume_sig(self, snap: dict) -> None:
        tag = str(np.asarray(snap.get("engine", "")).item()) if hasattr(
            snap.get("engine", ""), "dtype"
        ) else str(snap.get("engine", ""))
        if tag != "sweep":
            raise ValueError(
                f"resume snapshot was taken by the {tag!r} engine; this "
                "is the sweep engine"
            )
        if not np.array_equal(self._sweep_sig(), snap["model_sig"]):
            raise ValueError(
                "resume snapshot was taken from a different sweep "
                "(instance keys / namespaces / layouts disagree)"
            )
        rid = snap.get("run_id")
        if rid is not None and self.parent_run_id is None:
            self.parent_run_id = (
                str(np.asarray(rid).item())
                if hasattr(rid, "dtype") else str(rid)
            )

    # -- snapshotting --------------------------------------------------------

    def _carry_to_snapshot(self, carry, ci, cap, qcap, cand) -> dict:
        import json

        snap = {
            k: np.asarray(v) for k, v in zip(_SNAPSHOT_KEYS, carry)
        }
        snap["cap"], snap["qcap"] = cap, qcap
        snap["batch"], snap["cand"] = self._batch, cand
        snap["cohort"] = np.int64(ci)
        snap["engine"] = "sweep"
        snap["model_sig"] = self._sweep_sig()
        snap["run_id"] = self.run_id
        # completed cohorts: per-instance results + walked discovery
        # chains travel as a JSON manifest (the tables are gone)
        done = {}
        for ck in range(ci):
            for inst in self.cohorts[ck].instances:
                r = self.results[inst.key]
                done[inst.key] = {
                    "unique": int(r.unique),
                    "states": int(r.states),
                    "max_depth": int(r.max_depth),
                    "disc": [int(x) for x in np.asarray(r.disc)],
                    "chains": {
                        k: [int(f) for f in v]
                        for k, v in r.chains.items()
                    },
                    "cartography": r.cartography,
                }
        snap["sweep_done"] = json.dumps(done)
        if self._cartography and getattr(
            self, "_cart_depth_base", None
        ) is not None:
            # depth lanes banked by growth compactions: without them a
            # resumed per-instance depth histogram forgets every state
            # popped before a pre-snapshot growth (the wavefront
            # engine's cart_depth_base rule).  The per-step
            # action/property tallies restart at resume like the base
            # engine's (documented in docs/sweep.md).
            snap["cart_depth_base"] = self._cart_depth_base.copy()
        return snap

    def checkpoint(self, timeout: Optional[float] = 60.0) -> dict:
        if self._done.is_set() or self._thread is None:
            return dict(self._final_snapshot)
        with self._ckpt_lock:
            self._ckpt_req = self._ckpt_req or threading.Event()
            self._ckpt_ready.clear()
            self._ckpt_req.set()
            deadline = (
                None if timeout is None else time.monotonic() + timeout
            )
            while not self._ckpt_ready.wait(0.2):
                if self._done.is_set():
                    return dict(self._final_snapshot)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("checkpoint request not served")
            out, self._ckpt_out = self._ckpt_out, None
        if out is None:
            if self._done.is_set():
                return dict(self._final_snapshot)
            raise RuntimeError(
                "checkpoint signalled ready without a snapshot"
            )
        return out

    def stop(self) -> "SweepChecker":
        self._stop.set()
        return self

    # -- run loop ------------------------------------------------------------

    def _run_guarded(self) -> None:
        from ..telemetry.spans import start_span

        rec = self.flight_recorder
        sp = None
        if rec is not None:
            # engine_run span (telemetry/spans.py): parents under the
            # job/attempt span when the fleet/supervisor set
            # builder._span_ctx; roots a fresh trace otherwise
            sp = start_span("engine_run", parent=self._span_parent)
            rec.bind_span(sp.ctx.span_id)
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001 - re-raised at join()
            self._run_error = e
        finally:
            if sp is not None:
                sp.end(
                    rec,
                    engine="sweep",
                    error=(
                        type(self._run_error).__name__
                        if self._run_error else None
                    ),
                )
                rec.bind_span(None)
            self._done.set()

    def _restore_done(self, snap: dict) -> None:
        import json

        done = json.loads(str(np.asarray(snap["sweep_done"]).item()))
        for key, d in done.items():
            r = self.results[key]
            r.unique = d["unique"]
            r.states = d["states"]
            r.max_depth = d["max_depth"]
            r.disc = np.asarray(d["disc"], np.uint64)
            r.chains = {k: list(v) for k, v in d["chains"].items()}
            r.cartography = d.get("cartography")

    def _run(self) -> None:
        rec = self.flight_recorder
        start_ci = 0
        resume_carry = None
        if self._resume is not None:
            snap = self._resume
            start_ci = int(np.asarray(snap["cohort"]))
            self._restore_done(snap)
            self._batch = int(snap.get("batch", self._batch))
            resume_carry = snap
        for ci in range(start_ci, len(self.cohorts)):
            self._cohort_idx = ci
            if self._stop.is_set():
                break
            self._run_cohort(
                ci, resume=resume_carry if ci == start_ci else None
            )
            resume_carry = None
        if rec is not None:
            rec.record(
                "sweep", v=SWEEP_V, event="summary",
                instances=len(self.spec), cohorts=len(self.cohorts),
                engine_compiles=int(self.engine_compiles),
            )
            rec.close_run(done=not self._timed_out)

    def _timed_call(self, fn, arg=None):
        rec = self.flight_recorder
        watch = CompileWatch() if rec is not None else None
        t0 = time.monotonic()
        carry, stats = fn() if arg is None else fn(arg)
        carry = list(carry)
        stats = np.asarray(stats)
        if rec is not None:
            dt = time.monotonic() - t0
            d = watch.delta()
            comp = min(max(d["compile_secs"], 0.0), dt)
            rec.add("stage_compile_secs", comp)
            rec.add("stage_device_secs", dt - comp)
            if comp > 0 and self._pending_compile is not None:
                prev = self._pending_compile
                rec.amend(
                    prev,
                    duration=round(
                        float(prev.get("duration", 0.0)) + comp, 6
                    ),
                )
            elif self._pending_compile is not None:
                self._pending_compile = None
        return carry, stats

    def _engine(self, cohort, ci, cap, qcap, batch, cand,
                kind: str = "growth"):
        key = (ci, cap, qcap, batch, cand)
        eng = self._engine_cache.get(key)
        if eng is not None:
            return eng
        rec = self.flight_recorder
        self.engine_compiles += 1
        if rec is not None:
            self._pending_compile = rec.record(
                "compile", cap=cap, qcap=qcap, batch=batch, cand=cand,
                rung=kind, source="fresh", cache_hit=False,
                duration=0.0,
            )
            rec.record(
                "sweep", v=SWEEP_V, event="cohort_compile",
                cohort=ci, instances=cohort.K, width=cohort.width,
                arity=cohort.max_actions,
                unified=bool(cohort.unified and cohort.K > 1),
            )
        eng = _build_sweep_engine(
            cohort, cap, qcap, batch, self._steps, cand,
            cartography=self._cartography,
        )
        self._engine_cache[key] = eng
        return eng

    def _grow(self, carry_np, ci, cap, qcap, batch, status, cand):
        """Clean-boundary growth, the wavefront discipline: rehash the
        table on table-full, reclaim the consumed queue prefix (banking
        its per-instance depth lanes when cartography is on) and double
        the queue while still needed."""
        cohort = self.cohorts[ci]

        def table_small():
            return (
                int(np.sum(carry_np[_UNIQK])) * 4 > cap
                or cand * 4 > cap
            )

        if table_small() or status == _STATUS_TABLE_FULL:
            if table_small():
                while table_small():
                    cap *= 2
            else:
                cap *= 2
            tfp, tpl = host_bucket_rehash(
                carry_np[_TFP], carry_np[_TPL], cap // SLOTS
            )
            carry_np[_TFP], carry_np[_TPL] = tfp, tpl
        head, tail = int(carry_np[_HEAD]), int(carry_np[_TAIL])
        pending = tail - head
        self._bank_depth(
            cohort, carry_np[_QDEPTH], carry_np[_QTAG], head
        )
        for i in (_QROWS, _QFP, _QTAG, _QEBITS, _QDEPTH):
            carry_np[i] = np.asarray(carry_np[i])[head:tail].copy()
        carry_np[_HEAD] = np.int32(0)
        carry_np[_TAIL] = np.int32(pending)
        while pending * 2 > qcap:
            qcap *= 2
        carry_np[_STATUS] = np.int32(_STATUS_OK)
        self._repad(carry_np, qcap + batch * cohort.max_actions)
        return cap, qcap, carry_np

    @staticmethod
    def _repad(carry_np, qalloc: int) -> None:
        for i in (_QROWS, _QFP, _QTAG, _QEBITS, _QDEPTH):
            arr = np.asarray(carry_np[i])
            if arr.shape[0] < qalloc:
                pad = (qalloc - arr.shape[0],) + arr.shape[1:]
                fill = EMPTY if i == _QFP else 0
                arr = np.concatenate(
                    [arr, np.full(pad, fill, arr.dtype)]
                )
            carry_np[i] = (
                arr[:qalloc] if arr.ndim == 1 else arr[:qalloc, :]
            )

    def _bank_depth(self, cohort, qdepth, qtag, n: int) -> None:
        """Bank the consumed queue prefix's per-instance depth lanes
        (cartography only): the final per-instance depth histograms are
        queue-derived, and growth compaction drops the popped prefix."""
        if not self._cartography or n <= 0:
            return
        from ..ops.cartography import DEPTH_BINS

        dep = np.minimum(
            np.asarray(qdepth[:n], np.int64), DEPTH_BINS - 1
        )
        tag = np.asarray(qtag[:n], np.int64)
        np.add.at(self._cart_depth_base, (tag, dep), 1)

    def _run_cohort(self, ci: int, resume: Optional[dict] = None):
        cohort = self.cohorts[ci]
        rec = self.flight_recorder
        cap, qcap, batch = self._cap, self._qcap, self._batch
        arity = cohort.max_actions
        cand = min(self._cand, batch * arity)
        while cand * 4 > cap:
            cap *= 2
        n_init = cohort.init_data()[0].shape[0]
        while n_init > qcap:
            qcap *= 2
        self._engine_cache: dict = {}
        self._pending_compile = None
        if self._cartography:
            from ..ops.cartography import DEPTH_BINS

            self._cart_depth_base = np.zeros(
                (cohort.K, DEPTH_BINS), np.int64
            )
        if resume is not None:
            cap, qcap = int(resume["cap"]), int(resume["qcap"])
            cand = int(resume.get("cand", cand))
            if self._cartography and "cart_depth_base" in resume:
                self._cart_depth_base = np.asarray(
                    resume["cart_depth_base"], np.int64
                ).copy()
            carry = [np.asarray(resume[k]) for k in _SNAPSHOT_KEYS]
            st = int(carry[_STATUS])
            if st != _STATUS_OK:
                if st == _STATUS_CAND_FULL:
                    cand = min(cand * 2, batch * arity)
                cap, qcap, carry = self._grow(
                    carry, ci, cap, qcap, batch, st, cand
                )
            else:
                self._repad(carry, qcap + batch * arity)
            carry = [jnp.asarray(c) for c in carry]
            if self._cartography:
                carry = list(carry) + [
                    jnp.zeros((cohort.K, max(arity, 1)), jnp.int64),
                    jnp.zeros(
                        (cohort.K, max(cohort.n_props, 1)), jnp.int64
                    ),
                    jnp.zeros(
                        (cohort.K, max(cohort.n_props, 1)), jnp.int64
                    ),
                ]
            stats = None
        else:
            while True:
                init_fn, _ = self._engine(
                    cohort, ci, cap, qcap, batch, cand, kind="init"
                )
                carry, stats = self._timed_call(init_fn)
                if int(stats[2]) != _STATUS_TABLE_FULL:
                    break
                prev = cap
                while (n_init * 4 > cap) or (cand * 4 > cap):
                    cap *= 2
                if cap == prev:
                    cap *= 2
        k_dim, p_dim = cohort.K, max(cohort.n_props, 1)
        while True:
            if stats is None:
                stats = self._stats_np(carry, cohort)
            head, tail, status = (
                int(stats[0]), int(stats[1]), int(stats[2]),
            )
            o = 3
            uniq_k = stats[o:o + k_dim].astype(np.int64); o += k_dim
            scnt_k = stats[o:o + k_dim].astype(np.int64); o += k_dim
            maxd_k = stats[o:o + k_dim].astype(np.int64); o += k_dim
            disc2 = stats[o:o + k_dim * p_dim].reshape(k_dim, p_dim)
            o += k_dim * p_dim
            tot_u = int(uniq_k.sum()) + sum(
                r.unique for r in self.results.values()
                if r.cohort != ci
            )
            tot_s = int(scnt_k.sum()) + sum(
                r.states for r in self.results.values()
                if r.cohort != ci
            )
            self._live = (tot_s, tot_u)
            if rec is not None:
                rec.add_bytes(d2h=stats.nbytes)
                rec.step(
                    engine="sweep", states=tot_s, unique=tot_u,
                    status=status, queue=max(tail - head, 0), cap=cap,
                    cand=cand,
                    load_factor=round(int(uniq_k.sum()) / cap, 6),
                )
            if self._ckpt_req is not None and self._ckpt_req.is_set():
                self._ckpt_out = self._carry_to_snapshot(
                    [np.asarray(c) for c in carry[:_CART_START]],
                    ci, cap, qcap, cand,
                )
                self._ckpt_req.clear()
                self._ckpt_ready.set()
            if status == _STATUS_POISON:
                raise RuntimeError(
                    "poisoned rows reached by a sweep instance: a "
                    "compiled transition crossed its compile-time "
                    "state_bound/env_bound, or a hand-written twin's send "
                    "found no free network slot (n_slots); loosen the "
                    "bounds or raise n_slots"
                )
            if status != _STATUS_OK:
                self.growth_events.append((status, tot_u))
                if rec is not None:
                    rec.record(
                        "growth",
                        status=_STATUS_NAMES.get(status, str(status)),
                        unique=tot_u, cap=cap, qcap=qcap, cand=cand,
                        path="host",  # this engine's own _grow: pulled
                    )
                cart_tail = list(carry[_CART_START:])
                carry_np = [
                    np.asarray(c) for c in carry[:_CART_START]
                ]
                if status == _STATUS_CAND_FULL:
                    cand = min(cand * 2, batch * arity)
                    carry_np[_STATUS] = np.int32(_STATUS_OK)
                    while cand * 4 > cap:
                        cap, qcap, carry_np = self._grow(
                            carry_np, ci, cap, qcap, batch,
                            _STATUS_TABLE_FULL, cand,
                        )
                else:
                    cap, qcap, carry_np = self._grow(
                        carry_np, ci, cap, qcap, batch, status, cand
                    )
                carry = [jnp.asarray(c) for c in carry_np] + cart_tail
                stats = None
                continue
            if self._stop.is_set():
                break
            all_done = bool(
                np.all(self._done_k_np(cohort, disc2, uniq_k))
            )
            if tail <= head or all_done:
                break
            _, run_fn = self._engine(cohort, ci, cap, qcap, batch, cand)
            carry, stats = self._timed_call(run_fn, tuple(carry))
        self._extract_cohort(
            ci, carry, uniq_k, scnt_k, maxd_k, disc2, cap, qcap, cand
        )

    @staticmethod
    def _done_k_np(cohort, disc2, uniq_k) -> np.ndarray:
        tgt = cohort.targets_np.copy()
        tgt[tgt < 0] = np.int64(1) << 62
        done = uniq_k >= tgt
        if cohort.n_props:
            done = done | np.all(disc2 != 0, axis=1)
        return done

    def _stats_np(self, carry, cohort) -> np.ndarray:
        k_dim, p_dim = cohort.K, max(cohort.n_props, 1)
        vals = [
            np.asarray(carry[_HEAD]), np.asarray(carry[_TAIL]),
            np.asarray(carry[_STATUS]),
        ]
        out = np.asarray(vals, np.uint64)
        return np.concatenate([
            out,
            np.asarray(carry[_UNIQK]).astype(np.uint64),
            np.asarray(carry[_SCNTK]).astype(np.uint64),
            np.asarray(carry[_MAXDK]).astype(np.uint64),
            np.asarray(carry[_DISC]).reshape(-1),
        ])

    def _extract_cohort(self, ci, carry, uniq_k, scnt_k, maxd_k,
                        disc2, cap, qcap, cand) -> None:
        """Per-instance extraction at cohort end: counters, discovery
        chains (walked now, while the table exists), and — with
        cartography on — the per-instance reconciling counter set."""
        cohort = self.cohorts[ci]
        rec = self.flight_recorder
        tfp = np.asarray(carry[_TFP])
        tpl = np.asarray(carry[_TPL])
        occ = tfp != np.uint64(EMPTY)
        parents = dict(
            zip(tfp[occ].tolist(), tpl[occ].tolist())
        )
        self._last_cohort_carry = carry
        self._last_cohort_caps = (ci, cap, qcap, cand)
        if self._cartography:
            from ..ops.cartography import DEPTH_BINS, snapshot

            tail = int(np.asarray(carry[_TAIL]))
            dep = np.minimum(
                np.asarray(carry[_QDEPTH])[:tail].astype(np.int64),
                DEPTH_BINS - 1,
            )
            tag = np.asarray(carry[_QTAG])[:tail].astype(np.int64)
            dh = self._cart_depth_base.copy()
            np.add.at(dh, (tag, dep), 1)
            act_hist = np.asarray(carry[_CART_START])
            p_evals = np.asarray(carry[_CART_START + 1])
            p_hits = np.asarray(carry[_CART_START + 2])
        for t, inst in enumerate(cohort.instances):
            r = self.results[inst.key]
            r.unique = int(uniq_k[t])
            r.states = int(scnt_k[t])
            r.max_depth = int(maxd_k[t])
            r.disc = disc2[t].astype(np.uint64).copy()
            for i, p in enumerate(cohort.props):
                fp = int(r.disc[i])
                if fp != 0:
                    chain = [fp]
                    while True:
                        par = parents.get(chain[-1], 0)
                        if par == 0:
                            break
                        chain.append(par)
                    chain.reverse()
                    r.chains[p.name] = chain
            if self._cartography:
                r.cartography = snapshot(
                    depth_hist=dh[t], action_hist=act_hist[t],
                    prop_evals=p_evals[t][:max(cohort.n_props, 1)],
                    prop_hits=p_hits[t][:max(cohort.n_props, 1)],
                    prop_names=[p.name for p in cohort.props],
                    states=r.states, unique=r.unique,
                )
            if rec is not None:
                rec.record(
                    "sweep", v=SWEEP_V, event="instance_done",
                    key=inst.key, unique=r.unique, states=r.states,
                    depth=r.max_depth,
                )

    # -- result surface ------------------------------------------------------

    def is_done(self) -> bool:
        return self._done.is_set()

    def join(self) -> "SweepChecker":
        if self._thread is not None:
            self._thread.join()
        if self._run_error is not None:
            raise self._run_error
        self._maybe_write_report()
        return self

    def state_count(self) -> int:
        if self._done.is_set():
            return sum(r.states for r in self.results.values())
        return self._live[0]

    def unique_state_count(self) -> int:
        if self._done.is_set():
            return sum(r.unique for r in self.results.values())
        return self._live[1]

    def max_depth(self) -> int:
        if not self._done.is_set():
            return 0
        return max(
            (r.max_depth for r in self.results.values()), default=0
        )

    def discoveries(self) -> dict:
        """Aggregate view: ``"<instance key>: <property>"`` -> Path.
        Per-instance access: :meth:`instance_discoveries`."""
        self.join()
        out = {}
        for key in self.results:
            for name, path in self.instance_discoveries(key).items():
                out[f"{key}: {name}"] = path
        return out

    def instance_result(self, key: str) -> InstanceResult:
        self.join()
        return self.results[key]

    def _ns_key(self, key: str):
        """Host fingerprint key matching the instance's namespaced
        device fingerprints (``Path.from_fingerprints(key=...)``)."""
        r = self.results[key]
        inst = self.spec.instances[r.global_index]
        cohort = self.cohorts[r.cohort]
        tag, seed, bits = r.global_index, inst.seed, cohort.ns_bits
        model = inst.model
        return lambda s: ns_fingerprint(
            model.fingerprint_state(s), tag, seed, bits
        )

    def instance_discoveries(self, key: str) -> dict:
        self.join()
        r = self.results[key]
        inst = self.spec.instances[r.global_index]
        out = {}
        for name, chain in r.chains.items():
            out[name] = Path.from_fingerprints(
                inst.model, list(chain), key=self._ns_key(key)
            )
        return out

    def instance_view(self, key: str) -> "SweepInstanceView":
        """A checker-shaped view of one instance: what the report
        builder, the run registry, and the diff engine consume."""
        self.join()
        return SweepInstanceView(self, key)

    @property
    def _final_snapshot(self) -> dict:
        if not hasattr(self, "_last_cohort_carry"):
            if self._run_error is not None:
                raise self._run_error
            raise RuntimeError(
                "sweep has no snapshot: the run failed before "
                "completing a cohort"
            )
        ci, cap, qcap, cand = self._last_cohort_caps
        return self._carry_to_snapshot(
            [
                np.asarray(c)
                for c in self._last_cohort_carry[:_CART_START]
            ],
            ci, cap, qcap, cand,
        )

    def instance_run_id(self, key: str) -> str:
        rid = self._instance_run_ids.get(key)
        if rid is None:
            import uuid

            rid = uuid.uuid4().hex[:16]
            self._instance_run_ids[key] = rid
        return rid

    def _maybe_record_run(self, body=None) -> None:
        """One registry record PER INSTANCE, tagged with this sweep's
        ``sweep_id`` — so ``_cli compare`` and the Explorer dashboard
        work per instance (docs/sweep.md)."""
        if self._run_recorded or self._report_reentry:
            return
        from ..telemetry.registry import resolve_run_dir

        root = resolve_run_dir(self._run_dir)
        if not root:
            return
        self._run_recorded = True
        try:
            from ..telemetry.registry import RunRegistry
            from ..telemetry.report import build_report, identity_doc

            reg = RunRegistry(root)
            for key in self.results:
                view = self.instance_view(key)
                doc = identity_doc(view, build_report(view))
                doc["sweep_id"] = self.run_id
                doc["instance_key"] = key
                # a fleet-packed cohort (stateright_tpu/fleet/) tags
                # its members with the campaign; the instance key IS
                # the tenant's job key there
                cid = getattr(self, "_campaign_id", None)
                if cid:
                    doc["campaign_id"] = str(cid)
                    doc["job_key"] = key
                # checker=None: the headline stays count-derived — the
                # sweep recorder's wall clock is the whole family's, not
                # this instance's
                reg.record_doc(doc)
        except Exception as e:  # noqa: BLE001 - the ledger must never
            import sys

            print(
                "stateright-tpu: sweep registry write failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )


class SweepInstanceView:
    """Checker-shaped per-instance view over a joined sweep.

    Exposes exactly the surface ``telemetry/report.build_report`` /
    ``build_config`` read, so a sweep instance archives (and diffs)
    like a first-class run: ``config.instance.sig`` matches the same
    model's sequential run (engine ``sweep`` vs ``wavefront`` is an
    identical-class delta; the new ``sweep`` flag likewise), and
    ``compare --expect=IDENTICAL`` against the sequential oracle is the
    sweep's one-command parity check."""

    _engine_tag = "sweep"
    _is_sweep_instance = True
    flight_recorder = None
    parent_run_id = None
    timed_out = False

    def __init__(self, sweep: SweepChecker, key: str):
        self._sweep = sweep
        self._result = sweep.results[key]
        inst = sweep.spec.instances[self._result.global_index]
        self.model = inst.model
        self.tensor = inst.model._tensor_cached()
        self._target = inst.target
        self.key = key
        # flag honesty: the archived config says cartography iff the
        # sweep actually carried the per-instance counters
        self._cartography = bool(sweep._cartography)

    @property
    def run_id(self) -> str:
        return self._sweep.instance_run_id(self.key)

    def is_done(self) -> bool:
        return self._sweep.is_done()

    def state_count(self) -> int:
        return self._result.states

    def unique_state_count(self) -> int:
        return self._result.unique

    def max_depth(self) -> int:
        return self._result.max_depth

    def discoveries(self) -> dict:
        return self._sweep.instance_discoveries(self.key)

    def cartography(self) -> Optional[dict]:
        c = self._result.cartography
        return dict(c) if c else None

    def sweep_info(self) -> dict:
        return {
            "sweep_id": self._sweep.run_id,
            "instance_key": self.key,
            "params": dict(self._result.params),
            "seed": self._result.seed,
            "cohort": self._result.cohort,
        }

"""Multi-device sharded wavefront BFS — the ICI-mesh scale-out engine.

Scales the single-device wavefront engine (``wavefront.py``) across a
1-D ``jax.sharding.Mesh`` the way the reference scales across threads with a
work-stealing job market (reference ``src/checker/bfs.rs:70-151``) — except
that here "work distribution" is data-parallel sharding of the frontier and
"the shared visited set" (reference ``bfs.rs:26``) is partitioned by
fingerprint ownership:

 - Every device holds one shard of the visited hash table.  A fingerprint's
   owner is ``(fp >> 32) % D`` (high bits, so they stay independent of the
   low bits that pick the probe slot inside the owner's table shard).
 - Per wavefront, each device expands its local frontier slice, then routes
   every candidate successor to its owner via ``lax.all_to_all`` over the
   mesh axis — the ICI is the "job market".
 - The owner dedupes + claims table slots locally (``ops/buckets.py``) and
   keeps its novel states as its slice of the next frontier, so the frontier
   stays balanced by fingerprint uniformity rather than explicit stealing.
 - Counters and termination are ``psum``/``pmax`` all-reduces (reference
   analogue: the atomic ``state_count`` + "all threads waiting" test,
   ``bfs.rs:25,94-98``).

The whole run — expansion, routing, dedup, property kernels, termination —
is one jitted ``shard_map`` with a ``lax.while_loop`` inside: zero host
round-trips until the check finishes.  Collective-uniformity note: every
branch decision inside the loop derives from replicated values (psum/pmax
results), so all devices always execute the same collective sequence.

**Growth without lost work** (same protocol as ``wavefront.py``): every
capacity is a static shape, but each jitted step is ATOMIC — when a step
overflows the table, the frontier, or a route bucket, it returns the
pre-step carry with only the status code advanced.  The host then pulls the
carry once, grows the offending buffer host-side (rehashing each device's
table shard independently — fingerprint ownership is capacity-independent,
so shards never exchange entries during growth — or padding each device's
frontier segment), and resumes the run through a freshly built engine.
Counters, discoveries, and the visited set all survive; the overflowing
wavefront simply replays at the new capacity.  A proactive trigger grows
the table at 25% shard load before bucket overflows become likely.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from ..checker.base import CheckerBuilder
from ..core import Expectation
from ..ops.buckets import SLOTS, bucket_insert, window_unique
from ..ops.hashing import EMPTY, row_hash
from ..telemetry.spans import span as tel_span
from ..testing import faults
from ._base import WavefrontChecker
from .prewarm import CompileWatch

def _to_varying(x):
    """Mark a per-device array as varying over the mesh axis (vma typing).
    Idempotent: already-varying arrays pass through."""
    if AXIS in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, (AXIS,), to="varying")


_OK = 0
_FRONTIER_OVERFLOW = 1
_TABLE_OVERFLOW = 2
_BUCKET_OVERFLOW = 3
_CAND_OVERFLOW = 4  # valid candidates exceeded the compaction budget
_POISON = 5  # a compiled-twin transition crossed its compile bound

AXIS = "d"


def _build_sharded_run(
    tensor,
    props,
    mesh: Mesh,
    cap_local: int,
    fcap_local: int,
    bucket_cap: int,
    target: Optional[int],
    sym: bool = False,
    steps: int = 16,
    cand_local: Optional[int] = None,
    prededup: bool = False,
    cartography: bool = False,
    por=None,
    mxu=None,
):
    """Build the jitted whole-run shard_map for fixed per-device capacities.

    ``cand_local`` is the per-device valid-candidate compaction budget for
    the owner-side insert (see ``bucket_insert``); a step whose routed
    candidates exceed it reports ``_CAND_OVERFLOW`` atomically and the host
    doubles the budget and replays.

    ``prededup`` masks intra-window duplicate candidates to EMPTY
    (``ops/buckets.window_unique``) BEFORE the all-to-all routing, so a
    duplicate-heavy expansion window pays neither ICI transfer nor
    owner-side insert width for its copies.  Per-device only: duplicates
    generated on different devices still meet (and dedup) at the owner.
    Counts/traces are bit-identical either way (same contract as the
    single-device engine; pinned by tests).

    ``por`` is the resolved partial-order-reduction plan (None = off):
    each wavefront masks the enabled-action matrix down to per-state
    ample subsets (``ops/por.ample_mask``) before routing; the insert's
    per-candidate novelty verdict travels BACK through a reverse
    all-to-all so each source row learns whether any of its ample
    successors was fresh, and rows whose ample successors were all
    duplicates re-expand their remaining actions through a second
    route+insert in the same step (the conservative cycle proviso).  The
    whole two-phase step stays atomic under the rollback.  A replicated
    ``boost`` scalar forces one fully expanded wavefront after every
    growth/resume boundary.  Off means the program is bit-identical to a
    pre-POR build (the ``prededup``/``cartography`` contract).

    ``cartography`` appends the search counters (``ops/cartography.py``)
    to the carry: the replicated depth/action/property tallies the
    single-device engine keeps, PLUS the shard-local extras the
    multi-chip runs need — per-shard table load and the source→dest
    routed-candidate matrix (all-to-all volume), from which the host
    derives the imbalance summary.  Off means the whole program is
    bit-identical to a pre-cartography build (same contract as
    ``prededup``)."""
    ndev = mesh.shape[AXIS]
    width, arity = tensor.width, tensor.max_actions
    n_props = len(props)
    # MXU-recast knobs (ops/mxu.py): the coalesced expand kernel + the
    # BLEST probe apply here too; slim_queue has no sharded analogue —
    # the frontier is whole-wavefront compacted, not a FIFO window.
    # Off keeps the program bit-identical (the prededup contract).
    from ..ops.mxu import coalesced_step_fn

    step_rows_fn = coalesced_step_fn(tensor, mxu)
    probe_dot = bool(mxu is not None and mxu.probe)
    ev_idx = [i for i, p in enumerate(props) if p.expectation is Expectation.EVENTUALLY]
    ebit_of = {i: e for e, i in enumerate(ev_idx)}
    if len(ev_idx) > 32:
        raise ValueError("at most 32 eventually properties are supported")
    init_ebits = jnp.uint32((1 << len(ev_idx)) - 1)

    init_rows_np = np.asarray(tensor.init_rows(), dtype=np.uint64)
    n_init = init_rows_np.shape[0]
    boundary_fn = (
        tensor.boundary_rows
        if getattr(tensor, "has_boundary", False)
        else None
    )
    poison_fn = getattr(tensor, "poison_rows", None)
    m_cand = fcap_local * arity
    if cand_local is not None:
        cand_local = min(cand_local, ndev * bucket_cap)

    if por is not None:
        from ..analysis.footprint import conjunct_eval_fn
        from ..ops.por import ample_mask

        conjunct_kernel = conjunct_eval_fn(tensor)

    def owner_of(fps):
        return ((fps >> jnp.uint64(32)) % jnp.uint64(ndev)).astype(jnp.int32)

    if cartography:
        from ..ops.cartography import (
            DEPTH_BINS,
            action_hist_delta,
            prop_tally_delta,
        )

        p_len = max(n_props, 1)

        def cart_init(n_new_g, n_new_local):
            """Initial counters: replicated depth/action/property tallies
            plus the shard-local load vector and route matrix (varying)."""
            return (
                jnp.zeros((DEPTH_BINS,), jnp.int64)
                .at[0].set(n_new_g.astype(jnp.int64)),
                jnp.zeros((max(arity, 1),), jnp.int64),
                jnp.zeros((p_len,), jnp.int64),
                jnp.zeros((p_len,), jnp.int64),
                _to_varying(jnp.zeros((1,), jnp.int64))
                + n_new_local.astype(jnp.int64)[None],
                _to_varying(jnp.zeros((1, ndev), jnp.int64)),
            )

    # -- property kernels (cross-device: min-fp witness, deterministic) ------

    def pmin_u64(x):
        """``lax.pmin`` of a u64 scalar as two u32 all-reduces (high word,
        then low word among the shards that tie on it): the TPU backend
        emulates 64-bit integers and lowers only SUM all-reduces for them
        ("UNIMPLEMENTED: Supported lowering only of Sum all reduce", v5e,
        libtpu 0.0.34)."""
        hi = (x >> jnp.uint64(32)).astype(jnp.uint32)
        lo = x.astype(jnp.uint32)
        ghi = jax.lax.pmin(hi, AXIS)
        glo = jax.lax.pmin(
            jnp.where(hi == ghi, lo, jnp.uint32(0xFFFFFFFF)), AXIS
        )
        return (ghi.astype(jnp.uint64) << jnp.uint64(32)) | glo.astype(
            jnp.uint64
        )

    def record_first(disc, i, hit, fps):
        local = jnp.min(jnp.where(hit, fps, EMPTY))
        glob = pmin_u64(local)
        take = (disc[i] == jnp.uint64(0)) & (glob != EMPTY)
        return disc.at[i].set(jnp.where(take, glob, disc[i]))

    def eval_props(masks, fps, live, ebits, disc):
        for i, p in enumerate(props):
            if p.expectation is Expectation.ALWAYS:
                disc = record_first(disc, i, live & ~masks[..., i], fps)
            elif p.expectation is Expectation.SOMETIMES:
                disc = record_first(disc, i, live & masks[..., i], fps)
            else:
                clear = jnp.uint32(~(1 << ebit_of[i]) & 0xFFFFFFFF)
                ebits = jnp.where(masks[..., i], ebits & clear, ebits)
        return ebits, disc

    def flush_terminal(terminal, fps, ebits, disc):
        for i in ev_idx:
            bit = (ebits >> jnp.uint32(ebit_of[i])) & jnp.uint32(1)
            disc = record_first(disc, i, terminal & (bit == jnp.uint32(1)), fps)
        return disc

    def all_discovered(disc):
        if n_props == 0:
            return jnp.bool_(False)
        return jnp.all(disc != jnp.uint64(0))

    # -- all-to-all candidate routing ----------------------------------------

    def route(cand_fp, cand_rows, cand_par, cand_ebits):
        """Route candidates to their owner device.  Returns local views of the
        received candidates plus a bucket-overflow flag."""
        m = cand_fp.shape[0]
        valid = cand_fp != EMPTY
        owner = owner_of(cand_fp)
        key = jnp.where(valid, owner, jnp.int32(ndev))
        order = jnp.argsort(key, stable=True)
        so = key[order]
        starts = jnp.searchsorted(so, jnp.arange(ndev, dtype=jnp.int32))
        rank = jnp.arange(m, dtype=jnp.int32) - starts[jnp.clip(so, 0, ndev - 1)]
        ok = (so < ndev) & (rank < bucket_cap)
        overflow = jnp.any((so < ndev) & (rank >= bucket_cap))
        d_idx = jnp.where(ok, so, ndev)  # out-of-range rows drop
        r_idx = jnp.where(ok, rank, 0)

        def scatter(buf, vals):
            return buf.at[d_idx, r_idx].set(vals[order], mode="drop")

        send_fp = scatter(jnp.full((ndev, bucket_cap), EMPTY, jnp.uint64), cand_fp)
        send_rows = scatter(
            jnp.zeros((ndev, bucket_cap, width), jnp.uint64), cand_rows
        )
        send_par = scatter(jnp.zeros((ndev, bucket_cap), jnp.uint64), cand_par)
        send_ebt = scatter(jnp.zeros((ndev, bucket_cap), jnp.uint32), cand_ebits)

        a2a = lambda x: jax.lax.all_to_all(x, AXIS, 0, 0, tiled=False)
        recv_fp = a2a(send_fp).reshape(ndev * bucket_cap)
        recv_rows = a2a(send_rows).reshape(ndev * bucket_cap, width)
        recv_par = a2a(send_par).reshape(ndev * bucket_cap)
        recv_ebt = a2a(send_ebt).reshape(ndev * bucket_cap)
        overflow = jax.lax.pmax(overflow, AXIS)
        # routing aux (order/destination/rank/validity): lets the POR path
        # route the owner-side novelty verdict back to the source lanes;
        # plain python refs, zero extra ops for non-POR builds
        return recv_fp, recv_rows, recv_par, recv_ebt, overflow, (
            order, d_idx, r_idx, ok
        )

    # -- owner-side dedup + insert + compaction ------------------------------

    def insert_and_compact(tfp, tpl, cand_rows, cand_fp, cand_par,
                           cand_ebits, compact=None, want_novel=False):
        """Dedup candidates, claim table slots (bucketized one-shot insert —
        same visited-set as the single-device engine, ``ops/buckets.py``;
        the round-1 probe-loop insert cost a full-size scatter per
        probe iteration on real TPU), compact novel rows into a
        frontier-shaped (exactly ``fcap_local``-row) buffer.  ``compact``
        is the valid-candidate budget (see ``bucket_insert``) — the insert
        pipeline runs at that width instead of the padded receive size."""
        m = cand_fp.shape[0]
        tfp, tpl, sel, n_new, toverflow, coverflow = bucket_insert(
            tfp, tpl, cand_fp, cand_par,
            window=min(m, max(64, fcap_local)), generation_order=sym,
            compact=compact, probe_dot=probe_dot,
        )
        novel = None
        if want_novel:
            # per-received-candidate novelty, BEFORE the frontier trim —
            # the POR proviso routes this back to the source device
            from ..ops.por import candidate_novelty

            novel = candidate_novelty(m, sel, n_new)
        sel_w = sel.shape[0]
        take = min(sel_w, fcap_local)
        sel = sel[:take]  # original indices, novel-compacted
        nrows = cand_rows[sel]
        nfps = jnp.where(jnp.arange(take) < n_new, cand_fp[sel], EMPTY)
        nebt = cand_ebits[sel]
        pad = fcap_local - take
        if pad > 0:  # always emit exactly fcap_local rows (while_loop carry)
            nrows = jnp.concatenate([nrows, jnp.zeros((pad, width), jnp.uint64)])
            nfps = jnp.concatenate([nfps, jnp.full((pad,), EMPTY, jnp.uint64)])
            nebt = jnp.concatenate([nebt, jnp.zeros((pad,), jnp.uint32)])
        return tfp, tpl, nrows, nfps, nebt, n_new, toverflow, coverflow, novel

    # -- the per-device program ----------------------------------------------

    def device_init():
        idx = jax.lax.axis_index(AXIS)

        tfp = _to_varying(jnp.full((cap_local,), EMPTY, jnp.uint64))
        tpl = _to_varying(jnp.zeros((cap_local,), jnp.uint64))

        # Each device claims the init states it owns (no routing needed: the
        # init set is a replicated constant).
        irows = jnp.asarray(init_rows_np)
        ifp = row_hash(tensor.representative_rows(irows) if sym else irows)
        mine = owner_of(ifp) == idx
        cand_fp = jnp.where(mine, ifp, EMPTY)
        cand_par = jnp.zeros((n_init,), jnp.uint64)  # 0 = init state
        cand_ebt = jnp.full((n_init,), init_ebits, jnp.uint32)
        tfp, tpl, rows0, fps0, ebt0, n_new, toverflow, _, _ = (
            insert_and_compact(tfp, tpl, irows, cand_fp, cand_par, cand_ebt)
        )
        unique = jax.lax.psum(n_new.astype(jnp.int64), AXIS)
        foverflow = n_new > fcap_local
        status = jnp.where(
            jax.lax.pmax(toverflow, AXIS),
            jnp.int32(_TABLE_OVERFLOW),
            jnp.where(
                jax.lax.pmax(foverflow, AXIS),
                jnp.int32(_FRONTIER_OVERFLOW),
                jnp.int32(_OK),
            ),
        )
        carry = (tfp, tpl, rows0, fps0, ebt0, unique,
                 jnp.int64(n_init),  # state_count counts all inits
                 jnp.zeros((max(n_props, 1),), jnp.uint64),
                 jnp.int32(0), status)
        if por is not None:
            # replicated boost scalar + reduced-vs-full tallies; the init
            # wavefront is not a growth/resume boundary (boost=0)
            carry = carry + (jnp.int32(0), jnp.zeros((3,), jnp.int64))
        if cartography:
            carry = carry + cart_init(unique, n_new)
        return carry + (keep_going(carry).astype(jnp.int32),)

    def keep_going(carry):
        fps, unique, disc, status = carry[3], carry[5], carry[7], carry[9]
        frontier_live = (
            jax.lax.pmax(jnp.any(fps != EMPTY).astype(jnp.int32), AXIS) > 0
        )
        go = (status == _OK) & frontier_live & ~all_discovered(disc)
        if target is not None:
            go = go & (unique < jnp.int64(target))
        return go

    def device_steps(*carry):
        """Up to ``steps`` whole-frontier expansions; returns the carry for
        the next host sync (live counters, target checks, growth).  Each
        expansion is ATOMIC: on overflow it rolls back to the pre-step carry
        (status aside) so the host can grow buffers and replay it."""

        def expand(carry):
            (tfp, tpl, rows, fps, ebits, unique, scount, disc, depth,
             status) = carry[:10]
            if por is not None:
                boost, pstats = carry[10], carry[11]
                cart = carry[12:]
            else:
                cart = carry[10:]
            live = fps != EMPTY
            masks = tensor.property_masks(rows)  # [F, P] bool
            ebits, disc = eval_props(masks, fps, live, ebits, disc)
            # Mid-block early exit (reference ``bfs.rs:121-128``): mask the
            # expansion instead of branching so the collective sequence stays
            # uniform across devices.
            elive = live & ~all_discovered(disc)

            succ, valid = step_rows_fn(rows)  # [F, A, W], [F, A]
            if boundary_fn is not None:
                # host-checker parity: boundary filter before counting
                valid = valid & boundary_fn(succ)
            valid = valid & elive[:, None]
            terminal = elive & ~jnp.any(valid, axis=-1)
            disc = flush_terminal(terminal, fps, ebits, disc)

            # symmetry: route + dedup on the canonical class key while the
            # frontier carries original rows (see wavefront.py step)
            krows = tensor.representative_rows(succ) if sym else succ
            if por is not None:
                # ample-set selection before routing: masked candidates
                # pay neither ICI transfer nor owner-side insert width
                amp = ample_mask(valid, rows, por, conjunct_kernel)
                amp = jnp.where(boost > 0, valid, amp)
                v1 = amp
                all_fp = jnp.where(valid, row_hash(krows), EMPTY)
                cand_fp = jnp.where(v1, all_fp, EMPTY).reshape(m_cand)
            else:
                # the pre-POR expression verbatim: off-path program must
                # stay bit-identical (see wavefront.py)
                v1 = valid
                cand_fp = jnp.where(
                    valid, row_hash(krows), EMPTY
                ).reshape(m_cand)
            if prededup:
                # intra-window pre-dedup before routing: duplicate lanes
                # drop out of the all-to-all AND the owner-side insert
                cand_fp = window_unique(cand_fp)
            cand_rows = succ.reshape(m_cand, width)
            cand_par = jnp.broadcast_to(fps[:, None], (fcap_local, arity)).reshape(-1)
            cand_ebt = jnp.broadcast_to(ebits[:, None], (fcap_local, arity)).reshape(-1)

            rfp, rrows, rpar, rebt, boverflow, aux = route(
                cand_fp, cand_rows, cand_par, cand_ebt
            )
            tfp, tpl, nrows, nfps, nebt, n_new, toverflow, coverflow, novel_recv = (
                insert_and_compact(tfp, tpl, rrows, rfp, rpar, rebt,
                                   compact=cand_local,
                                   want_novel=por is not None)
            )
            if por is not None:
                # cycle proviso, cross-device: the owner-side novelty
                # verdict travels back through the REVERSE all-to-all
                # (the collective is an involution on the [D, C] layout),
                # then unsorts through the routing aux to the original
                # candidate lanes — each source row learns whether any of
                # its ample successors claimed a fresh slot
                order, d_idx, r_idx, ok = aux
                novel_send = jax.lax.all_to_all(
                    novel_recv.reshape(ndev, bucket_cap), AXIS, 0, 0,
                    tiled=False,
                )
                ns = novel_send[
                    jnp.clip(d_idx, 0, ndev - 1), r_idx
                ] & ok
                novel = (cand_fp != cand_fp).at[order].set(ns)
                fresh_row = jnp.any(
                    novel.reshape(fcap_local, arity), axis=1
                )
                reduced_row = jnp.any(valid & ~amp, axis=1)
                need_full = reduced_row & ~fresh_row
                v2 = valid & ~amp & need_full[:, None]
                cand_fp2 = jnp.where(v2, all_fp, EMPTY).reshape(m_cand)
                if prededup:
                    cand_fp2 = window_unique(cand_fp2)
                rfp2, rrows2, rpar2, rebt2, bovf2, _ = route(
                    cand_fp2, cand_rows, cand_par, cand_ebt
                )
                (tfp, tpl, nrows2, nfps2, nebt2, n_new2, tovf2, covf2,
                 _) = insert_and_compact(
                    tfp, tpl, rrows2, rfp2, rpar2, rebt2,
                    compact=cand_local,
                )
                # merge the two compacted frontier segments: non-EMPTY
                # first, stable (phase-1 novelty order preserved)
                fps_all = jnp.concatenate([nfps, nfps2])
                morder = jnp.argsort(fps_all == EMPTY, stable=True)
                take = morder[:fcap_local]
                nrows = jnp.concatenate([nrows, nrows2])[take]
                nfps = fps_all[take]
                nebt = jnp.concatenate([nebt, nebt2])[take]
                foverflow = jax.lax.pmax(
                    (n_new + n_new2) > fcap_local, AXIS
                )
                n_new = n_new + n_new2
                toverflow = toverflow | tovf2
                coverflow = coverflow | covf2
                boverflow = boverflow | bovf2
                gen_mask = v1 | v2
            else:
                gen_mask = valid
                foverflow = jax.lax.pmax(n_new > fcap_local, AXIS)
            gen = jnp.sum(gen_mask, dtype=jnp.int64)
            scount = scount + jax.lax.psum(gen, AXIS)
            if por is not None:
                pstats = pstats + jnp.stack([
                    jax.lax.psum(jnp.sum(
                        reduced_row & ~need_full, dtype=jnp.int64
                    ), AXIS),
                    jax.lax.psum(jnp.sum(need_full, dtype=jnp.int64), AXIS),
                    jax.lax.psum(
                        jnp.sum(valid, dtype=jnp.int64) - gen, AXIS
                    ),
                ])
                boost = jnp.int32(0)  # consumed; rollback re-arms on replay
            n_new_g = jax.lax.psum(n_new.astype(jnp.int64), AXIS)
            unique = unique + n_new_g
            coverflow = jax.lax.pmax(coverflow, AXIS)
            # proactive growth at 25% GLOBAL load: past it the Poisson bucket
            # overflow tail stops being negligible (cf. wavefront.py).  The
            # global unique counter is already replicated, so this is O(1);
            # per-shard skew beyond it is backstopped by the atomic bucket
            # overflow path (fingerprint uniformity keeps shards balanced).
            tthresh = unique * jnp.int64(4) > jnp.int64(ndev * cap_local)
            toverflow = jax.lax.pmax(toverflow | tthresh, AXIS)
            status = jnp.where(
                toverflow,
                jnp.int32(_TABLE_OVERFLOW),
                jnp.where(
                    coverflow,
                    jnp.int32(_CAND_OVERFLOW),
                    jnp.where(
                        boverflow,
                        jnp.int32(_BUCKET_OVERFLOW),
                        jnp.where(
                            foverflow,
                            jnp.int32(_FRONTIER_OVERFLOW),
                            status,
                        ),
                    ),
                ),
            )
            if poison_fn is not None:
                # a poisoned expanded row = a compile-time bound crossed by
                # a reachable transition; terminal, host raises (growth
                # cannot fix a bound).  pmax: any shard poisons the run.
                status = jnp.where(
                    jax.lax.pmax(
                        jnp.any(poison_fn(rows) & live), AXIS
                    ),
                    jnp.int32(_POISON),
                    status,
                )
            depth = depth + jnp.where(n_new_g > 0, 1, 0).astype(jnp.int32)
            if cartography:
                (depth_hist, act_hist, p_evals, p_hits, shard_load,
                 route_mat) = cart
                # the frontier is one BFS level, so the new ``depth`` IS the
                # level of this expansion's novel inserts (no-op if none)
                depth_hist = depth_hist.at[
                    jnp.clip(depth, 0, DEPTH_BINS - 1)
                ].add(n_new_g)
                act_hist = act_hist + jax.lax.psum(
                    action_hist_delta(gen_mask), AXIS
                )
                d_evals, d_hits = prop_tally_delta(live, masks, n_props)
                p_evals = p_evals + jax.lax.psum(d_evals, AXIS)
                p_hits = p_hits + jax.lax.psum(d_hits, AXIS)
                # shard extras stay device-local (varying): per-shard fresh
                # inserts, and this shard's routed-candidate row (what it
                # SENT per destination through the all-to-all — both POR
                # phases' routed lanes count)
                shard_load = shard_load + n_new.astype(jnp.int64)[None]
                routed = [cand_fp] + (
                    [cand_fp2] if por is not None else []
                )
                for rf in routed:
                    cvalid = rf != EMPTY
                    owner = jnp.where(
                        cvalid, owner_of(rf), jnp.int32(ndev)
                    )
                    d_route = jnp.zeros((ndev,), jnp.int64).at[owner].add(
                        jnp.where(cvalid, jnp.int64(1), jnp.int64(0)),
                        mode="drop",
                    )
                    route_mat = route_mat + d_route[None, :]
                cart = (depth_hist, act_hist, p_evals, p_hits, shard_load,
                        route_mat)
            out = (tfp, tpl, nrows, nfps, nebt, unique, scount, disc,
                   depth, status)
            if por is not None:
                out = out + (boost, pstats)
            return out + tuple(cart)

        def body(carry):
            new = expand(carry)
            status = new[9]
            # Atomic step: on overflow nothing advances except the status
            # code, so the host's growth transform resumes from a consistent
            # carry and the failed wavefront replays losslessly — the
            # cartography counters roll back with everything else, so a
            # replayed wavefront never double-counts.  (The visited-table
            # part of the rollback is already guaranteed by
            # ``bucket_insert`` writing nothing on overflow.)
            ofl = status != jnp.int32(_OK)
            rolled = [
                jnp.where(ofl, old, nxt) for old, nxt in zip(carry, new)
            ]
            rolled[9] = status
            return tuple(rolled)

        # Device-local carry components must enter the loop as "varying" over
        # the mesh axis even when their initial value is a replicated constant
        # (shard_map's vma typing for while_loop).  With cartography the two
        # shard-local counter buffers (load vector, route matrix) ride at the
        # carry tail and are varying too.
        ncarry = len(carry)
        varying_idx = set(range(5))
        if cartography:
            varying_idx |= {ncarry - 2, ncarry - 1}
        carry = tuple(
            _to_varying(x) if i in varying_idx else x
            for i, x in enumerate(carry)
        )
        _, carry = jax.lax.while_loop(
            lambda s: (s[0] < steps) & keep_going(s[1]),
            lambda s: (s[0] + 1, body(s[1])),
            (jnp.int32(0), carry),
        )
        return carry + (keep_going(carry).astype(jnp.int32),)

    in_specs = (P(AXIS),) * 5 + (P(),) * 5
    if por is not None:
        # replicated boost scalar + reduced-vs-full tallies
        in_specs = in_specs + (P(), P())
    if cartography:
        # replicated depth/action/property tallies + sharded load/route
        in_specs = in_specs + (P(),) * 4 + (P(AXIS), P(AXIS))
    out_specs = in_specs + (P(),)
    init_fn = jax.jit(
        jax.shard_map(
            device_init, mesh=mesh, in_specs=(), out_specs=out_specs
        )
    )
    step_fn = jax.jit(
        jax.shard_map(
            device_steps, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        ),
        donate_argnums=tuple(range(len(in_specs))),
    )
    return init_fn, step_fn


def default_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (AXIS,))


_SHARDED_SNAPSHOT_KEYS = (
    "table_fp", "table_parent", "rows", "fps", "ebits",
    "unique", "scount", "disc", "depth", "status",
)


class ShardedTpuChecker(WavefrontChecker):
    """Wavefront BFS sharded over a device mesh (TPU ICI on hardware; in tests
    an 8-device virtual CPU mesh).  Same result surface and work-preserving
    growth protocol as the single-device :class:`~.wavefront.TpuChecker`
    (atomic steps + host-side grow/rehash per shard — no restart, no counter
    reset), including mid-run :meth:`checkpoint` /
    ``spawn_tpu(devices=N, resume=snapshot)`` (the mesh width must match:
    table shards are partitioned by fingerprint ownership)."""

    def __init__(
        self,
        options: CheckerBuilder,
        mesh: Optional[Mesh] = None,
        n_devices: Optional[int] = None,
        capacity: int = 1 << 17,
        frontier_capacity: int = 1 << 13,
        bucket_factor: int = 2,
        cand_factor: int = 4,
        sync: bool = False,
        pallas: Optional[bool] = None,
        steps_per_call: int = 16,
        resume: Optional[dict] = None,
    ):
        if pallas:
            raise NotImplementedError(
                "the Pallas insert kernel is single-device only for now; "
                "drop pallas=True or use spawn_tpu() without devices/mesh"
            )
        if getattr(options, "checked_mode", False):
            # checkify's error carry does not compose with this engine's
            # shard_map collectives; the checked
            # exploration itself is engine-independent, so the guidance is
            # to reproduce on the single-device engine
            raise NotImplementedError(
                "checked mode (CheckerBuilder.checked()) is single-device "
                "only for now: run spawn_tpu() without devices/mesh to "
                "reproduce with checkify instrumentation"
            )
        if options.timeout_secs is not None:
            # timers fire per process at slightly different instants — one
            # controller would break the lockstep collectives while others
            # keep stepping
            self._require_single_controller("timeout()")
        self._resume = resume
        self.mesh = mesh if mesh is not None else default_mesh(n_devices)
        self.ndev = self.mesh.shape[AXIS]
        # capacities are global; divide into power-of-two per-device shards
        self._cap_local = max(64, _pow2(capacity // self.ndev))
        self._fcap_local = max(16, frontier_capacity // self.ndev)
        self._bucket_factor = bucket_factor
        # valid-candidate budget per device = cand_factor * fcap_local
        # (doubled on demand): the owner-side insert pipeline runs at this
        # width instead of the padded all-to-all receive size
        self._cand_factor = cand_factor
        self._steps = steps_per_call
        self._live = (0, 0, 0)  # states, unique, maxdepth
        # (status, unique-at-boundary) per mid-run growth event; unique is
        # monotone across events — growth preserves work (tests pin this)
        self.growth_events: list = []
        self._init_common(options, sync)

    def _host_table(self, sharded) -> np.ndarray:
        """The final visited table as a host array.  Single-controller runs
        read the shards directly; under multi-controller SPMD
        (``jax.distributed``, processes each owning a slice of the mesh) the
        shards on other hosts are not addressable, so the table is
        all-gathered on device first — every process then reconstructs
        identical discovery paths from its own full copy."""
        if jax.process_count() == 1:
            return np.asarray(sharded)
        gather = self.__dict__.get("_gather_fn")
        if gather is None:
            from jax.sharding import NamedSharding

            gather = jax.jit(
                lambda t: t,
                out_shardings=NamedSharding(self.mesh, P()),  # all-gather
            )
            self._gather_fn = gather  # one compile serves both tables
        return np.asarray(jax.device_get(gather(sharded)))

    # -- memory-ledger hooks (telemetry/memory.py) ---------------------------

    def _memory_spec_fn(self):
        """Analytic model of this engine's GLOBAL carry (logical array
        shapes; the snapshot's ``per_device_bytes`` divides the sharded
        buffers over the mesh).  Caps key ``cap`` is the GLOBAL table
        slot count — the growth forecast doubles it, exactly as a
        table-overflow doubles every shard."""
        from ..telemetry.memory import sharded_specs

        width, arity = self.tensor.width, self.tensor.max_actions
        n_props, ndev = len(self._props), self.ndev
        cart, por = self._cartography, self._por
        fcap_default = self._fcap_local

        def spec_fn(caps):
            return sharded_specs(
                width, arity, n_props, ndev,
                max(int(caps["cap"]) // ndev, 1),
                int(caps.get("fcap_local", fcap_default)),
                cartography=cart, por=por,
            )

        return spec_fn

    def _memory_caps(self) -> dict:
        return {
            "cap": self._cap_local * self.ndev,
            "fcap_local": self._fcap_local,
        }

    def _memory_extra(self) -> dict:
        return {
            "devices": self.ndev,
            "frontier_capacity": self._fcap_local * self.ndev,
        }

    def _roofline_cost_fn(self):
        """Model-kernel cost ledger (``costmodel.sharded_costs``):
        property/expand/hash at the per-device frontier width.  The
        mesh insert + all-to-all are collectives the single-kernel walk
        cannot price honestly — they land with the pod-scale mesh round
        (ROADMAP); the block's ``engine: sharded`` tag says so."""
        from ..analysis.costmodel import sharded_costs

        tensor = self.tensor
        cap_local, fcap_local = self._cap_local, self._fcap_local
        ndev, sym = self.ndev, self._symmetry is not None
        mxu = self._mxu

        def cost_fn():
            return sharded_costs(
                tensor, cap_local, fcap_local, ndev, sym=sym, mxu=mxu,
            )

        return cost_fn

    def _cart_zero_host(self) -> list:
        """Fresh host-side cartography counter buffers in carry-tail order
        (depth/action/property tallies + per-shard load and route matrix);
        empty when cartography is off."""
        if not self._cartography:
            return []
        from ..ops.cartography import cart_zero_np

        zeros = cart_zero_np(self.tensor.max_actions, len(self._props))
        zeros.append(np.zeros((self.ndev,), np.int64))
        zeros.append(np.zeros((self.ndev, self.ndev), np.int64))
        return zeros

    def _por_resume_host(self) -> list:
        """POR carry-tail seed for a resumed/finished carry: boost=1 (a
        resume IS a snapshot boundary — the proviso arms one fully
        expanded wavefront) + the snapshot's cumulative tallies (zeros
        for pre-POR snapshots)."""
        if not self._por:
            return []
        snap = self._resume if self._resume is not None else {}
        stats = np.asarray(
            snap.get("por_stats", np.zeros((3,), np.int64)), np.int64
        ).reshape(3)
        return [np.int32(1), stats]

    def _cart_resume_host(self) -> list:
        """Cartography counter tail for a resumed carry: the snapshot's
        stored cumulative counters when present (``cart0``..``cart5``,
        written by ``_carry_to_snapshot``), zeros for snapshots predating
        cartography (their histograms then cover post-resume work only —
        the old behavior, now the fallback instead of the rule)."""
        zeros = self._cart_zero_host()
        snap = self._resume if self._resume is not None else {}
        return [
            np.asarray(snap[f"cart{i}"]).astype(z.dtype).reshape(z.shape)
            if f"cart{i}" in snap
            else z
            for i, z in enumerate(zeros)
        ]

    def _sync_cartography(self, arrs, *, states: int, unique: int) -> None:
        """Assemble the sharded cartography snapshot from the pulled
        counter buffers (depth, action, prop-evals, prop-hits, per-shard
        load, route matrix — global views)."""
        from ..ops.cartography import snapshot

        dh, ah, pe, ph, load, route = arrs
        snap = snapshot(
            depth_hist=dh, action_hist=ah, prop_evals=pe, prop_hits=ph,
            prop_names=[pr.name for pr in self._props],
            states=states, unique=unique,
            shard_load=load, route_matrix=route,
            por=self._live_por if self._por else None,
        )
        self._live_cart = snap
        if self.flight_recorder is not None:
            self.flight_recorder.set_cartography(snap)

    # -- live progress.  Growth is work-preserving (atomic steps + host-side
    # buffer transforms), so counters are monotone across growth events. ----

    def state_count(self) -> int:
        if self._results:
            return self._results["states"]
        return self._live[0]

    def unique_state_count(self) -> int:
        if self._results:
            return self._results["unique"]
        return self._live[1]

    def max_depth(self) -> int:
        if self._results:
            return self._results["depth"]
        return self._live[2]

    def _pre_run_validate(self) -> None:
        if self._resume is not None:
            # snapshot consumption feeds full host arrays to a program
            # sharded over the global mesh — not expressible when other
            # processes own part of that mesh
            self._require_single_controller("resume=")
            self._check_snapshot_sig(self._resume)
            if int(self._resume["ndev"]) != self.ndev:
                raise ValueError(
                    f"snapshot was taken on a {self._resume['ndev']}-device "
                    f"mesh; this mesh has {self.ndev} (table shards are "
                    "partitioned by fingerprint ownership)"
                )

    _engine_tag = "sharded"

    @staticmethod
    def _require_single_controller(what: str) -> None:
        """Checkpoint/stop/resume are single-controller only for now: the
        full sharded carry is not addressable across hosts, and per-process
        host events (``_stop``, ``_ckpt_req``) would break the lockstep
        invariant that every controller issues the same collectives.  Raised
        from the CALLER-facing entry points so a multi-controller user gets
        the error, not a dead run thread.  (Mid-run GROWTH is *not* fenced:
        its trigger is a replicated status, so every controller executes the
        same per-shard growth at the same step boundary —
        :meth:`_grow_carry_lockstep`.)"""
        if jax.process_count() > 1:
            raise NotImplementedError(
                f"{what} is single-controller only: the sharded carry is "
                "not addressable across hosts and per-process control "
                "events would desynchronize the controllers' collectives. "
                "Pre-size capacity/frontier_capacity and let multi-host "
                "runs complete."
            )

    def checkpoint(self, timeout=60.0) -> dict:
        self._require_single_controller("checkpoint()")
        return super().checkpoint(timeout=timeout)

    def stop(self):
        self._require_single_controller("stop()")
        return super().stop()

    def _carry_to_snapshot(self, carry, more, cap, fcap, bf, cf) -> dict:
        snap = {
            k: np.asarray(v)
            for k, v in zip(_SHARDED_SNAPSHOT_KEYS, carry)
        }
        tail = list(carry[10:])
        if self._por:
            # the boost scalar is NOT persisted (resume always re-arms a
            # fully expanded wavefront); the cumulative reduced-vs-full
            # tallies are, like the cartography counters below
            snap["por_stats"] = np.asarray(tail[1])
            tail = tail[2:]
        # cartography counter tail (cumulative, in-carry on this engine):
        # persisted so a resumed run's histograms keep reconciling with
        # the cumulative totals (sum(depth_hist) == unique) instead of
        # restarting at zero against a non-zero ``unique``
        for i, v in enumerate(tail):
            snap[f"cart{i}"] = np.asarray(v)
        snap["more"] = int(np.asarray(more))
        snap["ndev"] = self.ndev
        snap["cap_local"] = cap
        snap["fcap_local"] = fcap
        snap["bucket_factor"] = bf
        snap["cand_factor"] = cf
        snap["engine"] = self._engine_tag
        snap["model_sig"] = self._model_sig()
        # run lineage: same manifest field as the wavefront engine, so
        # the registry links kill+resume chains (telemetry/registry.py)
        snap["run_id"] = self.run_id
        # snapshot manifest: analytic footprint at these capacities, for
        # the resume-time fits guard (parallel/_base._check_snapshot_sig)
        fb = self._analytic_footprint_bytes(
            {"cap": cap * self.ndev, "fcap_local": fcap}
        )
        if fb is not None:
            snap["footprint_bytes"] = np.int64(fb)
        return snap

    @property
    def _final_snapshot(self) -> dict:
        # lazy: pulling the whole carry off the device costs far more
        # than the run's last wavefronts, so only checkpoint() pays for it
        carry, more, caps = self._final_state
        return self._carry_to_snapshot(carry, more, *caps)

    # Per-shard growth transforms — THE single definition of the growth
    # semantics (rehash target, pad fill values, dtypes), shared by the
    # numpy resume path (_grow_carry) and the lockstep mid-run path
    # (_grow_carry_lockstep) so the two can never drift.  Each takes one
    # device's block and returns its grown block.

    @staticmethod
    def _rehash_table_block(fp_blk, pl_blk, cap2):
        from ..ops.buckets import host_bucket_rehash

        return host_bucket_rehash(fp_blk, pl_blk, cap2 // SLOTS)

    @staticmethod
    def _pad_frontier_block(k: int, blk, grow: int):
        """Pad carry component ``k`` (2=rows, 3=fps, 4=ebits) at its tail
        (novel rows are front-compacted)."""
        if k == 2:
            return np.concatenate(
                [blk, np.zeros((grow, blk.shape[-1]), np.uint64)]
            )
        if k == 3:
            return np.concatenate([blk, np.full((grow,), EMPTY, np.uint64)])
        return np.concatenate([blk, np.zeros((grow,), np.uint32)])

    @classmethod
    def _grow_carry(cls, carry_np: list, ndev: int, cap: int, fcap: int,
                    bf: int, cf: int, status: int):
        """Work-preserving growth: transform a consistent (pre-overflow)
        carry for doubled capacity, host-side.  Table shards rehash
        independently (ownership is ``(fp >> 32) % D`` — capacity changes
        only the bucket index *within* a shard); frontier segments pad at
        their tail; the route-bucket and candidate budgets are engine
        parameters (step-internal buffers), so growing them needs no carry
        change at all.  Returns ``(cap, fcap, bf, cf, carry_np)`` with
        status reset to OK."""
        if status == _TABLE_OVERFLOW:
            cap2 = cap * 2
            tfp = np.asarray(carry_np[0]).reshape(ndev, cap)
            tpl = np.asarray(carry_np[1]).reshape(ndev, cap)
            parts = [
                cls._rehash_table_block(tfp[d], tpl[d], cap2)
                for d in range(ndev)
            ]
            carry_np[0] = np.concatenate([p[0] for p in parts])
            carry_np[1] = np.concatenate([p[1] for p in parts])
            cap = cap2
        elif status == _FRONTIER_OVERFLOW:
            fcap2 = fcap * 2
            grow = fcap2 - fcap
            for k in (2, 3, 4):
                blk = np.asarray(carry_np[k])
                blocks = [
                    cls._pad_frontier_block(
                        k, blk[d * fcap : (d + 1) * fcap], grow
                    )
                    for d in range(ndev)
                ]
                carry_np[k] = np.concatenate(blocks)
            fcap = fcap2
        elif status == _BUCKET_OVERFLOW:
            bf *= 2
        elif status == _CAND_OVERFLOW:
            cf *= 2
        carry_np[9] = np.int32(_OK)
        return cap, fcap, bf, cf, carry_np

    def _grow_carry_lockstep(self, carry, cap, fcap, bf, cf, status):
        """Mid-run growth that works under multi-controller SPMD: the
        trigger (``status``) is a replicated psum'd scalar, so EVERY
        controller enters here at the same step boundary with identical
        parameters.  Each controller transforms only its ADDRESSABLE
        shards host-side (growth is per-shard local: table shards rehash
        independently — ownership is ``(fp >> 32) % D``, capacity only
        changes the bucket index within a shard — and frontier segments
        pad at their tail), then reassembles global arrays with
        ``make_array_from_single_device_arrays``.  No cross-host data
        moves; the controllers stay in lockstep because the transform is
        deterministic.  Returns ``(cap, fcap, bf, cf, new_carry)`` with
        the replicated status reset to OK."""
        from jax.sharding import NamedSharding

        shard_sp = NamedSharding(self.mesh, P(AXIS))
        repl_sp = NamedSharding(self.mesh, P())
        ndev = self.ndev

        def reassemble(bufs_by_dev, global_rows, trailing):
            bufs = [
                jax.device_put(blk, dev) for dev, blk in bufs_by_dev
            ]
            return jax.make_array_from_single_device_arrays(
                (global_rows,) + trailing, shard_sp, bufs
            )

        # cartography counter buffers (carry tail past the 10 base
        # elements) are capacity-independent: they pass through untouched
        new = list(carry)
        if status == _TABLE_OVERFLOW:
            cap2 = cap * 2
            pl_by_dev = {
                sh.device: np.asarray(sh.data)
                for sh in carry[1].addressable_shards
            }
            fp_bufs, pl_bufs = [], []
            for sh in carry[0].addressable_shards:
                nfp, npl = self._rehash_table_block(
                    np.asarray(sh.data), pl_by_dev[sh.device], cap2
                )
                fp_bufs.append((sh.device, nfp))
                pl_bufs.append((sh.device, npl))
            new[0] = reassemble(fp_bufs, ndev * cap2, ())
            new[1] = reassemble(pl_bufs, ndev * cap2, ())
            cap = cap2
        elif status == _FRONTIER_OVERFLOW:
            fcap2 = fcap * 2
            grow = fcap2 - fcap
            for k in (2, 3, 4):
                bufs = [
                    (
                        sh.device,
                        self._pad_frontier_block(
                            k, np.asarray(sh.data), grow
                        ),
                    )
                    for sh in carry[k].addressable_shards
                ]
                new[k] = reassemble(
                    bufs, ndev * fcap2, carry[k].shape[1:]
                )
            fcap = fcap2
        elif status == _BUCKET_OVERFLOW:
            bf *= 2  # engine parameter only: the carry is unchanged
        elif status == _CAND_OVERFLOW:
            cf *= 2
        ok = np.int32(_OK)
        new[9] = jax.make_array_from_callback(
            (), repl_sp, lambda idx: ok
        )
        return cap, fcap, bf, cf, tuple(new)

    def _run(self):
        if self._resume is not None:
            # capacities are baked into the compiled programs; adopt the
            # snapshot's so the carry shapes line up
            self._cap_local = int(self._resume["cap_local"])
            self._fcap_local = int(self._resume["fcap_local"])
            self._bucket_factor = int(self._resume["bucket_factor"])
            self._cand_factor = int(self._resume.get("cand_factor", 4))
        cap, fcap, bf = self._cap_local, self._fcap_local, self._bucket_factor
        cf = self._cand_factor
        arity = self.tensor.max_actions
        cache = getattr(self.tensor, "_sharded_run_cache", None)
        if cache is None:
            cache = {}
            self.tensor._sharded_run_cache = cache
        mesh_key = tuple(d.id for d in self.mesh.devices.flat)

        rec = self.flight_recorder
        occ_every = int(self._telemetry_opts.get("occupancy_every") or 0)
        syncs = 0
        hs = 0  # host-sync ordinal for the chaos seam
        # autosave is single-controller only, like checkpoint(): the full
        # sharded carry is not addressable across hosts.  Disarm LOUDLY
        # on a multi-controller run (the checkpoint() rule, minus the
        # raise: autosave can arrive via the env knob, and killing an
        # otherwise-valid run over an inapplicable checkpoint cadence
        # would be worse than running without checkpoints) — and retract
        # the durability block so the operator is never told checkpoints
        # exist when none are being written
        single_controller = jax.process_count() == 1
        if not single_controller and self._autosave is not None:
            import sys as _sys

            print(
                "stateright-tpu: autosave is single-controller only on "
                "the sharded engine (the sharded carry is not "
                "addressable across hosts); DISARMED for this run — no "
                "checkpoints will be written and a preemption loses the "
                "run. Pre-size capacity or run single-controller for "
                "durable checkpoints.",
                file=_sys.stderr,
            )
            self._autosave = None
            self._refresh_durability()
        if rec is not None:
            rec.update_meta(
                devices=self.ndev, steps_per_call=self._steps,
            )
        # sharded status words, named for growth records — keyed on THIS
        # engine's codes (they are numbered differently from wavefront's;
        # the names come from the telemetry.STATUS_NAMES vocabulary)
        status_names = {
            _OK: "ok", _FRONTIER_OVERFLOW: "frontier_full",
            _TABLE_OVERFLOW: "table_full", _BUCKET_OVERFLOW: "bucket_full",
            _CAND_OVERFLOW: "cand_full", _POISON: "poison",
        }

        pending = None  # host carry to feed step_fn (resume or post-growth)
        finished = None  # carry of an already-complete resume snapshot
        first_build = True  # compile-event kind: the first build is "init"
        if self._resume is not None:
            carry0 = [np.asarray(self._resume[k])
                      for k in _SHARDED_SNAPSHOT_KEYS]
            st = int(carry0[9])
            if st != _OK:
                # snapshot taken at a growth boundary: grow first, then run
                cap, fcap, bf, cf, carry0 = self._grow_carry(
                    carry0, self.ndev, cap, fcap, bf, cf, st
                )
                pending = carry0
            elif int(self._resume["more"]):
                pending = carry0
            else:
                finished = carry0

        # carry tail: [por boost + tallies]? then the cartography tail
        # (4 replicated counter buffers + 2 shard-local ones) after the
        # 10 base elements (ops/por.py, ops/cartography.py)
        por_n = 2 if self._por else 0
        cart_lo = 10 + por_n
        ncarry = cart_lo + (6 if self._cartography else 0)
        while True:  # one iteration per engine build (growth rebuilds)
            bucket_cap = max(64, (fcap * arity * bf) // self.ndev)
            cand_local = max(64, cf * fcap)
            sym = self._symmetry is not None
            key = (mesh_key, cap, fcap, bucket_cap, cand_local, self._target,
                   sym, self._steps, self._prededup, self._cartography,
                   self._por)
            if self._mxu is not None:
                # MXU off leaves the key exactly the pre-MXU tuple (the
                # wavefront engine's cache-unkeyed discipline), and the
                # key carries only the EFFECTIVE components the sharded
                # program actually reads — slim_queue has no sharded
                # analogue and coalesce falls back on twins without a
                # coalesced kernel, so keying on either would recompile
                # an identical shard_map
                from ..ops.mxu import effective_mxu

                eff = effective_mxu(self.tensor, self._mxu)
                if eff is not None and (eff.coalesce or eff.probe):
                    key = key + (("mxu", eff.coalesce, eff.probe),)
            fns = cache.get(key)
            if rec is not None and key != getattr(
                self, "_last_engine_key", None
            ):
                # engine-cache accounting, as in wavefront.py: counted only
                # when the engine is (re)acquired (init + growth rebuilds)
                rec.add(
                    "compile_cache_hits" if fns is not None
                    else "compile_cache_misses"
                )
                if fns is None:
                    # duration/cache_hit amended once the first device call
                    # pays the lazy compile (see the sync loop below)
                    self._pending_compile_rec = rec.record(
                        "compile", cap=cap * self.ndev, fcap=fcap,
                        bucket_cap=bucket_cap, cand=cand_local,
                        rung="init" if first_build else "growth",
                        source="fresh", cache_hit=False, duration=0.0,
                    )
            self._last_engine_key = key
            first_build = False
            if fns is None:
                fns = _build_sharded_run(
                    self.tensor, self._props, self.mesh, cap, fcap, bucket_cap,
                    self._target, sym=sym, steps=self._steps,
                    cand_local=cand_local, prededup=self._prededup,
                    cartography=self._cartography,
                    por=self._por_plan if self._por else None,
                    mxu=self._mxu,
                )
                cache[key] = fns
            init_fn, step_fn = fns
            from_init = False
            watch = CompileWatch() if rec is not None else None
            t_call = time.monotonic()
            if finished is not None:
                out = (
                    tuple(jnp.asarray(c) for c in finished)
                    + tuple(jnp.asarray(z) for z in self._por_resume_host())
                    + tuple(jnp.asarray(z) for z in self._cart_resume_host())
                    + (jnp.int32(0),)
                )
                watch = None
            elif pending is not None:
                if len(pending) == 10:
                    # re-seed the carry tail: the POR boost/tallies and the
                    # snapshot's stored cumulative cartography counters
                    # (zeros only for snapshots predating each feature)
                    pending = (
                        list(pending)
                        + self._por_resume_host()
                        + self._cart_resume_host()
                    )
                out = step_fn(*pending)
                pending = None
            else:
                out = init_fn()
                from_init = True
            while True:
                # only the replicated scalars cross to the host per sync
                # (one batched transfer); the sharded carry stays
                # device-resident between calls
                carry = out[:ncarry]
                pulls = [out[5], out[6], out[8], out[9], out[ncarry], out[7]]
                if self._por:
                    pulls.append(out[11])  # the reduced-vs-full tallies
                if self._cartography:
                    pulls.extend(out[cart_lo:ncarry])
                got = jax.device_get(tuple(pulls))
                unique, scount, depth, status, more, disc = got[:6]
                tail_arrs = got[6:]
                if self._por:
                    self._live_por = self._por_stats_dict(tail_arrs[0])
                    tail_arrs = tail_arrs[1:]
                cart_arrs = tail_arrs
                if rec is not None and watch is not None:
                    # the device_get above blocked on the dispatched block:
                    # dispatch-to-materialize is the real device+compile wall
                    dt = time.monotonic() - t_call
                    d = watch.delta()
                    comp = min(max(d["compile_secs"], 0.0), dt)
                    self._stage("compile", comp)
                    self._stage("device", dt - comp)
                    if self._pending_compile_rec is not None:
                        if comp > 0:
                            prev = self._pending_compile_rec
                            hit = (bool(prev.get("cache_hit"))
                                   or d["persistent_hits"] > 0)
                            rec.amend(
                                prev,
                                duration=round(
                                    float(prev.get("duration", 0.0)) + comp,
                                    6,
                                ),
                                cache_hit=hit,
                                source="persistent" if hit else "fresh",
                            )
                        else:  # converged: stop amending this event
                            self._pending_compile_rec = None
                    watch = None
                unique, scount, depth, status, more = (
                    int(unique), int(scount), int(depth), int(status),
                    int(more),
                )
                self._live = (scount, unique, depth)
                self._live_disc = np.asarray(disc)
                if self._cartography and cart_arrs:
                    self._sync_cartography(
                        cart_arrs, states=scount, unique=unique
                    )
                if rec is not None:
                    syncs += 1
                    # the replicated scalars + discovery vector are the
                    # per-sync D2H transfer (lockstep-growth round-trips
                    # are recorded as events, not byte-priced)
                    rec.add_bytes(d2h=5 * 8 + np.asarray(disc).nbytes)
                    rec.step(
                        engine="sharded", states=scount, unique=unique,
                        depth=depth, status=status,
                        cap=cap * self.ndev, cand=cand_local * self.ndev,
                        load_factor=round(unique / (cap * self.ndev), 6),
                        # only the keep-going flag crosses to the host, not
                        # a frontier count: hand the health model liveness
                        # explicitly so the final zero-novelty sync is
                        # completion-shaped, never a stall
                        busy=bool(more),
                    )
                    if occ_every and syncs % occ_every == 0:
                        self._telemetry_occupancy(
                            self._host_table(carry[0]),
                            at=f"sync{syncs}", transferred=True,
                        )
                    if self._mem_ledger is not None:
                        self._mem_ledger.observe(
                            {"cap": cap * self.ndev, "fcap_local": fcap},
                            extra={
                                "frontier_capacity": fcap * self.ndev,
                            },
                        )
                # chaos seam (testing/faults.py): inert unless a FaultPlan
                # is installed; host-side only, jaxpr untouched
                faults.fire(
                    "host_sync", recorder=rec, step=hs, unique=unique
                )
                hs += 1
                if self._ckpt_req is not None and self._ckpt_req.is_set():
                    self._ckpt_out = self._carry_to_snapshot(
                        carry, more, cap, fcap, bf, cf
                    )
                    self._ckpt_req.clear()
                    self._ckpt_ready.set()
                if single_controller:
                    # periodic autosave (checkpoint.py) — single-controller
                    # only, like checkpoint(): the full sharded carry is
                    # not addressable across hosts
                    self._maybe_autosave(
                        lambda: self._carry_to_snapshot(
                            carry, more, cap, fcap, bf, cf
                        ),
                        force=self._stop.is_set(),
                    )
                if status != _OK or not more or self._stop.is_set():
                    break
                if self._profiler is not None:
                    self._profiler.maybe_start()
                watch = CompileWatch() if rec is not None else None
                t_call = time.monotonic()
                out = step_fn(*carry)
                from_init = False
                if self._profiler is not None:
                    self._profiler.tick()
            if status == _POISON:
                raise RuntimeError(
                    "poisoned rows reached by the device run: a compiled "
                    "transition crossed its compile-time state_bound/"
                    "env_bound, so counts would be silently wrong. Loosen "
                    "the bounds (they must cover everything the bounded "
                    "configuration actually reaches)."
                )
            if status != _OK and not self._stop.is_set():
                # chaos seam: growth boundaries are the device-OOM locus
                faults.fire(
                    "growth", recorder=rec, status=status, unique=unique
                )
                if rec is not None:
                    rec.record(
                        "growth", status=status_names.get(status, str(status)),
                        unique=unique, cap=cap * self.ndev,
                        from_init=from_init,
                    )
                    if status == _CAND_OVERFLOW:
                        rec.add("compaction_hits")
                if (
                    rec is not None
                    and self._cartography
                    and getattr(self, "_live_cart", None)
                ):
                    rec.record("cartography", at="growth", **self._live_cart)
                if from_init:
                    # init overflow: nothing ran yet, so a plain re-init at
                    # doubled capacity loses no work (device_init is not
                    # atomic — its frontier compaction truncates)
                    if status == _TABLE_OVERFLOW:
                        cap *= 2
                    elif status == _FRONTIER_OVERFLOW:
                        fcap *= 2
                    elif status == _CAND_OVERFLOW:
                        cf *= 2
                    else:
                        bf *= 2
                else:
                    # mid-run overflow: the atomic step rolled back, so the
                    # carry is consistent — grow and resume.  Works under
                    # multi-controller SPMD: status is replicated, so EVERY
                    # controller takes this branch at the same step boundary
                    # and performs the identical per-shard transform on its
                    # own addressable data (lockstep growth).
                    self.growth_events.append((status, unique))
                    t_grow = time.monotonic()
                    # host seam span: mesh-wide lockstep resharding is
                    # the sharded engine's expensive host excursion —
                    # the trace nests it under the engine_run span
                    with tel_span(
                        "resharding", rec,
                        parent=self._run_span_ctx, cap=int(cap),
                        unique=int(unique),
                    ):
                        cap, fcap, bf, cf, pending = (
                            self._grow_carry_lockstep(
                                carry, cap, fcap, bf, cf, status
                            )
                        )
                    if self._por:
                        # growth is a boundary: arm one fully expanded
                        # wavefront (replicated scalar, lockstep-safe)
                        from jax.sharding import NamedSharding

                        pending = list(pending)
                        pending[10] = jax.device_put(
                            jnp.int32(1), NamedSharding(self.mesh, P())
                        )
                    self._stage("growth", time.monotonic() - t_grow)
                continue
            break
        self._cap_local, self._fcap_local, self._bucket_factor = cap, fcap, bf
        self._cand_factor = cf
        if self._profiler is not None:
            self._profiler.stop()
        self._results = {
            "unique": unique,
            "states": scount,
            "disc": np.asarray(carry[7]),
            "depth": depth,
            "table_fp": self._host_table(carry[0]),
            "table_parent": self._host_table(carry[1]),
        }
        if self._por and self._live_por is not None:
            self._results["por"] = dict(self._live_por)
        if self._cartography and getattr(self, "_live_cart", None):
            self._results["cartography"] = self._live_cart
            if rec is not None:
                rec.record("cartography", at="final", **self._live_cart)
        if rec is not None:
            # the final tables just crossed to the host for _results —
            # price that pull, then take the closing occupancy sample on
            # the already-host-side array (free)
            rec.add_bytes(
                d2h=self._results["table_fp"].nbytes
                + self._results["table_parent"].nbytes
            )
            self._telemetry_occupancy(
                self._results["table_fp"], at="final", transferred=False
            )
        if self._mem_ledger is not None:
            self._mem_ledger.finalize()
        if rec is not None:
            rec.close_run(done=not self._timed_out)
        # keep the final carry device-resident; a stopped run's snapshot
        # keeps more=1 so resume continues it (see _final_snapshot)
        # full carry (base 10 + cartography counter tail when on): the
        # final snapshot persists the counters too (_carry_to_snapshot)
        self._final_state = (carry, more, (cap, fcap, bf, cf))
        self._warn_small_space()
        self._done.set()


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p

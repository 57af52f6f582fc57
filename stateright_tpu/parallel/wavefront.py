"""The TPU wavefront BFS engine — ``spawn_tpu()``.

Replaces the reference's work-stealing threaded BFS (``src/checker/bfs.rs``)
with data-parallelism over states.  The engine keeps a device-resident FIFO
**work queue** of encoded state rows and, per inner step (one jitted
``lax.while_loop`` iteration), pops a fixed-size batch:

 1. evaluates all property conditions as fused boolean kernels over the
    batch (reference analogue ``bfs.rs:192-227``), recording first-hit
    fingerprints per property;
 2. expands every row through the tensor model's static-arity transition
    (``step_rows``), masking disabled/no-op actions;
 3. flushes pending ``eventually`` bits at terminal rows as liveness
    counterexamples (``bfs.rs:265-272``; the reference's documented DAG-join /
    cycle caveats are replicated since ebits are not fingerprinted);
 4. fingerprints all successors, dedupes the batch (sort + first-occurrence
    mask), and inserts into the HBM bucketized table (``ops/buckets.py``),
    which stores the parent fingerprint per slot — the device analogue of the
    reference's ``DashMap<Fingerprint, Option<Fingerprint>>`` (``bfs.rs:26``);
 5. appends the novel survivors at the queue tail.

Because the queue is FIFO and successors of depth-``d`` rows are appended
after every depth-``d`` row was enqueued, pops are in exact BFS level order —
parent pointers therefore record shortest paths, like single-threaded
reference BFS.  The fixed expansion batch keeps every intermediate buffer
small and independent of the state-space size (the round-1 design expanded a
whole BFS level at once, whose worst-case buffers grew past what the backend
could allocate).

**Growth without lost work.**  All capacities are static shapes, but unlike
the round-1 engine (restart from scratch with doubled capacity), the run
stops at a *clean batch boundary* whenever the hash table passes 50%
occupancy or the queue tail passes its high-water mark; the host then grows
the offending buffer — rehashing the table or compacting/extending the queue
in numpy — and resumes exactly where the device left off.  The same
host-visible carry powers **checkpoint/resume** (SURVEY §5: wavefront
checkpointing): :meth:`TpuChecker.checkpoint` snapshots the run mid-flight
and ``spawn_tpu(resume=snapshot)`` continues it, surviving process restarts.

Trace reconstruction is identical in spirit to the reference
(``bfs.rs:314-342``): walk parent fingerprints back to an init state — on
the device, against the final carry's table where it lies
(``ops/buckets.parent_chains``; only the chains cross to the host) — then
re-execute the *object-form* model on the host (``Path.from_fingerprints``),
which works because host and device fingerprint functions agree bit-for-bit.

**Symmetry reduction** (beyond the reference, whose symmetry is DFS-only):
when the builder requests ``symmetry()`` and the tensor twin provides a
vectorized ``representative_rows``, the engine keeps exploring ORIGINAL
rows but dedups/keys the table on the canonical class member's hash — the
device analogue of ``checker/dfs.py::_dedup_key``.  Novel rows are appended
in generation order, so the reduced search equals a host FIFO-BFS oracle
exactly (see ``tests/test_tensor_models.py::host_fifo_sym_oracle``); traces
reconstruct by matching canonical fingerprints class-wise
(``Path.from_fingerprints(key=...)``).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..checker.base import CheckerBuilder
from ..core import Expectation
from ..ops.buckets import (
    SLOTS,
    bucket_insert,
    bucket_split,
    host_bucket_rehash,
    lane_compact,
    window_unique,
)
from ..ops.hashing import EMPTY, row_hash
from ..telemetry.spans import (
    PROGRAM_LOAD,
    PROGRAM_LOWER,
    STAGE_APPEND,
    STAGE_BOOKKEEP,
    STAGE_EXPAND,
    STAGE_GROW,
    STAGE_HASH,
    STAGE_INSERT,
    STAGE_POP,
    STAGE_PROPS,
    STAGE_STATS,
    SYM_CANON,
    TWIN_POISON,
    record_span,
)
from ..telemetry.spans import span as tel_span
from ..testing import faults
from ._base import WavefrontChecker
from .carry import (
    QUEUE_FIELDS,
    SNAPSHOT_KEYS,
    ST_DSTEPS,
    Carry,
    CartTail,
    PorTail,
    SpillTail,
    carry_avals,
    depth_hist,
    fresh_tails,
    queue_alloc,
    read_stats,
    repad_queue,
    stats_np,
    stats_of,
)
from .prewarm import LOWER_EVENT, CompileWatch

_STATUS_OK = 0
_STATUS_QUEUE_FULL = 1
_STATUS_TABLE_FULL = 2
_STATUS_CAND_FULL = 3  # valid candidates exceeded the compaction budget
_STATUS_POISON = 4  # a compiled-twin transition crossed its compile bound
_STATUS_SPILL_SYNC = 5  # spill tier: pending buffer near-full, the host
#                         must resolve it against the host index

# growth-record names for the flight recorder, keyed on THIS engine's
# status words (telemetry.STATUS_NAMES is the recorder's vocabulary)
_STATUS_TELEMETRY_NAMES = {
    _STATUS_OK: "ok",
    _STATUS_QUEUE_FULL: "queue_full",
    _STATUS_TABLE_FULL: "table_full",
    _STATUS_CAND_FULL: "cand_full",
    _STATUS_POISON: "poison",
    _STATUS_SPILL_SYNC: "spill_sync",
}


def _in_column_planes(rows):
    """``rows`` (a chunk ``[n, width]`` of payload rows), its value
    unchanged, pinned to the physical order the queue's payload buffer
    lies in on the TPU: one plane a word (``u32[qalloc, 33]`` pads its 33
    words to 40 that way, and to 128 row by row).  An update slice inside
    a nested loop takes the BUFFER's layout from its update, and a row
    gather emits rows word by word - so without the pin the compiler
    carries the queue row-major (3.9x the bytes at 33 words) through the
    whole run program and re-lays it out for every pop, two whole-queue
    copies a step.  A one-dimensional array has ONE physical order, so
    flattening the transposed chunk behind a barrier fixes it; the
    transposition is the chunk's, never the queue's.
    ``tests/test_table_layout.py`` holds the compiled step to no copy of
    the queue."""
    n, width = rows.shape
    planes = jax.lax.optimization_barrier(rows.T.reshape(n * width))
    return planes.reshape(width, n).T


def _chunk_rows(block, off, n: int, width: int):
    """Rows ``[off, off + n)`` of ``block`` (``[cand, width]``, the gathered
    payload rows a ``while_loop`` slices), cut loose from the layout the
    loop's body wants them in: the slice is flattened behind a barrier - a
    one-dimensional array has ONE physical order - so whatever order the
    body's write asks of the chunk stops there, and ``block`` enters
    the loop as its gather laid it out, with no copy at the loop's edge.
    The rows are padded to the TPU tile's 128 lanes first: a row-major
    ``[n, 128]`` and its flattening are the same bytes, which a ``[n, 21]``
    and its flattening are not (a pass, and a megabyte of code a step
    program at 4,096 rows).

    A ``block`` that was flattened WHOLE before the loop (``[cand x
    width]``, the mesh's: ``append_novel``) is cut loose already: its chunk
    is a slice of it, as it lies."""
    if block.ndim == 1:
        return jax.lax.dynamic_slice(
            block, (off * width,), (n * width,)
        ).reshape(n, width)
    lanes = -(-width // 128) * 128
    rows = jax.lax.dynamic_slice(block, (off, jnp.int32(0)), (n, width))
    flat = jax.lax.optimization_barrier(
        jnp.pad(rows, ((0, 0), (0, lanes - width))).reshape(n * lanes)
    )
    return flat.reshape(n, lanes)[:, :width]


def append_novel(bufs, tail0, sel, n_new, cands, qchunk: int, place=None):
    """Append the novel-compacted ``sel`` prefix of the candidate arrays
    ``cands`` to the queue buffers ``bufs`` (rows, fp, ebits, depth: one
    candidate array a buffer) at ``tail0``: the buffers, and how many
    chunks were written.

    The work follows ``n_new``, not ``cand``: ONE body, a ``while_loop``
    over ``qchunk``-row chunks while ``k * qchunk < n_new`` (a step's
    novel rows are a twentieth to a fifth of ``cand``); a chunk slices
    ``qchunk`` lanes of ``sel``, takes the candidate arrays' rows there
    and writes them at ``tail0 +`` the chunk's start.  Where ``qchunk``
    does not divide ``len(sel)`` the last chunk starts at ``len(sel) -
    qchunk``: a slice whose start clamped would misalign the rows it
    writes, and this one rewrites rows the chunk before it wrote, with the
    same values.  Rows ``[tail0, tail0 + n_new)`` are exact; rows past
    them, up to the last chunk's end - inside ``carry.queue_alloc``'s
    slack - are garbage that later appends overwrite before ``tail``
    reaches them; a batch with nothing novel (an overflowed one too)
    writes nothing.

    A column of words is gathered inside the loop, a chunk's lanes a
    trip.  A payload WIDER than a word is gathered once, ``len(sel)``
    rows before the loop, and the loop slices it (``_chunk_rows``): the
    candidate block reaches the gather through a relayout (planes to
    rows: a pass or two over ``batch x actions x width`` words), and the
    TPU compiler files that pass under the stage that shaped the block
    only while the gather that reads it stands outside any loop - as a
    ``while``'s operand the relaid block gets a copy with no name, which
    the profile's reader files under no stage
    (``tests/test_table_layout.py`` holds the compiled step to that).
    One-word rows have no such relayout and go with the columns.

    On a mesh (``place``: ``partition.StepPlacement``) the body is the
    same and the trips are counted the same; ``place`` decides how the
    gathered block is held and how a chunk is written.  The block is made
    whole before the loop (``place.whole``): a chunk's rows may belong to
    any chip's range of the queue, so every chip holds the ``len(sel)``
    novel rows - one all-reduce a step, the window's own - and the loop
    slices them with no collective a trip.  It is also flattened whole
    there, behind the barrier, where one chip pads and flattens a chunk a
    trip: a chip of the mesh runs the insert's loop with its table shard's
    planes in the chip's fast memory and this loop with the narrow
    columns' shards there, and a padded chunk's buffers beside them are
    more than that memory holds - the compiler then leaves the TABLE's
    planes out of it and every scatter of the insert pays (read off the
    text compiled for the described 2x2 at the benchmark's shapes: the
    memory space of the insert's scatters' operands; PERF.md section 6).
    One pass over the block a step is the cheaper price, and the one-chip
    engines cannot pay it for their code's size (``_chunk_rows``).  And a
    chunk is written through ``place.append``, by row index at ``tail0 +
    off``, where one chip writes an update slice: an update slice at a
    traced offset of a sharded buffer makes the partitioner gather the
    buffer whole.  The chunk goes to the scatter as ``_chunk_rows`` left
    it, not through ``_in_column_planes``: a scatter by row index takes its
    operand's layout, not its update's, so the queue's shard stays in
    column planes without the pin (``tests/test_table_layout.py`` holds the
    text compiled for the 2x2 to no copy of a shard inside a loop and none
    the window's module lacked), and the pin would be a transposition a
    trip for nothing."""
    last = jnp.int32(sel.shape[0] - qchunk)
    # a payload wider than a word: gathered here, sliced in the loop
    wide = [c.ndim == 2 and c.shape[1] > 1 for c in cands]
    srcs = [c[sel] if w else c for c, w in zip(cands, wide)]
    if place is not None:
        srcs = [
            jax.lax.optimization_barrier(place.whole(s).reshape(-1)) if w else s
            for s, w in zip(srcs, wide)
        ]

    def written(q, src, is_wide, off, w_idx):
        rows = (_chunk_rows(src, off, qchunk, q.shape[1]) if is_wide
                else src[w_idx])
        at = tail0 + off
        if place is not None:
            return place.append(q, rows, at)
        if q.ndim == 2:
            rows = _in_column_planes(rows)
        return jax.lax.dynamic_update_slice(
            q, rows, (at,) + (jnp.int32(0),) * (q.ndim - 1)
        )

    def chunk(state):
        k, bufs = state
        off = jnp.minimum(k * qchunk, last)
        w_idx = jax.lax.dynamic_slice(sel, (off,), (qchunk,))
        return k + 1, tuple(
            written(q, src, w, off, w_idx)
            for q, src, w in zip(bufs, srcs, wide)
        )

    n_chunks, bufs = jax.lax.while_loop(
        lambda s: s[0] * qchunk < n_new, chunk, (jnp.int32(0), tuple(bufs))
    )
    return bufs, n_chunks


def _build_engine(tensor, props, cap: int, qcap: int, batch: int,
                  steps: int, target: Optional[int],
                  sym: bool = False, cand: Optional[int] = None,
                  checked: bool = False, prededup: bool = False,
                  cartography: bool = False, por=None, spill=None,
                  mxu=None, place=None):
    """Build ``(init_fn, run_fn)`` for fixed capacities.

    ``qcap`` is the queue high-water mark; the buffers are over-allocated by
    one batch's worth of candidates (``m``) so the dynamic slice/update at
    ``head``/``tail`` is always in bounds without clamping.

    ``cand`` is the valid-candidate compaction budget per batch (see
    ``ops/buckets.bucket_insert``): the insert pipeline runs at this width
    instead of the padded ``batch * arity``.  A batch whose enabled-action
    count exceeds it reports ``_STATUS_CAND_FULL`` without writing anything
    and the host doubles the budget and replays — self-tuning, like the
    other capacities.

    ``prededup`` masks intra-window duplicate candidate fingerprints to
    EMPTY (``ops/buckets.window_unique``) before the insert, shrinking the
    insert pipeline's effective width to the window's unique count.  The
    inserted set, counts, and traces are bit-identical either way (the
    filter keeps exactly the lane the insert's stable sort would keep);
    off by default, and off means zero extra ops in the step jaxpr.

    ``por`` is the resolved partial-order-reduction plan
    (``analysis/independence.PorPlan``, None = off): each batch masks its
    enabled-action matrix down to a per-state ample subset
    (``ops/por.ample_mask`` — the stubborn-set closure over the
    compile-time conflict matrix) and inserts only the ample successors;
    a second insert in the same step fully expands exactly the rows whose
    ample successors were ALL duplicates (the conservative cycle
    proviso), and a ``boost`` carry scalar forces one fully-expanded
    batch after every growth/resume boundary.  Both inserts are atomic
    together: any overflow rolls the table back to the pre-step buffers
    so the replay after growth sees the same novelty verdicts.  Off means
    zero extra ops in the step jaxpr (the telemetry/checked/prededup
    contract, pinned by test).

    ``mxu`` is the resolved MXU-recast config (``ops/mxu.MxuConfig``,
    None = off; docs/roofline.md "Executing the hot-spot list"): two
    flag-gated bytes-moved reductions executing the JX4xx hot-spot
    ranking — ``coalesce`` traces the twin's scatter-coalesced step
    kernel (``step_rows_coalesced``) when it provides one, and ``probe``
    recasts the bucket membership reductions as one blocked bitmapped
    ``dot_general`` (``bucket_insert(probe_dot=True)``).  Off means zero
    extra ops AND the exact unflagged jaxpr (the prededup contract); on,
    counts/verdicts/traces are bit-identical — pinned by tests.
    ``checked`` mode keeps the plain step under its checkify wrapper
    (the coalesced kernel is a perf shape, not a debug surface); the
    probe recast still applies.

    ``checked`` is the sanitizer's dynamic guard
    (``stateright_tpu/analysis/sanitizer.py``): the MODEL kernels
    (``property_masks`` + ``step_rows``) run under
    ``jax.experimental.checkify`` index/nan/div instrumentation, with a
    sticky failure flag threaded through the while-loop carry;
    the loop stops at the first failing batch and the host loop raises a
    :class:`~stateright_tpu.analysis.CheckedExecutionError` naming the
    offending row.  Only the model kernels are wrapped — the engine's
    insert deliberately scatters out of range with ``mode='drop'`` (dead
    lanes), which the OOB check would flag by design.  ``checked=False``
    is bit-identical to an engine built before the flag existed (pinned
    by test, same contract as telemetry).

    ``place`` is the mesh engine's hook (``partition.StepPlacement``, None
    on one device): where the step's VALUES lie across the chips - the
    popped batch and the candidate block by lane, what the insert and the
    append take replicated - and the pop and the append spelled by row
    index, which the partitioner splits by queue shard.  It never touches
    the carry's own placement (the run program's in/out shardings), and
    off it is the identity: no equation of the step's jaxpr comes from it.
    """
    width, arity = tensor.width, tensor.max_actions
    m = batch * arity
    eff_cand = min(cand, m) if cand else m
    # MXU-recast knobs (ops/mxu.py): resolved once here so the off path
    # below stays literally the pre-MXU expressions (jaxpr pin)
    from ..ops.mxu import coalesced_step_fn

    step_rows_fn = coalesced_step_fn(tensor, mxu)
    probe_dot = bool(mxu is not None and mxu.probe)
    # the append's chunk: what one trip of append_novel's loop gathers
    # and writes (a ``cand`` under ``batch`` is one chunk)
    qchunk = min(batch, eff_cand)
    qalloc = queue_alloc(qcap, m, por is not None, spill)
    n_props = len(props)
    ev_idx = [
        i for i, p in enumerate(props) if p.expectation is Expectation.EVENTUALLY
    ]
    ebit_of = {i: e for e, i in enumerate(ev_idx)}
    if len(ev_idx) > 32:
        raise ValueError("at most 32 eventually properties are supported")
    init_ebits = jnp.uint32((1 << len(ev_idx)) - 1)

    init_rows_np = np.asarray(tensor.init_rows(), dtype=np.uint64)
    n_init = init_rows_np.shape[0]

    if checked:
        from ..analysis.sanitizer import checkify_kernels, error_flag

        # the carry threads only a BOOLEAN "some check failed" scalar:
        # checkify Error pytrees mint fresh error codes per trace, so the
        # full error cannot ride a carry across jit boundaries — and the
        # host localizes by re-running the failing batch row-by-row, which
        # reconstructs the full message anyway
        checked_kernels = checkify_kernels(tensor)

    if spill is not None:
        # spill tier (stateright_tpu/spill/, docs/spill.md): POR's
        # two-phase insert and the Bloom deferral do not compose yet —
        # the builder rejects the combination before the engine is built
        assert por is None, "spill and por are mutually exclusive"
        from ..spill.bloom import bloom_test

        spill_bits, pend_cap = spill
    if por is not None:
        from ..analysis.footprint import conjunct_eval_fn
        from ..ops.por import ample_mask, candidate_novelty

        conjunct_kernel = conjunct_eval_fn(tensor)
    # search-cartography counters (ops/cartography.py): action histogram +
    # property tallies only; the depth histogram is queue-derived at sync
    # time (carry.stats_of), so the per-step cost stays at two small
    # column-sums.  Off means zero extra ops in the step jaxpr (same
    # contract as telemetry/checked/prededup, pinned by test)
    if cartography:
        from ..ops.cartography import action_hist_delta, prop_tally_delta

    def record_first(disc, i, hit, fps):
        """First-wins discovery of property ``i`` at the first hit row."""
        fp = fps[jnp.argmax(hit)]
        take = (disc[i] == jnp.uint64(0)) & jnp.any(hit)
        return disc.at[i].set(jnp.where(take, fp, disc[i]))

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

    boundary_fn = (
        tensor.boundary_rows
        if getattr(tensor, "has_boundary", False)
        else None
    )
    poison_fn = getattr(tensor, "poison_rows", None)
    if place is None:
        lanes = whole = lambda x: x  # one device: no equation left behind
    else:
        lanes, whole = place.lanes, place.whole

    def step(carry):
        """Pop one batch, expand, dedup+insert, append novel rows."""
        (tfp, tpl, qrows, qfp, qebits, qdepth, head, tail,
         unique, scount, disc, maxdepth, status) = carry.base()
        err, cart, sp = carry.err, carry.cart, carry.spill
        with jax.named_scope(STAGE_POP):
            n_avail = tail - head
            if place is not None:
                rows, fps, ebits, depths = (
                    place.pop(q, head, batch)
                    for q in (qrows, qfp, qebits, qdepth)
                )
            else:
                rows = jax.lax.dynamic_slice(qrows, (head, jnp.int32(0)), (batch, width))
                fps = jax.lax.dynamic_slice(qfp, (head,), (batch,))
                ebits = jax.lax.dynamic_slice(qebits, (head,), (batch,))
                depths = jax.lax.dynamic_slice(qdepth, (head,), (batch,))
            live = jnp.arange(batch, dtype=jnp.int32) < n_avail

        with jax.named_scope(STAGE_PROPS):
            if checked:
                # both model kernels under checkify; sticky failure flag.
                # Dead lanes (past n_avail) hold queue padding/garbage the
                # unchecked engine discards via the live mask AFTER computing
                # on them — checkify would check that garbage and abort on
                # phantom rows, so substitute a known-good init row first
                # (outputs for those lanes are discarded identically below)
                safe_rows = jnp.where(
                    live[:, None], rows, jnp.asarray(init_rows_np[0])[None, :]
                )
                err_new, (masks, succ, valid) = checked_kernels(safe_rows)
                err = err | error_flag(err_new)
            else:
                masks = tensor.property_masks(rows)  # [B, P] bool
            ebits, disc = eval_props(masks, fps, live, ebits, disc)
            maxdepth = jnp.maximum(
                maxdepth, jnp.max(jnp.where(live, depths, 0)).astype(jnp.int32)
            )
            # Mid-run early exit (reference ``bfs.rs:121-128``): stop expanding
            # once every property has a discovery.
            elive = live & ~all_discovered(disc)

        with jax.named_scope(STAGE_EXPAND):
            if not checked:
                succ, valid = step_rows_fn(rows)  # [B, A, W], [B, A]
            succ, valid = lanes(succ), lanes(valid)
            if boundary_fn is not None:
                # mirror the host checkers: out-of-boundary successors are
                # neither counted nor enqueued, and a state whose successors
                # all fall outside IS terminal for ebits flushing
                valid = valid & boundary_fn(succ)
            valid = valid & elive[:, None]
            terminal = elive & ~jnp.any(valid, axis=-1)
        with jax.named_scope(STAGE_PROPS):
            disc = flush_terminal(terminal, fps, ebits, disc)

        with jax.named_scope(STAGE_HASH):
            # Under symmetry the search still explores ORIGINAL states (queue
            # rows) but dedups / keys the table on the canonical class member's
            # hash — the host analogue is ``checker/dfs.py::_dedup_key``, and it
            # preserves the reference's pinned symmetry counts (2pc.rs:138).
            if sym:
                with jax.named_scope(SYM_CANON):
                    # the barrier keeps the canonicaliser in the successor
                    # block's layout: fused into row_hash's flat candidate
                    # layout XLA splits it in two and copies + reshapes every
                    # per-actor column between them (2pc-13: ~100 a step,
                    # 0.017 of the stage's 0.020 s; PERF.md section 6, PR 34)
                    krows = jax.lax.optimization_barrier(
                        tensor.representative_rows(succ)
                    )
            else:
                krows = succ
            if por is not None:
                # ample-set selection: expand only a minimal conflict-closed
                # subset of each row's enabled actions; the boost scalar (set
                # by the host at growth/resume boundaries) forces one fully
                # expanded batch, and stays armed until a batch succeeds
                boost, pstats = carry.por.boost, carry.por.stats
                amp = ample_mask(valid, rows, por, conjunct_kernel)
                amp = jnp.where(boost > 0, valid, amp)
                v1 = amp
                all_fp = jnp.where(valid, row_hash(krows), EMPTY)
                cand_fp = jnp.where(v1, all_fp, EMPTY).reshape(m)
            else:
                # exactly the pre-POR expression: the off-path jaxpr must stay
                # bit-identical (a nested same-predicate select would add an
                # eqn and silently break the cross-release compile cache)
                v1 = valid
                cand_fp = jnp.where(valid, row_hash(krows), EMPTY).reshape(m)
            if prededup:
                # intra-window pre-dedup (BLEST-style): duplicate lanes become
                # EMPTY so the compaction budget, membership gathers, and rank
                # pipeline run at the window's UNIQUE count.  scount deliberately
                # still sums the generated states, duplicates included.
                cand_fp = window_unique(cand_fp)
            if spill is not None:
                # Bloom pre-filter (spill/bloom.py): a candidate the filter
                # says MAY be spilled leaves the on-device insert entirely —
                # it is appended to the pending buffer below and resolved
                # against the host index at the next host sync.  A Bloom MISS
                # is a proof of off-device absence (no false negatives), so
                # the common case never leaves the chip; before the first
                # eviction the filter is all-zero and nothing defers.
                fp_full = cand_fp
                maybe_spilled = (cand_fp != EMPTY) & bloom_test(
                    sp.bloom, cand_fp, spill_bits
                )
                cand_fp = jnp.where(maybe_spilled, EMPTY, cand_fp)
            # the insert ranks the whole block's keys against each other:
            # it takes them on every chip (m words, not m rows)
            cand_fp = whole(lanes(cand_fp))
            cand_rows = lanes(succ.reshape(m, width))
            cand_par = whole(
                jnp.broadcast_to(fps[:, None], (batch, arity)).reshape(-1)
            )
            cand_ebt = jnp.broadcast_to(ebits[:, None], (batch, arity)).reshape(-1)
            cand_dep = jnp.broadcast_to(
                depths[:, None] + jnp.uint32(1), (batch, arity)
            ).reshape(-1)

        with jax.named_scope(STAGE_INSERT):
            if por is not None:
                tfp_pre, tpl_pre = tfp, tpl  # two-phase atomic rollback
            # window stays at ``batch`` (measured: one cand-wide loop iteration
            # is SLOWER than 2-3 batch-wide ones — wide iterations pay for dead
            # lanes; the compaction budget only bounds the pipeline width)
            tfp, tpl, sel, n_new, toverflow, coverflow = bucket_insert(
                tfp, tpl, cand_fp, cand_par, window=batch,
                generation_order=sym, compact=eff_cand, probe_dot=probe_dot,
            )
        with jax.named_scope(STAGE_APPEND):
            # Append novel rows (novel-compacted ``sel`` prefix) at the queue
            # tail, in whole chunks up to ``n_new`` (see append_novel)
            (qrows, qfp, qebits, qdepth), n_chunks = append_novel(
                (qrows, qfp, qebits, qdepth), tail, sel, n_new,
                (cand_rows, cand_fp, cand_ebt, cand_dep), qchunk, place,
            )

        if por is not None:
            with jax.named_scope(STAGE_INSERT):
                # conservative cycle proviso: a reduced row whose ample
                # successors were ALL duplicates is fully expanded — its
                # remaining (non-ample) candidates go through a second insert
                # in the same step, so no state can be starved around a cycle
                novel = candidate_novelty(m, sel, n_new)
                reduced_row = jnp.any(valid & ~amp, axis=1)
                fresh_row = jnp.any(novel.reshape(batch, arity), axis=1)
                need_full = reduced_row & ~fresh_row
                v2 = valid & ~amp & need_full[:, None]
                cand_fp2 = jnp.where(v2, all_fp, EMPTY).reshape(m)
                if prededup:
                    cand_fp2 = window_unique(cand_fp2)
                tail1 = tail + n_new
                tfp, tpl, sel2, n_new2, tovf2, covf2 = bucket_insert(
                    tfp, tpl, cand_fp2, cand_par, window=batch,
                    generation_order=sym, compact=eff_cand,
                    probe_dot=probe_dot,
                )
            with jax.named_scope(STAGE_APPEND):
                (qrows, qfp, qebits, qdepth), n_chunks2 = append_novel(
                    (qrows, qfp, qebits, qdepth), tail1, sel2, n_new2,
                    (cand_rows, cand_fp2, cand_ebt, cand_dep), qchunk, place,
                )
                n_chunks = n_chunks + n_chunks2
            toverflow = toverflow | tovf2
            coverflow = coverflow | covf2
            n_new_all = n_new + n_new2
        else:
            n_new_all = n_new

        with jax.named_scope(STAGE_BOOKKEEP):
            # Any overflow means the batch wrote nothing durable: leave the
            # cursors and counters untouched so the batch replays after the
            # host grows the table / candidate budget.  (The queue appends
            # above wrote garbage past ``tail``, which the replay overwrites;
            # with POR's two inserts the table itself rolls back so the replay
            # sees the same novelty verdicts.)
            overflow = toverflow | coverflow
        with jax.named_scope(STAGE_APPEND):
            if spill is not None:
                # append the deferred lanes (compacted, order-preserving:
                # lane_compact, as in bucket_insert's budget compaction) at
                # the pending cursor.  The buffer writes run even on
                # an overflowed batch — the cursor then does not advance, so
                # the post-growth replay overwrites the same window (the
                # counters' replay discipline).
                pcount = sp.pend_count
                didx, dlive, n_def = lane_compact(maybe_spilled, m)
                pfp_b = jax.lax.dynamic_update_slice(
                    sp.pend_fp,
                    jnp.where(dlive, fp_full[didx], EMPTY), (pcount,),
                )
                prows_b = jax.lax.dynamic_update_slice(
                    sp.pend_rows, cand_rows[didx], (pcount, jnp.int32(0)),
                )
                ppar_b = jax.lax.dynamic_update_slice(
                    sp.pend_parent, cand_par[didx], (pcount,)
                )
                pebt_b = jax.lax.dynamic_update_slice(
                    sp.pend_ebits, cand_ebt[didx], (pcount,)
                )
                pdep_b = jax.lax.dynamic_update_slice(
                    sp.pend_depth, cand_dep[didx], (pcount,)
                )
                pcount = pcount + jnp.where(overflow, jnp.int32(0), n_def)
                d_sp = jnp.stack([
                    n_def.astype(jnp.int64),
                    jnp.sum(valid, dtype=jnp.int64) - n_def.astype(jnp.int64),
                ])
                sp = sp.replace(
                    pend_fp=pfp_b, pend_rows=prows_b, pend_parent=ppar_b,
                    pend_ebits=pebt_b, pend_depth=pdep_b, pend_count=pcount,
                    stats=sp.stats + jnp.where(overflow, jnp.int64(0), d_sp),
                )
        with jax.named_scope(STAGE_BOOKKEEP):
            if por is not None:
                tfp = jnp.where(overflow, tfp_pre, tfp)
                tpl = jnp.where(overflow, tpl_pre, tpl)
                n_new_all = jnp.where(overflow, 0, n_new_all)
            head = jnp.where(overflow, head, head + jnp.minimum(n_avail, batch))
            tail = tail + n_new_all
            unique = unique + n_new_all.astype(jnp.int64)
            if por is not None:
                gen_mask = v1 | v2
                gen = jnp.sum(gen_mask, dtype=jnp.int64)
            else:
                gen_mask = valid
                gen = jnp.sum(valid, dtype=jnp.int64)
            scount = jnp.where(overflow, scount, scount + gen)
            if por is not None:
                zero64 = jnp.int64(0)
                d_por = jnp.stack([
                    jnp.sum(reduced_row & ~need_full, dtype=jnp.int64),
                    jnp.sum(need_full, dtype=jnp.int64),
                    jnp.sum(valid, dtype=jnp.int64) - gen,
                ])
                pstats = pstats + jnp.where(overflow, zero64, d_por)
                # a successful batch consumes the boundary boost; a replayed
                # (overflowed) one keeps it armed
                boost = jnp.where(overflow, boost, jnp.int32(0))
            if cartography:
                # same replay discipline as scount: an overflowed batch counts
                # nothing so the post-growth replay is the only count.  (The
                # depth histogram needs no guard at all: it is derived from the
                # queue at sync time, and an overflowed insert appended
                # nothing.)  Under POR the histogram counts what was actually
                # GENERATED (ample + proviso re-expansions), which is what
                # reconciles against scount.
                zero = jnp.int64(0)
                act_hist = cart.action_hist + jnp.where(
                    overflow, zero, action_hist_delta(gen_mask)
                )
                d_evals, d_hits = prop_tally_delta(live, masks, n_props)
                cart = CartTail(
                    act_hist,
                    cart.prop_evals + jnp.where(overflow, zero, d_evals),
                    cart.prop_hits + jnp.where(overflow, zero, d_hits),
                )
            # Clean-boundary growth triggers: past these thresholds the host
            # grows buffers and resumes (table target load ≤ 25%: the Poisson
            # bucket-overflow tail stays negligible).  With the spill tier
            # armed the trigger reads HOT occupancy — evicted uniques live
            # off-device and must not count against the hot table's load.
            if spill is not None:
                hot_unique = unique - sp.base
            else:
                hot_unique = unique
            status = jnp.where(
                toverflow | (hot_unique * 4 > cap) | (eff_cand * 4 > cap),
                jnp.int32(_STATUS_TABLE_FULL),
                jnp.where(
                    coverflow,
                    jnp.int32(_STATUS_CAND_FULL),
                    jnp.where(tail > qcap, jnp.int32(_STATUS_QUEUE_FULL), status),
                ),
            )
            if spill is not None:
                # the pending buffer cannot take another full window: stop the
                # block at this clean boundary so the host resolves it.  Lowest
                # priority — a growth status wins (growth also syncs).
                status = jnp.where(
                    (status == jnp.int32(_STATUS_OK))
                    & (pcount + m > jnp.int32(pend_cap)),
                    jnp.int32(_STATUS_SPILL_SYNC),
                    status,
                )
            if poison_fn is not None:
                # a poisoned popped row means a compile-time bound was crossed
                # by a REACHABLE transition — silently wrong counts otherwise;
                # surface it as a terminal host-visible status (takes priority
                # over growth: growing cannot fix a bound)
                with jax.named_scope(TWIN_POISON):
                    status = jnp.where(
                        jnp.any(poison_fn(rows) & live),
                        jnp.int32(_STATUS_POISON),
                        status,
                    )
        return Carry(
            tfp, tpl, qrows, qfp, qebits, qdepth, head, tail,
            unique, scount, disc, maxdepth, status,
            err=err,
            por=PorTail(boost, pstats) if por is not None else None,
            spill=sp, cart=cart,
        ), n_chunks

    def cond(state):
        k, _, carry = state
        go = (carry.status == jnp.int32(_STATUS_OK)) & (k < steps)
        go = go & (carry.tail > carry.head) & ~all_discovered(carry.disc)
        if target is not None:
            go = go & (carry.unique < jnp.int64(target))
        if checked:
            # stop at the first failing batch: the host raises from it
            go = go & ~carry.err
        return go

    # The two programs' function names are their XLA module names
    # (``jit_wavefront_run`` / ``jit_wavefront_init``: the profiler's
    # ``XLA Modules`` line) AND part of the persistent compile cache's key,
    # which otherwise ignores debug metadata: an executable cached before
    # the stages had names must not be served in place of this one.
    def body(state):
        # ``k`` and ``chunks`` ride the loop beside the carry, not in it:
        # the device steps of this call and its append's chunk writes
        k, chunks, carry = state
        carry, n_chunks = step(carry)
        return k + 1, chunks + n_chunks, carry

    def wavefront_run(carry):
        k, chunks, carry = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.int32(0), carry)
        )
        with jax.named_scope(STAGE_STATS):
            return carry, stats_of(carry, k, chunks)

    # the carry is donated on every backend (jax 0.9.0 donates on CPU
    # too): the host loop never touches a carry after passing it in
    run_fn = jax.jit(wavefront_run, donate_argnums=(0,))

    def wavefront_init():
        tfp = jnp.full((cap,), EMPTY, jnp.uint64)
        tpl = jnp.zeros((cap,), jnp.uint64)
        qrows = jnp.zeros((qalloc, width), jnp.uint64)
        qfp = jnp.full((qalloc,), EMPTY, jnp.uint64)
        qebits = jnp.zeros((qalloc,), jnp.uint32)
        qdepth = jnp.zeros((qalloc,), jnp.uint32)

        irows = jnp.asarray(init_rows_np)
        ikrows = irows
        if sym:
            with jax.named_scope(STAGE_HASH), jax.named_scope(SYM_CANON):
                ikrows = tensor.representative_rows(irows)
        ifp = row_hash(ikrows)
        tfp, tpl, sel, n_new, overflow, _ = bucket_insert(
            tfp, tpl, ifp,
            jnp.zeros((n_init,), jnp.uint64),  # parent 0 = "is an init state"
            window=n_init, generation_order=sym, probe_dot=probe_dot,
        )
        qrows = jax.lax.dynamic_update_slice(
            qrows, irows[sel], (jnp.int32(0), jnp.int32(0))
        )
        qfp = jax.lax.dynamic_update_slice(qfp, ifp[sel], (jnp.int32(0),))
        qebits = jax.lax.dynamic_update_slice(
            qebits, jnp.full((n_init,), init_ebits, jnp.uint32), (jnp.int32(0),)
        )
        status = jnp.where(
            overflow
            | (n_new.astype(jnp.int64) * 4 > cap)
            | (eff_cand * 4 > cap),
            jnp.int32(_STATUS_TABLE_FULL),
            jnp.where(
                n_new > qcap,  # init set alone past the high-water mark
                jnp.int32(_STATUS_QUEUE_FULL),
                jnp.int32(_STATUS_OK),
            ),
        )
        carry = Carry(
            tfp, tpl, qrows, qfp, qebits, qdepth,
            jnp.int32(0), n_new,
            n_new.astype(jnp.int64),
            jnp.int64(n_init),  # state_count counts all inits (bfs parity)
            jnp.zeros((max(n_props, 1),), jnp.uint64),
            jnp.int32(0),
            status,
            # (boost 0: the init batch is not a growth/resume boundary; the
            # depth histogram is not carried - the init states' depth-0
            # lanes already sit in qdepth[:n_new], where stats_of derives it)
            **fresh_tails(carry_avals(
                tensor, n_props, cap, qcap, batch, checked, cartography,
                por is not None, spill,
            )),
        )
        with jax.named_scope(STAGE_STATS):
            return carry, stats_of(carry, jnp.int32(0), jnp.int32(0))

    init_fn = jax.jit(wavefront_init)
    return init_fn, run_fn


def _grown_cap(hot_unique: int, cap: int, cand: int, status: int) -> int:
    """The table capacity a growth event leaves: doubled until the hot
    occupancy sits at or under 25% and the candidate budget fits
    (``cap >= 4 * cand``, the engine's actual precondition), or doubled
    once where a single bucket clustered past ``SLOTS`` entries; ``cap``
    itself where the table is not what is full."""

    def small(c: int) -> bool:
        return hot_unique * 4 > c or cand * 4 > c

    if not small(cap):
        return cap * 2 if status == _STATUS_TABLE_FULL else cap
    while small(cap):
        cap *= 2
    return cap


def _array_bytes(carry: Carry) -> int:
    """What the base's buffers weigh (its scalars apart): the bytes a
    growth on the host moves each way."""
    return sum(a.nbytes for a in carry.base() if a.ndim)


def _slide_queue(qrows, qfp, qebits, qdepth, head, tail, *, qalloc: int):
    """The four queue buffers after a growth, where they lie: the live
    window ``[head, tail)`` at row 0 of buffers of ``qalloc`` rows, every
    lane past it the buffer's fill (``EMPTY`` for ``qfp``, 0 else) - bit
    for bit what ``TpuChecker._grow``'s slice and copy and
    :func:`~.carry.repad_queue` leave on the host (the rows the step wrote past ``tail`` are garbage
    there too, and go the same way).  A buffer is padded by ``qalloc``
    rows of fill behind it, so the window's ``dynamic_slice`` never clamps
    whatever ``head`` is; the compiler fuses pad, slice and mask into one
    pass a buffer (``tests/test_table_layout.py`` holds its temporaries to
    the ``u32`` planes of what it reads and writes)."""
    live = jnp.arange(qalloc, dtype=jnp.int32) < tail - head

    def slide(buf, fill):
        fill = jnp.asarray(fill, buf.dtype)
        rest = (1,) * (buf.ndim - 1)
        window = jax.lax.dynamic_slice_in_dim(
            jax.lax.pad(buf, fill, [(0, qalloc, 0)] + [(0, 0, 0)] * len(rest)),
            head, qalloc,
        )
        return jnp.where(live.reshape((qalloc,) + rest), window, fill)

    with jax.named_scope(STAGE_GROW):
        return (slide(qrows, 0), slide(qfp, EMPTY), slide(qebits, 0),
                slide(qdepth, 0))


# Module-level and keyed by shapes and ``qalloc`` alone, not by the twin
# object: a fresh model object in a warm process finds them compiled.  A
# slide that keeps the buffers' size runs in place (donated); one into larger
# buffers cannot (jax 0.9.0 donates an input only to an output of its size,
# and warns of every other), so the old buffers live until the host drops
# them.
_slide_queue_grown = jax.jit(_slide_queue, static_argnames="qalloc")
_slide_queue_in_place = jax.jit(
    _slide_queue, static_argnames="qalloc", donate_argnums=(0, 1, 2, 3)
)


def _build_inject(tensor, cap: int, qcap: int, batch: int,
                  sym: bool, spill, mxu=None):
    """Jitted pending-injection program for the spill tier: insert one
    host-VERIFIED batch of novel ``(fp, row, parent, ebits, depth)``
    tuples into the hot table + queue, bump ``unique``/``tail``, and
    clear the pending count — the device half of pending resolution
    (``TpuChecker._resolve_pending``).  The insert dedups against the
    hot table exactly like a step insert, so a Bloom false positive
    whose fingerprint was meanwhile injected simply drops out.  Growth
    statuses mirror the step's; on a table overflow NOTHING is written
    and the host evicts-or-grows and retries."""
    _, pend_cap = spill
    probe_dot = bool(mxu is not None and mxu.probe)

    @jax.jit
    def inject_fn(carry, ifp, irows, ipar, iebt, idep, n):
        tail = carry.tail
        live = jnp.arange(pend_cap, dtype=jnp.int32) < n
        cfp = jnp.where(live, ifp, EMPTY)
        tfp, tpl, sel, n_new, tovf, _ = bucket_insert(
            carry.table_fp, carry.table_parent, cfp, ipar,
            window=min(batch, pend_cap), generation_order=sym,
            probe_dot=probe_dot,
        )
        qrows = jax.lax.dynamic_update_slice(
            carry.q_rows, irows[sel], (tail, jnp.int32(0))
        )
        qfp = jax.lax.dynamic_update_slice(carry.q_fp, cfp[sel], (tail,))
        qebits = jax.lax.dynamic_update_slice(
            carry.q_ebits, iebt[sel], (tail,)
        )
        qdepth = jax.lax.dynamic_update_slice(
            carry.q_depth, idep[sel], (tail,)
        )
        tail = tail + n_new
        unique = carry.unique + n_new.astype(jnp.int64)
        status = jnp.where(
            carry.status == jnp.int32(_STATUS_SPILL_SYNC),
            jnp.int32(_STATUS_OK), carry.status,
        )
        status = jnp.where(
            tovf | ((unique - carry.spill.base) * 4 > cap),
            jnp.int32(_STATUS_TABLE_FULL),
            jnp.where(
                tail > qcap, jnp.int32(_STATUS_QUEUE_FULL), status
            ),
        )
        out = carry.replace(
            table_fp=tfp, table_parent=tpl, q_rows=qrows, q_fp=qfp,
            q_ebits=qebits, q_depth=qdepth, tail=tail, unique=unique,
            status=status,
            spill=carry.spill.replace(pend_count=jnp.int32(0)),
        )
        return out, jnp.stack([n_new, tovf.astype(jnp.int32)])

    return inject_fn


def _aot_compile(run_fn, avals):
    """Compile the jitted run program ahead of time for the given carry
    signature.  The returned executable is the same program the lazy path
    would compile on first call (donation included) — kept as a
    module-level hook so tests can observe/instrument prewarm compiles."""
    return run_fn.lower(avals).compile()


class TpuChecker(WavefrontChecker):
    """Queue-based wavefront BFS on the default JAX device (TPU on hardware,
    CPU in tests).

    Requires the model to provide a tensor twin via ``model.tensor_model()``
    and to fingerprint states via the row encoding (``TensorBackedModel``),
    so host-side path reconstruction matches device fingerprints.

    ``capacity`` — hash-table slots (grown on demand, work preserved).
    ``batch`` — rows expanded per device step (``frontier_capacity`` is the
    backwards-compatible alias).  ``queue_capacity`` — queue high-water mark
    (default: ``capacity // 2``; grown/compacted on demand).
    ``cand`` — valid-candidate compaction budget per batch (default
    ``max(4 * batch, 4096)``; doubled on demand): the insert pipeline runs
    at this width instead of the fully padded ``batch * max_actions``,
    which is the engine's main latency lever on hardware.
    ``steps_per_call`` — device steps per host round-trip: the host syncs
    this often to refresh live counters and serve checkpoint requests.
    ``resume`` — a snapshot from :meth:`checkpoint` to continue from.
    """

    def __init__(
        self,
        options: CheckerBuilder,
        capacity: int = 1 << 17,
        frontier_capacity: Optional[int] = None,
        batch: Optional[int] = None,
        queue_capacity: Optional[int] = None,
        steps_per_call: int = 64,
        sync: bool = False,
        resume: Optional[dict] = None,
        cand: Optional[int] = None,
        spill_bloom_bits: Optional[int] = None,
        spill_dir: Optional[str] = None,
        spill_host_bytes: Optional[int] = None,
    ):
        self._cap = max(_pow2(capacity), 4 * SLOTS)
        # spill-tier knobs (docs/spill.md); consumed by _init_spill when
        # the builder armed the tier (CheckerBuilder.spill() / --spill /
        # STATERIGHT_TPU_SPILL=1, resolved in _init_common)
        self._spill_bloom_bits = spill_bloom_bits
        self._spill_dir = spill_dir
        self._spill_host_bytes = spill_host_bytes
        # checked execution mode (builder.checked() / --checked): checkify
        # instrumentation of the model kernels; see _build_engine
        self._checked = bool(getattr(options, "checked_mode", False))
        if batch is None:
            batch = frontier_capacity if frontier_capacity else 1 << 11
        self._batch = max(8, batch)
        self._cand = cand or max(4 * self._batch, 4096)
        self._qcap = queue_capacity or max(self._cap // 2, 4 * self._batch)
        self._steps = steps_per_call
        self._resume = resume
        self._live = (0, 0, 0)  # states, unique, maxdepth
        self._live_lock = threading.Lock()
        # (status, unique-at-boundary) per mid-run growth event; unique is
        # monotone across events — growth preserves work (tests pin this)
        self.growth_events: list = []
        self._init_common(options, sync)

    # -- run loop ------------------------------------------------------------

    def _engine_cache(self) -> dict:
        cache = getattr(self.tensor, "_run_cache", None)
        if cache is None:
            cache = {}
            self.tensor._run_cache = cache
        return cache

    def _engine_key(self, cap, qcap, batch, cand) -> tuple:
        # spill OFF leaves the key exactly the pre-spill tuple (and the
        # step jaxpr bit-identical): the engine cache — in-memory and the
        # persistent XLA cache both — is unkeyed by the feature's absence
        key = (cap, qcap, batch, cand, self._steps, self._target,
               self._symmetry is not None, self._checked,
               self._prededup, self._cartography, self._por)
        if self._spill:
            key = key + (("spill",) + self._spill_cfg)
        if self._mxu is not None:
            # same discipline: MXU off leaves the key exactly the
            # pre-MXU tuple (cache unkeyed by the feature's absence) —
            # and the key carries the EFFECTIVE config, so component
            # subsets that fall back to an identical program (no
            # coalesced kernel on this twin) share one cache entry
            # instead of paying a duplicate engine compile
            from ..ops.mxu import effective_mxu

            eff = effective_mxu(self.tensor, self._mxu)
            if eff is not None and (eff.coalesce or eff.probe):
                key = key + (eff.key(),)
        return key

    def _build(self, cap, qcap, batch, cand):
        return _build_engine(
            self.tensor, self._props, cap, qcap, batch, self._steps,
            self._target, sym=self._symmetry is not None, cand=cand,
            checked=self._checked, prededup=self._prededup,
            cartography=self._cartography,
            por=self._por_plan if self._por else None,
            spill=self._spill_cfg if self._spill else None,
            mxu=self._mxu, place=self._step_placement(),
        )

    def _step_placement(self):
        """Where the step program's values lie across devices (hook:
        ``partition.StepPlacement`` on a mesh); None on one device."""
        return None

    # -- memory-ledger hooks (telemetry/memory.py) ---------------------------

    def _avals(self, cap: int, qcap: int, batch: int) -> Carry:
        """Abstract carry of THIS engine's build at these capacities."""
        return carry_avals(
            self.tensor, len(self._props), cap, qcap, batch,
            self._checked, self._cartography, self._por,
            self._spill_cfg if self._spill else None,
        )

    def _place(self, avals: Carry) -> Optional[Carry]:
        """The sharding of each buffer of ``avals``; None on one device."""
        return None

    def _memory_spec_fn(self):
        """Analytic per-buffer model of THIS engine's carry: derived from
        its abstract signature (what prewarm compiles for), so the bytes
        reconcile exactly against the live buffers (pinned by test)."""
        from ..telemetry.memory import carry_specs

        batch = self._batch

        def spec_fn(caps):
            avals = self._avals(
                int(caps["cap"]),
                int(caps.get("qcap", max(int(caps["cap"]) // 2, 1))),
                int(caps.get("batch", batch)),
            )
            return carry_specs(avals, self._place(avals))

        return spec_fn

    def _memory_caps(self) -> dict:
        return {"cap": self._cap, "qcap": self._qcap, "batch": self._batch}

    def _roofline_cost_fn(self):
        """Analytic pipeline cost model at THIS engine's spawn
        capacities (``analysis/costmodel.wavefront_costs``, cached on
        the twin) — the roofline ledger's data source."""
        from ..analysis.costmodel import wavefront_costs

        tensor = self.tensor
        cap, qcap, batch = self._cap, self._qcap, self._batch
        cand, sym = self._cand, self._symmetry is not None
        mxu = self._mxu

        def cost_fn():
            return wavefront_costs(
                tensor, cap, qcap, batch, cand, sym=sym, mxu=mxu,
            )

        return cost_fn

    def _memory_extra(self) -> dict:
        return {"queue_capacity": self._qcap}

    def _bank_depth_lanes(self, qdepth, n: int, sign: int = 1) -> None:
        """Fold the depth lanes of ``qdepth[:n]`` into the cartography
        depth bank (``sign=-1`` un-banks) — the ONE definition of the
        banking rule shared by growth compaction, queue offload, and
        refill, so ``sum(depth_hist) == unique`` cannot silently break
        at one forgotten site.  No-op when cartography is off."""
        if not self._cartography or n <= 0:
            return
        from ..ops.cartography import DEPTH_BINS

        if self._cart_depth_base is None:
            self._cart_depth_base = np.zeros(DEPTH_BINS, np.int64)
        hist = depth_hist(qdepth, np.int32(n))
        if isinstance(qdepth, jax.Array) and self.flight_recorder is not None:
            self.flight_recorder.add_bytes(d2h=hist.nbytes)
        self._cart_depth_base += sign * hist

    def _sync_cartography(self, tail, *, states: int, unique: int) -> None:
        """Parse the cartography section of the packed stats vector (the
        part after the discovery fps) into the live snapshot, and hand it
        to the flight recorder when one is attached."""
        from ..ops.cartography import DEPTH_BINS, snapshot

        arity = max(self.tensor.max_actions, 1)
        p = max(len(self._props), 1)
        o = 0
        dh = np.asarray(tail[o:o + DEPTH_BINS]).astype(np.int64)
        if self._cart_depth_base is not None:
            # growth reclaimed queue prefixes: their banked depth lanes
            # complete the queue-derived histogram (see _grow)
            dh = dh + self._cart_depth_base
        o += DEPTH_BINS
        ah = tail[o:o + arity]
        o += arity
        pe = tail[o:o + p]
        o += p
        ph = tail[o:o + p]
        snap = snapshot(
            depth_hist=dh, action_hist=ah, prop_evals=pe, prop_hits=ph,
            prop_names=[pr.name for pr in self._props],
            states=states, unique=unique,
            por=self._live_por if self._por else None,
        )
        self._live_cart = snap
        if self.flight_recorder is not None:
            self.flight_recorder.set_cartography(snap)

    # -- spill tier (stateright_tpu/spill/; docs/spill.md) -------------------

    def _init_spill(self) -> None:
        """Arm the host/disk overflow tiers for this run; called from
        ``_init_common`` once the builder flag resolved true.  Everything
        here is host state — the device half is the carry tail the
        engine builder appends when ``spill`` is set."""
        import os as _os

        from ..spill import SpillStore
        from ..spill.bloom import MAX_BLOOM_BITS, MIN_BLOOM_BITS

        bits = self._spill_bloom_bits
        if not bits:
            env = _os.environ.get(
                "STATERIGHT_TPU_SPILL_BLOOM_BITS", ""
            ).strip()
            if env and not env.isdigit():
                import sys as _sys

                print(
                    "stateright-tpu: spill: ignoring malformed "
                    f"STATERIGHT_TPU_SPILL_BLOOM_BITS={env!r} (want "
                    "plain bits, e.g. 8388608); using the default",
                    file=_sys.stderr,
                )
            bits = int(env) if env.isdigit() else (1 << 23)
        bits = min(max(_pow2(int(bits)), MIN_BLOOM_BITS), MAX_BLOOM_BITS)
        m = self._batch * self.tensor.max_actions
        # pending capacity = FOUR expansion windows: the stop rule
        # (pend_count + m > pend_cap halts the block) then lets several
        # deferring batches run per host sync instead of forcing a
        # resolve round-trip after every one (post-eviction, nearly
        # every window defers something — one-window capacity collapsed
        # steps_per_call batching to 1), while the over-allocated buffer
        # (pend_cap + m) still never clamps a write; the queue's append
        # slack is widened to match the inject window (_qalloc)
        self._spill_cfg = (bits, 4 * m)
        self._spill_store = SpillStore(
            directory=self._spill_dir, host_budget=self._spill_host_bytes
        )
        self._spill_bloom_np = np.zeros(bits // 32, np.uint32)
        self._spill_qrows: list = []  # host FIFO of offloaded queue chunks
        self._spill_tally = {
            "evictions": 0, "resolved_dups": 0, "resolved_novel": 0,
            "queue_offloaded": 0, "queue_refilled": 0, "deferred": 0,
            "on_device": 0,
        }
        self._inject_cache: dict = {}

    def _spill_snapshot(self) -> dict:
        """Live spill-tier status (JSON-safe): tier bytes, Bloom load,
        deferral/resolution tallies — the block telemetry/report/watch/
        Explorer all read."""
        from ..spill import SPILL_V
        from ..spill.bloom import BLOOM_K, bloom_est_false_pos

        bits, pend_cap = self._spill_cfg
        store = self._spill_store
        t = self._spill_tally
        q_host = sum(int(c[1].shape[0]) for c in self._spill_qrows)
        return {
            "v": SPILL_V,
            "enabled": True,
            "evictions": t["evictions"],
            "spilled_fps": len(store),
            "host_bytes": store.host_bytes,
            "disk_bytes": store.disk_bytes,
            "index_bytes": store.index_bytes,
            "bloom_bits": bits,
            "bloom_k": BLOOM_K,
            "bloom_est_false_pos": round(
                bloom_est_false_pos(len(store), bits), 6
            ),
            "pend_cap": pend_cap,
            "deferred": t["deferred"],
            "on_device": t["on_device"],
            "resolved_dups": t["resolved_dups"],
            "resolved_novel": t["resolved_novel"],
            "queue_offloaded": t["queue_offloaded"],
            "queue_refilled": t["queue_refilled"],
            "queue_host_rows": q_host,
            **(
                {"degraded": True,
                 "degraded_reason": store.degraded_reason}
                if store.degraded else {}
            ),
        }

    def _refresh_spill(self) -> None:
        if self.flight_recorder is not None:
            self.flight_recorder.set_spill(self._spill_snapshot())
            if self._spill_store.degraded:
                # disk tier lost (ENOSPC/dead disk): the sticky
                # ``spill_degraded`` health transition, emitted once
                self.flight_recorder.set_spill_degraded()

    def spill_status(self) -> Optional[dict]:
        """Spill-tier status of this run, or None when ``spill()`` was
        never requested: evictions, per-tier bytes, Bloom parameters and
        estimated false-positive rate, deferral/resolution tallies."""
        if not getattr(self, "_spill", False):
            return None
        return self._spill_snapshot()

    def _spill_fits_transient(self, cur_caps: dict, new_caps: dict) -> bool:
        """Does the growth migration ``cur -> new`` (both carries live
        across the swap) fit the device budget?  No budget known — or no
        analytic model — means growth proceeds as ever (the tier only
        changes behavior where PR 7's ledger can prove the wall)."""
        from ..telemetry.memory import device_budget

        budget, _ = device_budget()
        if budget is None:
            return True
        cur = self._analytic_footprint_bytes(cur_caps)
        nxt = self._analytic_footprint_bytes(new_caps)
        if cur is None or nxt is None:
            return True
        return cur + nxt <= budget

    def _spill_should_evict(self, cap, qcap, batch) -> bool:
        """Evict instead of growing iff the NEXT table rung's migration
        transient (PR 7's ``next_rung.transient_bytes``) exceeds the
        device budget."""
        return not self._spill_fits_transient(
            {"cap": cap, "qcap": qcap, "batch": batch},
            {"cap": cap * 2, "qcap": qcap, "batch": batch},
        )

    def _evict_hot_table(self, carry: Carry) -> Carry:
        """Sweep the hot table (on the host) into the host tier at a
        growth boundary: append every occupied ``(fp, parent)`` to the
        spill store, fold the evicted fingerprints into the Bloom mirror,
        and return the carry with the table cleared and the spill tail's
        bloom/base refreshed.
        Exactness: evicted fingerprints remain reachable through the
        Bloom -> pending -> host-index path, and their parents merge back
        at trace reconstruction (``_parents``)."""
        from ..spill import SPILL_V
        from ..spill.bloom import bloom_est_false_pos, bloom_set_np

        tfp, tpl = carry.table_fp, carry.table_parent
        occ = tfp != np.uint64(EMPTY)
        fps, pars = tfp[occ], tpl[occ]
        self._spill_store.append(fps, pars)
        bloom_set_np(self._spill_bloom_np, fps)
        carry = carry.replace(
            table_fp=np.full(tfp.shape, EMPTY, np.uint64),
            table_parent=np.zeros(tpl.shape, np.uint64),
            spill=carry.spill.replace(
                bloom=jnp.asarray(self._spill_bloom_np),
                base=jnp.int64(len(self._spill_store)),
            ),
        )
        self._spill_tally["evictions"] += 1
        rec = self.flight_recorder
        if rec is not None:
            bits, _ = self._spill_cfg
            rec.add("spill_evictions")
            rec.record(
                "spill", v=SPILL_V, event="evict",
                evicted=int(fps.size),
                spilled_fps=len(self._spill_store),
                host_bytes=self._spill_store.host_bytes,
                disk_bytes=self._spill_store.disk_bytes,
                bloom_bits=bits,
                bloom_est_false_pos=round(
                    bloom_est_false_pos(len(self._spill_store), bits), 6
                ),
            )
            self._refresh_spill()
        return carry

    def _inject(self, cap, qcap, batch):
        """The compiled pending-injection program for these capacities
        (rebuilt per growth rung, like the engine)."""
        key = (cap, qcap, batch)
        if self._mxu is not None and self._mxu.probe:
            # the inject program depends on the probe component only
            # (off leaves the key exactly the pre-MXU tuple — the
            # _engine_key discipline)
            key = key + ("mxu-probe",)
        fn = self._inject_cache.get(key)
        if fn is None:
            fn = _build_inject(
                self.tensor, cap, qcap, batch,
                self._symmetry is not None, self._spill_cfg, mxu=self._mxu,
            )
            self._inject_cache[key] = fn
        return fn

    def _resolve_pending(self, carry, cap, qcap, batch, cand):
        """Resolve the device pending buffer against the host index:
        fingerprints the store knows are duplicates (Bloom true
        positives) and drop out; the rest (false positives) are novel
        and re-enter the hot table + queue through the jitted inject
        program.  A hot table too full to take them evicts-or-grows and
        retries — nothing is ever lost.  Returns ``(cap, qcap, carry)``.
        """
        from ..spill import SPILL_V

        sp = carry.spill
        bits, pend_cap = self._spill_cfg
        n = int(np.asarray(sp.pend_count))
        if n == 0:
            return cap, qcap, carry
        pfp = np.asarray(sp.pend_fp)[:n]
        prows = np.asarray(sp.pend_rows)[:n]
        ppar = np.asarray(sp.pend_parent)[:n]
        pebt = np.asarray(sp.pend_ebits)[:n]
        pdep = np.asarray(sp.pend_depth)[:n]
        rec = self.flight_recorder
        if rec is not None:
            rec.add_bytes(d2h=pfp.nbytes + prows.nbytes + ppar.nbytes
                          + pebt.nbytes + pdep.nbytes)
        valid = pfp != np.uint64(EMPTY)
        pfp, prows = pfp[valid], prows[valid]
        ppar, pebt, pdep = ppar[valid], pebt[valid], pdep[valid]
        # intra-batch dedup, keep-FIRST occurrence: the earliest
        # generation wins the parent/ebits/depth payload, exactly the
        # lane the insert's stable sort would have kept
        _, first = np.unique(pfp, return_index=True)
        first.sort()
        seen = self._spill_store.contains(pfp[first])
        novel_idx = first[~seen]
        k = int(novel_idx.size)
        dups = n - k
        injected = 0
        if k == 0:
            # nothing to inject: clear the count with cheap eager updates
            carry = carry.replace(spill=sp.replace(pend_count=jnp.int32(0)))
            if int(np.asarray(carry.status)) == _STATUS_SPILL_SYNC:
                carry = carry.replace(status=jnp.int32(_STATUS_OK))
        else:
            nfp = pfp[novel_idx]
            nrows = prows[novel_idx]
            npar = ppar[novel_idx]
            nebt = pebt[novel_idx]
            ndep = pdep[novel_idx]
            while True:
                ifp = np.full(pend_cap, EMPTY, np.uint64)
                irows = np.zeros((pend_cap, self.tensor.width), np.uint64)
                ipar = np.zeros(pend_cap, np.uint64)
                iebt = np.zeros(pend_cap, np.uint32)
                idep = np.zeros(pend_cap, np.uint32)
                ifp[:k] = nfp
                irows[:k] = nrows
                ipar[:k] = npar
                iebt[:k] = nebt
                idep[:k] = ndep
                args = tuple(jnp.asarray(a) for a in
                             (ifp, irows, ipar, iebt, idep))
                carry, io = self._inject(cap, qcap, batch)(
                    carry, *args, jnp.int32(k)
                )
                io = np.asarray(io)
                if int(io[1]) == 0:
                    # tally what actually ENTERED the hot table: the
                    # inject's dedup drops hot-resident Bloom false
                    # positives, which count as duplicates, not novel
                    injected = int(io[0])
                    dups += k - injected
                    break
                # the hot table cannot take the batch: evict under budget
                # pressure, else grow (the decision the step boundary
                # makes), rebuild the inject program for the new rung, retry
                carry, cap, qcap, _, _ = self._grow(
                    carry, _STATUS_TABLE_FULL, cap, qcap, batch, cand
                )
                # the boundary may have EVICTED: pending fps that were
                # hot-resident (Bloom false positives the inject's
                # hot-table dedup would have dropped) are now in the
                # store, and retrying the original batch against the
                # emptied table would insert them a SECOND time —
                # re-filter against the store before every retry
                seen2 = self._spill_store.contains(nfp)
                if seen2.any():
                    keep = ~seen2
                    dups += int(seen2.sum())
                    nfp, nrows = nfp[keep], nrows[keep]
                    npar, nebt, ndep = npar[keep], nebt[keep], ndep[keep]
                    k = int(nfp.size)
                    if k == 0:
                        # nothing left to inject; the boundary already
                        # cleared the status and the inject that
                        # overflowed cleared the pending count
                        break
        self._spill_tally["resolved_dups"] += dups
        self._spill_tally["resolved_novel"] += injected
        if rec is not None:
            rec.record(
                "spill", v=SPILL_V, event="resolve",
                pending=n, dups=dups, novel=injected,
            )
            self._refresh_spill()
        return cap, qcap, carry

    def _offload_queue_tail(self, queue: dict, pending: int,
                            qcap: int) -> int:
        """The queue outgrew a budget-blocked doubling: move the tail
        excess (the rows furthest from being popped) to the host FIFO;
        they re-enter via ``_queue_refill`` when the device queue drains.
        ``queue`` holds the four (host) buffers AFTER the consumed-prefix
        compaction, so live rows sit at ``[0:pending]``; returns how many
        stay."""
        from ..spill import SPILL_V

        keep = max(qcap // 2, 1)
        if pending <= keep:
            return pending
        chunk = tuple(
            np.asarray(queue[k][keep:pending]).copy() for k in QUEUE_FIELDS
        )
        self._spill_qrows.append(chunk)
        # the offloaded rows leave qdepth[:tail], which the queue-derived
        # depth histogram is computed from: bank their lanes (un-banked
        # at refill, where they re-enter) so sum(depth_hist) == unique
        # holds at every sync — including a run that ends (target hit,
        # all props discovered) with rows still in the host FIFO
        self._bank_depth_lanes(chunk[3], int(chunk[3].shape[0]))
        moved = pending - keep
        self._spill_tally["queue_offloaded"] += moved
        rec = self.flight_recorder
        if rec is not None:
            rec.record(
                "spill", v=SPILL_V, event="queue_offload", rows=moved,
                host_rows=sum(int(c[1].shape[0])
                              for c in self._spill_qrows),
            )
            self._refresh_spill()
        return keep

    def _queue_refill(self, carry, cap, qcap, batch):
        """The device queue drained while host-offloaded frontier rows
        remain: compact, append up to the high-water mark's worth from
        the host FIFO, and continue.  The carry crosses to the host here
        — rare by construction (once per ``qcap`` drained rows)."""
        from ..spill import SPILL_V

        carry = carry.pulled(QUEUE_FIELDS + ("head", "tail"))
        head, tail = int(carry.head), int(carry.tail)
        self._bank_depth_lanes(carry.q_depth, head)
        live = [getattr(carry, k)[head:tail] for k in QUEUE_FIELDS]
        pending = tail - head
        room = qcap - pending
        taken = [[], [], [], []]
        moved = 0
        while self._spill_qrows and room > 0:
            chunk = self._spill_qrows[0]
            cn = int(chunk[1].shape[0])
            if cn <= room:
                self._spill_qrows.pop(0)
                take = chunk
            else:
                take = tuple(a[:room] for a in chunk)
                self._spill_qrows[0] = tuple(a[room:] for a in chunk)
            for j in range(4):
                taken[j].append(take[j])
            cn = int(take[1].shape[0])
            # un-bank the refilled rows' depth lanes: they re-enter
            # qdepth[:tail], where the histogram derivation counts them
            # (the offload banked them — see _offload_queue_tail)
            self._bank_depth_lanes(take[3], cn, sign=-1)
            moved += cn
            room -= cn
        carry = repad_queue(
            carry.replace(
                head=np.int32(0), tail=np.int32(pending + moved),
                **{k: np.concatenate([live[j]] + taken[j])
                   for j, k in enumerate(QUEUE_FIELDS)},
            ),
            self._qalloc(qcap, batch),
        )
        self._spill_tally["queue_refilled"] += moved
        rec = self.flight_recorder
        if rec is not None:
            rec.record(
                "spill", v=SPILL_V, event="queue_refill", rows=moved,
                host_rows=sum(int(c[1].shape[0])
                              for c in self._spill_qrows),
            )
            self._refresh_spill()
        return carry.pushed(QUEUE_FIELDS + ("head", "tail"))

    def _restore_spill_host(self, snap: dict) -> None:
        """Restore the HOST half of the spill tier from the snapshot
        manifest (store, Bloom mirror, offloaded-queue FIFO, config) —
        called from ``_snapshot_to_carry`` BEFORE any growth handling,
        which reads ``len(self._spill_store)`` as the spill base."""
        from ..spill.bloom import MAX_BLOOM_BITS, bloom_set_np

        if "spill_bloom_bits" in snap:
            bits = min(_pow2(int(snap["spill_bloom_bits"])), MAX_BLOOM_BITS)
            if bits != self._spill_cfg[0]:
                self._spill_cfg = (bits, self._spill_cfg[1])
                self._spill_bloom_np = np.zeros(bits // 32, np.uint32)
        # batch travels with the snapshot and governs the window size
        # (keep the four-window pending sizing of _init_spill)
        m = self._batch * self.tensor.max_actions
        self._spill_cfg = (self._spill_cfg[0], 4 * m)
        f = snap.get("spill_fp")
        if f is not None:
            self._spill_store.append(
                np.asarray(f, np.uint64),
                np.asarray(snap["spill_parent"], np.uint64),
            )
            bloom_set_np(self._spill_bloom_np, np.asarray(f, np.uint64))
        if "spill_q_fp" in snap:
            self._spill_qrows.append(tuple(
                np.asarray(snap[k])
                for k in ("spill_q_rows", "spill_q_fp", "spill_q_ebits",
                          "spill_q_depth")
            ))

    def _spill_resume_tail(self, snap: dict) -> SpillTail:
        """Rebuild the spill CARRY tail at resume (host state already
        restored by ``_restore_spill_host``): the Bloom + base from the
        restored store, pending from the snapshot's mid-resolution
        buffer (if the checkpoint landed on a growth boundary with
        candidates still deferred)."""
        bits, pend_cap = self._spill_cfg
        m = self._batch * self.tensor.max_actions
        palloc = pend_cap + m
        width = self.tensor.width
        pfp = np.full(palloc, EMPTY, np.uint64)
        prows = np.zeros((palloc, width), np.uint64)
        ppar = np.zeros(palloc, np.uint64)
        pebt = np.zeros(palloc, np.uint32)
        pdep = np.zeros(palloc, np.uint32)
        pn = 0
        if "spill_pend_fp" in snap:
            pf = np.asarray(snap["spill_pend_fp"], np.uint64)
            pn = min(int(pf.size), pend_cap)
            pfp[:pn] = pf[:pn]
            prows[:pn] = np.asarray(snap["spill_pend_rows"])[:pn]
            ppar[:pn] = np.asarray(snap["spill_pend_parent"])[:pn]
            pebt[:pn] = np.asarray(snap["spill_pend_ebits"])[:pn]
            pdep[:pn] = np.asarray(snap["spill_pend_depth"])[:pn]
        return SpillTail(
            jnp.asarray(self._spill_bloom_np),
            jnp.int64(len(self._spill_store)),
            jnp.asarray(pfp), jnp.asarray(prows), jnp.asarray(ppar),
            jnp.asarray(pebt), jnp.asarray(pdep), jnp.int32(pn),
            jnp.zeros((2,), jnp.int64),
        )

    def _spilled(self) -> bool:
        return getattr(self, "_spill", False) and len(self._spill_store) > 0

    def _host_parents(self, tfp, tpl) -> dict:
        """Trace reconstruction merges every tier: host/disk-resident
        parents first, then the hot table's (the sets are disjoint —
        eviction removes what it spills)."""
        hot = super()._host_parents(tfp, tpl)
        if not self._spilled():
            return hot
        parents: dict = {}
        for fps, pars in self._spill_store.iter_segments():
            parents.update(zip(fps.tolist(), pars.tolist()))
        parents.update(hot)
        return parents

    def _engine(self, cap, qcap, batch, cand, kind: str = "growth"):
        """The compiled engine for these capacities, through (in order) the
        in-memory compiled-run cache on the tensor twin, the background
        prewarmer (growth rungs compiled ahead of time), or a cold build.
        Compile events record which path served the rung and how long the
        run actually waited for it (docs/perf.md attribution)."""
        cache = self._engine_cache()
        key = self._engine_key(cap, qcap, batch, cand)
        eng = cache.get(key)
        rec = self.flight_recorder
        fresh_acquire = key != getattr(self, "_last_engine_key", None)
        if rec is not None and fresh_acquire:
            # compiled-run cache accounting: a miss means a fresh trace +
            # XLA compile is about to be paid (growth events recompile).
            # Only counted when the engine is (re)ACQUIRED — the run loop
            # re-fetches run_fn every sync, which must not inflate hits.
            rec.add(
                "compile_cache_hits" if eng is not None
                else "compile_cache_misses"
            )
        self._last_engine_key = key
        if eng is not None and not fresh_acquire:
            return eng  # the run loop re-fetching the engine it holds
        # host seam span, on a (re)acquire only.  ``source`` is what is
        # known HERE: the lazy path's compile (or persistent-cache
        # retrieval) is paid inside the next device_call's ``dispatch``
        with tel_span(
            "engine_acquire", rec, parent=self._run_span_ctx,
            rung=kind, cap=cap,
        ) as acq:
            if eng is not None:
                source = "in-memory"
            else:
                eng, source = self._acquire_engine(
                    cache, key, cap, qcap, batch, cand, kind, acq.ctx
                )
            acq.set(source=source)
        return eng

    def _acquire_engine(self, cache, key, cap, qcap, batch, cand,
                        kind: str, span_ctx) -> tuple:
        """``(engine, source)`` for a key the in-memory cache lacks: the
        prewarmer's finished rung, else a build (lazy; ahead of time with
        the memory ledger on, where a persistent-cache hit shows)."""
        rec = self.flight_recorder
        if self._prewarmer is not None:
            try:
                taken = self._prewarmer.take(key)
            except Exception:  # noqa: BLE001 - a failed background compile
                taken = None  # falls back to the cold path below
            if taken is not None:
                eng, waited, was_ready, job = taken
                cache[key] = eng
                self._pending_compile_rec = None
                # time spent blocked on the in-flight background compile is
                # compile-stall wall time; a ready rung costs ~0 here (the
                # growth-stall elision the prewarm exists for)
                self._stage("compile", waited)
                if rec is not None:
                    ev = rec.record(
                        "compile", cap=cap, qcap=qcap, batch=batch,
                        cand=cand, rung=kind, source="prewarm",
                        cache_hit=True, prewarm_ready=was_ready,
                        duration=round(waited, 6),
                        build_secs=round(job.compile_secs, 6),
                    )
                    rec.add("prewarm_consumed")
                    if self._mem_ledger is not None:
                        # the prewarmed executable is at hand: capture its
                        # compile-time memory analysis onto the event
                        mem = self._mem_ledger.attach_exec(eng[1])
                        if mem:
                            rec.amend(ev, memory=mem)
                self._schedule_prewarm(cap, qcap, batch, cand)
                return eng, "prewarm"
        source = "fresh"
        if rec is not None:
            # duration/cache_hit are amended by the run loop after the
            # first device call actually pays the (lazy) compile
            self._pending_compile_rec = rec.record(
                "compile", cap=cap, qcap=qcap, batch=batch, cand=cand,
                rung=kind, source="fresh", cache_hit=False, duration=0.0,
            )
        eng = self._build(cap, qcap, batch, cand)
        if self._compiles_ahead():
            # With the ledger on, the fresh path compiles the run program
            # AHEAD OF TIME (the same executable the lazy path would
            # build — the prewarm contract, pinned by its tests) so the
            # executable handle exists and its compile-time memory
            # analysis can be captured; the wait is paid HERE instead of
            # at the first device call, and lands on the same compile
            # event via amend() (init_fn's lazy compile still accumulates
            # there afterwards).  Persistent-cache hits flow through this
            # path too and are detected by the monitoring delta.
            watch = CompileWatch() if rec is not None else None
            t0 = time.monotonic()
            try:
                exe = _aot_compile(eng[1], self._avals(cap, qcap, batch))
            except Exception:  # noqa: BLE001 - fall back to the lazy path;
                exe = None  # accounting must never break a run
            build = time.monotonic() - t0
            if rec is not None:
                # what was lowered and loaded, under the ``engine_acquire``
                # span that paid for it; the rest of the build is tracing
                d = watch.delta()
                comp = min(self._record_programs(span_ctx, d["events"]), build)
                self._stage("compile", comp)
                self._stage("trace", build - comp)
            if exe is not None:
                eng = (eng[0], exe)
                mem = (self._mem_ledger.attach_exec(exe)
                       if self._mem_ledger is not None else None)
                if rec is not None and self._pending_compile_rec is not None:
                    hit = d["persistent_hits"] > 0
                    source = "persistent" if hit else "fresh"
                    fields = dict(
                        duration=round(comp, 6), cache_hit=hit,
                        source=source,
                    )
                    if mem:
                        fields["memory"] = mem
                    rec.amend(self._pending_compile_rec, **fields)
        cache[key] = eng
        return eng, source

    def _compiles_ahead(self) -> bool:
        """Whether a fresh engine's run program is compiled when it is
        built, the executable at hand, rather than at its first call."""
        return self._mem_ledger is not None

    def _maybe_schedule_prewarm(self, cap, qcap, batch, cand,
                                unique: int, tail: int) -> None:
        """Threshold gate for prediction scheduling: background compiles
        start only once a growth trigger is actually approaching (table
        at 1/16 load vs the 1/4 trigger; queue tail at half the
        high-water mark) — a pre-sized run that never grows never pays a
        single background compile, which keeps prewarm's overhead at
        exactly zero for the runs that don't need it."""
        if self._prewarmer is None:
            return
        if unique * 16 > cap or tail * 2 > qcap:
            self._schedule_prewarm(cap, qcap, batch, cand)

    def _schedule_prewarm(self, cap, qcap, batch, cand) -> None:
        """Queue ahead-of-time compiles for the growth ladder's predicted
        next rungs: the table doubling, the queue doubling, and the
        candidate-budget doubling (``_grow`` / the cand-full replay only
        ever move capacities along these edges).  Called from the
        threshold gate above and — growth momentum — after a prewarmed
        rung is consumed.  A wrong prediction costs one wasted background
        compile; a right one turns the next growth boundary's cold
        compile into an instant swap."""
        if self._prewarmer is None:
            return
        cache = self._engine_cache()
        arity = self.tensor.max_actions
        rungs = [(cap * 2, qcap, cand), (cap, qcap * 2, cand)]
        cand2 = min(cand * 2, batch * arity)
        if cand2 != cand:
            nc = cap
            while cand2 * 4 > nc:  # the cand-full replay pre-sizes the table
                nc *= 2
            rungs.append((nc, qcap, cand2))
        keys = [self._engine_key(nc_, nq_, batch, ncd_)
                for nc_, nq_, ncd_ in rungs]
        # predictions from superseded capacities are dead rungs: cancel
        # queued ones (they would delay the useful compile on the single
        # worker) and release finished executables nobody can consume
        self._prewarmer.prune(keys)
        for (ncap, nqcap, ncand), key in zip(rungs, keys):
            if key in cache or self._prewarmer.scheduled(key):
                continue
            avals = self._avals(ncap, nqcap, batch)

            def build(ncap=ncap, nqcap=nqcap, ncand=ncand, avals=avals):
                init_fn, run_fn = self._build(ncap, nqcap, batch, ncand)
                return init_fn, _aot_compile(run_fn, avals)
            if self._prewarmer.schedule(key, build):
                if self.flight_recorder is not None:
                    self.flight_recorder.add("prewarm_scheduled")

    def _raise_on_checked_error(self, carry, head: int, tail: int,
                                batch: int) -> None:
        """Checked mode: if the carry's failure flag is set, localize the
        offending row in the last popped batch window (per-row checkified
        replay reconstructs the full check message) and raise
        CheckedExecutionError."""
        if not bool(np.asarray(carry.err)):
            return
        from ..analysis.sanitizer import localize_checked_failure

        qrows = np.asarray(carry.q_rows)
        # the failing batch sits at [head - batch, head) after a normal
        # pop, or [head, head + batch) when an overflow replay kept the
        # cursor — scan the union, clipped at tail (rows past tail are
        # unwritten padding the run never popped); clean rows re-check
        # clean
        lo = max(0, head - batch)
        hi = min(qrows.shape[0], max(head, tail))
        hi = min(hi, head + batch)
        localize_checked_failure(self.tensor, qrows[lo:hi])

    def _carry_to_snapshot(self, carry, cap, qcap, cand=None) -> dict:
        # under the run span while it is open; after it (the final
        # snapshot) a parentless span of the same trace
        with tel_span(
            "checkpoint.pull", self.flight_recorder,
            parent=None if self._done.is_set() else self._run_span_ctx,
            trace_id=self._trace_id,
        ):
            snap = dict(zip(SNAPSHOT_KEYS, carry.pulled().base()))
        snap["cap"], snap["qcap"], snap["batch"] = cap, qcap, self._batch
        # self-tuned budget survives resume.  The run loop passes its LIVE
        # cand: self._cand is only written back when the run ends, so a
        # checkpoint taken after a mid-run _STATUS_CAND_FULL doubling would
        # otherwise store the stale pre-growth budget and resume would
        # replay the growth (an extra engine recompile).
        snap["cand"] = self._cand if cand is None else cand
        snap["width"] = self.tensor.width
        snap["engine"] = self._engine_tag
        snap["model_sig"] = self._model_sig()
        # run lineage (docs/telemetry.md "Comparing runs"): the manifest
        # carries this run's id, so a resumed run records it as
        # parent_run_id and the run registry links kill+resume chains
        snap["run_id"] = self.run_id
        # snapshot manifest (telemetry/memory.py): the analytic byte
        # footprint at these capacities travels with the snapshot, so a
        # resume on a smaller device can warn BEFORE compiling
        # (_check_snapshot_sig -> snapshot_fits_guard)
        fb = self._analytic_footprint_bytes(
            {"cap": cap, "qcap": qcap, "batch": self._batch}
        )
        if fb is not None:
            snap["footprint_bytes"] = np.int64(fb)
        if self._cart_depth_base is not None:
            # depth lanes banked by growth compactions (_grow): without
            # them a resumed histogram forgets every state popped before
            # a pre-snapshot growth, breaking sum(depth_hist) == unique
            snap["cart_depth_base"] = self._cart_depth_base.copy()
        if getattr(self, "_spill", False):
            # the snapshot manifest carries the HOST/DISK tier contents
            # (and any in-flight pending/offloaded rows) so a resumed run
            # reconstructs the whole tiered visited set; footprint_bytes
            # above stays HOT-TIER-ONLY — spill_* keys are host-resident
            # and snapshot_fits_guard must not count them against HBM
            snap["spill_bloom_bits"] = np.int64(self._spill_cfg[0])
            snap["spill_base"] = np.int64(len(self._spill_store))
            f, p = self._spill_store.to_arrays()
            if f.size:
                snap["spill_fp"], snap["spill_parent"] = f, p
            if self._spill_qrows:
                for j, k in enumerate(
                    ("spill_q_rows", "spill_q_fp", "spill_q_ebits",
                     "spill_q_depth")
                ):
                    snap[k] = np.concatenate(
                        [c[j] for c in self._spill_qrows]
                    )
            sp = carry.spill
            pn = int(np.asarray(sp.pend_count))
            if pn > 0:
                for k in ("fp", "rows", "parent", "ebits", "depth"):
                    snap[f"spill_pend_{k}"] = np.asarray(
                        getattr(sp, f"pend_{k}"))[:pn]
        return snap

    def _pre_run_validate(self) -> None:
        if self._resume is not None:
            self._check_snapshot_sig(self._resume)

    def _qalloc(self, qcap: int, batch: int) -> int:
        """Queue allocation of THIS engine's build for these capacities."""
        return queue_alloc(
            qcap, batch * self.tensor.max_actions, self._por,
            self._spill_cfg if self._spill else None,
        )

    def _snapshot_to_carry(self, snap: dict):
        """``(cap, qcap, carry)`` of a snapshot: its thirteen buffers by
        name, the queue re-padded to this build's allocation, and every
        tail seeded anew - the failure flag clear, the tallies at zero
        (totals keep counting; the depth histogram, queue-derived, comes
        back COMPLETE, since the snapshot kept the queue), ``por.boost``
        armed (a resume IS a boundary: one fully expanded batch), and the
        spill tail rebuilt from the snapshot's host tier, whose pending
        buffer a boundary checkpoint can carry."""
        self._check_snapshot_sig(snap)
        cap = int(snap["cap"])
        qcap = int(snap["qcap"])
        self._batch = int(snap.get("batch", self._batch))
        self._cand = int(snap.get("cand", self._cand))
        if self._spill:
            # BEFORE any boundary growth below: _grow reads the restored
            # store's length as the spill base (hot occupancy)
            self._restore_spill_host(snap)
        qalloc = self._qalloc(qcap, self._batch)
        base = snap.get("cart_depth_base")
        if base is not None:
            self._cart_depth_base = np.asarray(base, np.int64).copy()
        tails = fresh_tails(
            self._avals(cap, qcap, self._batch).replace(spill=None)
        )
        if self._por:
            tails["por"] = tails["por"].replace(boost=jnp.int32(1))
        if self._spill:
            tails["spill"] = self._spill_resume_tail(snap)
        carry = Carry(*(np.asarray(snap[k]) for k in SNAPSHOT_KEYS), **tails)
        # snapshots may have been taken at a different qalloc; re-pad
        return cap, qcap, repad_queue(carry, qalloc).pushed()

    def _grows_on_device(self, carry: Carry) -> bool:
        """Whether a growth event transforms ``carry`` where it lies or on
        the host - by what the engine observes, not by a knob.  The spill
        tier works on a carry that is on the host by design (its eviction,
        its queue offload and its transient forecast); and the mesh engine
        runs this loop over a carry sharded by bucket range and by queue
        row range - ONE queue, one head and one tail, replicated scalars -
        whose growth programs nobody has placed yet (``bucket_split`` and
        ``_slide_queue`` move rows across the shards' ranges)."""
        return (
            not self._spill
            and len(carry.table_fp.sharding.device_set) == 1
        )

    def _grow(self, carry: Carry, status: int, cap: int, qcap: int,
              batch: int, cand: int, at=None, parent=None):
        """Grow whatever is (near) full: ``(carry, cap, qcap, cand,
        occupancy)``, ``occupancy`` the new table's per-bucket histogram
        where a split on the device left one (still there; else None).
        ``at`` is ``(head, tail, unique)`` where the caller holds them
        (the sync that found the status), ``parent`` the ``grow`` span
        the phases here are children of.

        One decision.  A full candidate budget doubles ``cand`` - an
        engine parameter, not a carry buffer; the insert wrote nothing,
        so only the status word is cleared unless the table must follow
        (``cap >= 4 * cand``, the engine's actual precondition - NOT the
        padded ``4 * batch * arity``, a width the candidate compaction
        exists to avoid paying for).  Otherwise table and queue are both
        re-checked whichever status fired: they can trip in the same
        batch, and a ``tail`` left past the high-water mark would let the
        next append clamp its window onto unexpanded rows.  The spill
        tier reads HOT occupancy (``unique - spilled``), evicts the hot
        table INSTEAD of growing it where the next rung's migration does
        not fit the device budget (the cleared table satisfies the
        trigger at the same capacity), and offloads the queue's tail
        excess to the host FIFO where a doubling does not.

        Two executors, chosen by :meth:`_grows_on_device`: where the carry
        lies, ``ops/buckets.bucket_split`` and :func:`_slide_queue`
        (``grow.queue`` / ``grow.rehash`` wrap their dispatch - the device
        time is the trace's ``sr.grow`` stage and shows in the next
        ``wait`` - ``grow.pull`` what cartography banks of the popped
        prefix, ``grow.push`` the three scalars rewritten); or the base
        pulled, re-hashed and compacted in numpy and uploaded, bit for
        bit the same carry (``tests/test_growth_on_device.py``).  The
        tails stay where they are; ``por.boost`` is armed (growth is a
        boundary: one fully expanded batch)."""
        rec = self.flight_recorder
        parent = parent or self._run_span_ctx
        head, tail, unique = at or (
            int(np.asarray(v)) for v in (carry.head, carry.tail, carry.unique)
        )
        if status == _STATUS_CAND_FULL:
            cand = min(cand * 2, batch * self.tensor.max_actions)
        if carry.por is not None:
            carry = carry.replace(por=carry.por.replace(boost=jnp.int32(1)))
        if status == _STATUS_CAND_FULL and cand * 4 <= cap:
            return (carry.replace(status=jnp.int32(_STATUS_OK)), cap, qcap,
                    cand, None)
        on_device = self._grows_on_device(carry)
        with tel_span("grow.pull", rec, parent=parent):
            if not on_device:
                carry = carry.pulled()
            # the transforms drop the consumed queue prefix: bank its depth
            # lanes first, or the queue-derived histogram
            # (ops/cartography.queue_depth_hist) would forget every state
            # popped before this growth - counted where they lie
            self._bank_depth_lanes(carry.q_depth, head)
        if not on_device:
            if rec is not None:
                # the base just crossed to the host (and goes back after
                # growth) - price it, and take the free occupancy sample
                # growth boundaries offer
                rec.add_bytes(d2h=_array_bytes(carry))
                self._telemetry_occupancy(
                    carry.table_fp, at="growth", transferred=False
                )
            if (
                self._spill
                and status == _STATUS_TABLE_FULL
                and self._spill_should_evict(cap, qcap, batch)
            ):
                carry = self._evict_hot_table(carry)
                status = _STATUS_OK
        spilled = len(self._spill_store) if self._spill else 0
        old_cap, cap = cap, _grown_cap(unique - spilled, cap, cand, status)
        pending, offload = tail - head, False
        while pending * 2 > qcap and not offload:
            offload = self._spill and not self._spill_fits_transient(
                {"cap": cap, "qcap": qcap, "batch": batch},
                {"cap": cap, "qcap": qcap * 2, "batch": batch},
            )
            if not offload:
                qcap *= 2
        qalloc = self._qalloc(qcap, batch)
        occupancy = None
        if on_device:
            # the queue before the table: a slide's temporaries are the u32
            # planes of what it reads and writes (up to 1.4 x the queue), so
            # it runs while the table is still the small one
            with tel_span("grow.queue", rec, parent=parent):
                slide = (
                    _slide_queue_in_place if carry.q_rows.shape[0] == qalloc
                    else _slide_queue_grown
                )
                carry = carry.replace(**dict(zip(QUEUE_FIELDS, slide(
                    *(getattr(carry, k) for k in QUEUE_FIELDS),
                    carry.head, carry.tail, qalloc=qalloc,
                ))))
            if cap != old_cap:
                with tel_span("grow.rehash", rec, parent=parent, cap=cap):
                    tfp, tpl, occupancy = bucket_split(
                        carry.table_fp, carry.table_parent,
                        new_nbuckets=cap // SLOTS,
                    )
                carry = carry.replace(table_fp=tfp, table_parent=tpl)
        else:
            if cap != old_cap:
                with tel_span("grow.rehash", rec, parent=parent, cap=cap):
                    tfp, tpl = host_bucket_rehash(
                        carry.table_fp, carry.table_parent, cap // SLOTS
                    )
                carry = carry.replace(table_fp=tfp, table_parent=tpl)
            with tel_span("grow.queue", rec, parent=parent):
                # reclaim the consumed prefix, then pad to the allocation
                queue = {
                    k: getattr(carry, k)[head:tail].copy()
                    for k in QUEUE_FIELDS
                }
                if offload:
                    # the frontier's tail excess moves to the host FIFO
                    # (re-injected by _queue_refill when the device queue
                    # drains)
                    pending = self._offload_queue_tail(queue, pending, qcap)
                carry = repad_queue(carry.replace(**queue), qalloc)
        carry = carry.replace(
            head=np.int32(0), tail=np.int32(pending),
            status=np.int32(_STATUS_OK),
        )
        if rec is not None:
            rec.add_bytes(h2d=12 if on_device else _array_bytes(carry))
        # no block_until_ready here: what an upload leaves in flight shows
        # in the next device_call's wait
        with tel_span("grow.push", rec, parent=parent):
            carry = carry.pushed(
                ("head", "tail", "status") if on_device else None
            )
        return carry, cap, qcap, cand, occupancy

    def _growth_event(self, carry: Carry, st, cap: int, qcap: int,
                      batch: int, cand: int):
        """One growth event of the run loop, found by the sync that read
        ``st``: :meth:`_grow` under a ``grow`` span (its phases the span's
        children - on the recorder's clock and, as ``sr/grow*``, the
        profiler's), the ``growth`` record with what the event moved
        between host and device, and the ``growth`` stage's seconds."""
        rec = self.flight_recorder
        status, unique = st.status, st.unique
        # chaos seam: a growth boundary is where device OOM strikes in the
        # wild (the migration transient) — the chaos suite injects
        # RESOURCE_EXHAUSTED exactly here
        faults.fire("growth", recorder=rec, status=status, unique=unique)
        t_grow = time.monotonic()
        self.growth_events.append((status, unique))
        status_name = _STATUS_TELEMETRY_NAMES.get(status, str(status))
        with tel_span(
            "grow", rec, parent=self._run_span_ctx,
            status=status_name, unique=unique, cap=cap,
        ) as grow:
            if rec is not None:
                crossed = rec.counters()
                event = rec.record(
                    "growth", status=status_name,
                    unique=unique, cap=cap, qcap=qcap, cand=cand,
                    path="device" if self._grows_on_device(carry) else "host",
                )
                if status == _STATUS_CAND_FULL:
                    rec.add("compaction_hits")
                if self._cartography and getattr(self, "_live_cart", None):
                    # growth boundaries are the cartography time series:
                    # one ring record each (plus the closing "final")
                    rec.record("cartography", at="growth", **self._live_cart)
            out = self._grow(
                carry, status, cap, qcap, batch, cand,
                at=(st.head, st.tail, unique), parent=grow.ctx,
            )
            if rec is not None:
                # what this event moved between host and device, by the
                # recorder's own byte counters
                now = rec.counters()
                rec.amend(event, **{
                    k: int(now.get(k, 0) - crossed.get(k, 0))
                    for k in ("d2h_bytes", "h2d_bytes")
                })
        self._stage("growth", time.monotonic() - t_grow)
        return out

    def _run(self):
        try:
            self._run_impl()
        finally:
            if self._prewarmer is not None:
                # stop the background compiler with the run (its daemon
                # thread would otherwise idle for the process lifetime)
                self._prewarmer.close()

    def _record_programs(self, parent, events) -> float:
        """Lay a :class:`CompileWatch`'s events down as children of the
        span that paid for them (``dispatch``; ``engine_acquire`` ahead of
        time): a ``program.lower`` per module lowered, a ``program.load``
        per program that reached the backend's compile-or-load step.
        Returns the seconds inside the ``program.load`` ones."""
        rec = self.flight_recorder
        loaded = 0.0
        for event, end, secs, retrieved in events:
            if event == LOWER_EVENT:
                record_span(rec, PROGRAM_LOWER, parent=parent,
                            start=end - secs, dur=secs)
            else:
                loaded += secs
                record_span(
                    rec, PROGRAM_LOAD, parent=parent, start=end - secs,
                    dur=secs, hit=retrieved is not None,
                    retrieved_s=None if retrieved is None
                    else round(retrieved, 6),
                )
        return loaded

    def _timed_device_call(self, fn, arg=None):
        """Run one device call (init or a steps block) and say where its
        wall time went: ``compile`` is what JAX's monitoring saw of the
        backend's compile-or-load step (the ``program.load`` children of
        ``dispatch``), ``trace`` the rest of ``dispatch`` (Python tracing,
        lowering, the enqueue: what no cache saves), ``device`` the
        ``wait`` span, the host blocked on the device.  The pending compile
        event is amended with the measured load.  Blocking on the packed
        stats vector is what makes the wall time real (dispatch alone
        returns immediately)."""
        rec = self.flight_recorder
        watch = CompileWatch() if rec is not None else None
        # host seam span: ``dispatch`` ends when the call returns (tracing,
        # a lazy compile, the enqueue), ``wait`` is the host blocked on the
        # device — what a growth upload left in flight shows here too
        with tel_span("device_call", rec, parent=self._run_span_ctx) as call:
            with tel_span("dispatch", rec, parent=call.ctx) as dispatch:
                carry, stats = fn() if arg is None else fn(arg)
                if watch is not None:
                    d = watch.delta()
                    dispatch.set(jaxprs_traced=d["jaxprs_traced"])
            with tel_span("wait", rec, parent=call.ctx) as wait:
                stats = np.asarray(stats)
            call.set(dsteps=int(stats[ST_DSTEPS]))
        if rec is not None:
            dt = dispatch.fields["dur"]
            comp = min(self._record_programs(dispatch.ctx, d["events"]), dt)
            self._stage("compile", comp)
            self._stage("trace", dt - comp)
            self._stage("device", wait.fields["dur"])
            if self._pending_compile_rec is not None:
                # accumulate: one engine acquisition covers two programs
                # (init_fn + run_fn) whose lazy compiles land on different
                # calls; once a call measures zero compile the event has
                # converged and stops amending (a later rung records its
                # own event)
                if comp > 0:
                    prev = self._pending_compile_rec
                    hit = (bool(prev.get("cache_hit"))
                           or d["persistent_hits"] > 0)
                    rec.amend(
                        prev,
                        duration=round(
                            float(prev.get("duration", 0.0)) + comp, 6
                        ),
                        cache_hit=hit,
                        source="persistent" if hit else "fresh",
                    )
                else:
                    self._pending_compile_rec = None
        return carry, stats

    def _run_impl(self):
        cap, qcap, batch = self._cap, self._qcap, self._batch
        arity = self.tensor.max_actions
        cand = min(self._cand, batch * arity)
        # static preconditions are known here; pre-size rather than paying an
        # engine compile + re-init per doubling: cand*4 <= cap, and the init
        # set must fit the queue (its write window is qalloc = qcap + m)
        while cand * 4 > cap:
            cap *= 2
        n_init = len(np.asarray(self.tensor.init_rows()))
        while n_init > qcap:
            qcap *= 2
        self._cap, self._qcap, self._cand = cap, qcap, cand
        if self._resume is not None:
            cap, qcap, carry = self._snapshot_to_carry(self._resume)
            batch = self._batch  # the snapshot's batch governs buffer layout
            cand = min(self._cand, batch * arity)  # snapshot's tuned budget
            stats = None
            # a snapshot taken at a growth boundary still carries the flag
            st = int(np.asarray(carry.status))
            if st != _STATUS_OK:
                carry, cap, qcap, cand, _ = self._grow(
                    carry, st, cap, qcap, batch, cand
                )
        else:
            while True:
                init_fn, _ = self._engine(cap, qcap, batch, cand,
                                          kind="init")
                carry, stats = self._timed_device_call(init_fn)
                # init insertion must be atomic: a table-full at init means
                # nothing was written, so grow statically and re-init rather
                # than resuming an inconsistent carry.  A queue-full init is
                # consistent (table + queue both hold every init row) and the
                # main loop's generic growth compacts/extends it in place.
                if read_stats(stats, carry).status != _STATUS_TABLE_FULL:
                    break
                n_init = len(self.model.init_states())
                prev = cap
                while (n_init * 4 > cap) or (cand * 4 > cap):
                    cap *= 2
                if cap == prev:
                    cap *= 2  # guarantee progress on a clustered init set

        rec = self.flight_recorder
        occ_every = int(self._telemetry_opts.get("occupancy_every") or 0)
        syncs = 0
        hs = 0  # host-sync ordinal for the chaos seam (recorder-independent)
        if rec is not None:
            rec.update_meta(batch=batch, steps_per_call=self._steps)
            if self._spill:
                from ..spill import SPILL_V
                from ..telemetry.memory import device_budget

                budget, _src = device_budget()
                rec.record(
                    "spill", v=SPILL_V, event="arm",
                    bloom_bits=self._spill_cfg[0],
                    pend_cap=self._spill_cfg[1],
                    **({"budget_bytes": int(budget)} if budget else {}),
                )
                self._refresh_spill()
        grown_occ = None  # a split table's histogram, still on the device
        while True:
            # one host sync per iteration: the packed stats vector
            if stats is None:
                stats = stats_np(carry)
            elif grown_occ is not None:
                # the device call behind this sync ran after the split, so
                # its histogram is there to read at no wait: the occupancy
                # sample a growth boundary offers, of the table it left
                self._telemetry_occupancy_hist(grown_occ, at="growth")
                grown_occ = None
            st = read_stats(stats, carry)
            (head, tail, unique, scount, maxdepth, status, dsteps,
             append_chunks, disc) = st[:9]
            self._device_steps += dsteps
            with self._live_lock:
                self._live = (scount, unique, maxdepth)
                self._live_disc = disc
            if st.por is not None:
                self._live_por = self._por_stats_dict(st.por)
            pend_live, spilled_live = 0, 0
            if st.spill is not None:
                pend_live, spilled_live = int(st.spill[0]), int(st.spill[1])
                self._spill_tally["deferred"] = int(st.spill[2])
                self._spill_tally["on_device"] = int(st.spill[3])
            if st.cart is not None:
                self._sync_cartography(st.cart, states=scount, unique=unique)
            if carry.err is not None:
                # a failed kernel check raises HERE, before any growth or
                # checkpoint handling touches the (possibly garbage) carry
                self._raise_on_checked_error(carry, head, tail, batch)
            if rec is not None:
                # all fields below are host state the loop already synced —
                # the telemetry cost is one dict append per block
                syncs += 1
                rec.add_bytes(d2h=stats.nbytes)
                rec.step(
                    # subclass engines (the mesh engine) reuse this loop:
                    # telemetry must carry the tag of the engine that ran
                    engine=(
                        "wavefront" if self._engine_tag == "single"
                        else self._engine_tag
                    ),
                    states=scount, unique=unique,
                    depth=maxdepth, status=status,
                    queue=max(tail - head, 0), cap=cap, cand=cand,
                    # device steps of the call this sync closed, and the
                    # lanes each popped: dsteps * batch lanes were offered
                    dsteps=dsteps, batch=batch,
                    # chunk writes of that call's appends (append_novel):
                    # / dsteps the trips a step, x min(batch, cand) against
                    # dsteps x cand the lanes the cand-wide window wrote
                    append_chunks=append_chunks,
                    # HOT occupancy with the spill tier armed: evicted
                    # uniques live off-device (spilled_live is 0 otherwise)
                    load_factor=round((unique - spilled_live) / cap, 6),
                )
                if occ_every and syncs % occ_every == 0:
                    self._telemetry_occupancy(
                        carry.table_fp, at=f"sync{syncs}", transferred=True
                    )
                if self._mem_ledger is not None:
                    # rung changes emit a ``memory`` ring record (the
                    # per-growth series); otherwise this is a cheap dict
                    # compare plus the periodic watermark sample
                    self._mem_ledger.observe(
                        {"cap": cap, "qcap": qcap, "batch": batch},
                        extra={"queue_capacity": qcap},
                    )
            # chaos seam (testing/faults.py): inert unless a FaultPlan is
            # installed — host-side only, so the step jaxpr cannot change
            faults.fire("host_sync", recorder=rec, step=hs, unique=unique)
            hs += 1
            # serve a pending checkpoint BEFORE growing OR resolving: a
            # request landing on a growth boundary snapshots the boundary
            # carry (status != OK) and resume re-applies the growth; one
            # landing mid-deferral snapshots the pending buffer (the
            # manifest carries it), so heavy Bloom traffic can never
            # starve a checkpoint behind back-to-back resolutions
            if self._ckpt_req is not None and self._ckpt_req.is_set():
                self._ckpt_out = self._carry_to_snapshot(carry, cap, qcap, cand)
                self._ckpt_req.clear()
                self._ckpt_ready.set()
            # periodic autosave (stateright_tpu/checkpoint.py): when the
            # cadence is due, this sync's carry lands as an atomic
            # rotating generation — a boundary carry (status != OK) is a
            # valid snapshot (resume re-applies the growth), so no status
            # gate is needed
            self._maybe_autosave(
                lambda: self._carry_to_snapshot(carry, cap, qcap, cand)
            )
            # spill pending resolution: every sync with deferred
            # candidates (and a table/queue the inject can write into —
            # growth boundaries resolve on the NEXT sync) looks them up
            # in the host index and injects the Bloom false positives
            if (
                self._spill
                and pend_live > 0
                and status in (_STATUS_OK, _STATUS_SPILL_SYNC)
            ):
                t_sp = time.monotonic()
                # host seam span: the Bloom-deferral drain is where a
                # spilled run's wall time hides — the trace shows it as
                # a child of the engine_run span (telemetry/spans.py)
                with tel_span(
                    "spill_drain", rec,
                    parent=self._run_span_ctx, pending=int(pend_live),
                ):
                    cap, qcap, carry = self._resolve_pending(
                        carry, cap, qcap, batch, cand
                    )
                self._stage("spill", time.monotonic() - t_sp)
                stats = None
                continue
            if status == _STATUS_POISON:
                raise RuntimeError(
                    "poisoned rows reached by the device run: a compiled "
                    "transition crossed its compile-time state_bound/"
                    "env_bound, or a hand-written twin's send found no free "
                    "network slot (n_slots), so counts would be silently "
                    "wrong. Loosen the bounds, or raise n_slots (they must "
                    "cover everything the configuration actually reaches)."
                )
            if status != _STATUS_OK:
                carry, cap, qcap, cand, grown_occ = self._growth_event(
                    carry, st, cap, qcap, batch, cand
                )
                stats = None
                continue
            if self._stop.is_set():
                # cooperative preemption (stop()/SIGTERM/deadline): one
                # forced final generation so "stall => snapshot + yield
                # the chip" loses at most the current steps block
                self._maybe_autosave(
                    lambda: self._carry_to_snapshot(carry, cap, qcap, cand),
                    force=True,
                )
                break
            all_disc = bool(self._props) and bool((disc != 0).all())
            target_hit = self._target is not None and unique >= self._target
            if (
                self._spill
                and tail <= head
                and self._spill_qrows
                and not all_disc
                and not target_hit
            ):
                # the device queue drained but host-offloaded frontier
                # rows remain: refill and keep going — the search is not
                # done until every tier is empty
                t_sp = time.monotonic()
                carry = self._queue_refill(carry, cap, qcap, batch)
                self._stage("spill", time.monotonic() - t_sp)
                stats = None
                continue
            done = tail <= head
            if all_disc:
                done = True
            if target_hit:
                done = True
            if done:
                break
            self._maybe_schedule_prewarm(cap, qcap, batch, cand, unique, tail)
            _, run_fn = self._engine(cap, qcap, batch, cand)
            if self._profiler is not None:
                self._profiler.maybe_start()
            carry, stats = self._timed_device_call(run_fn, carry)
            if self._profiler is not None:
                self._profiler.tick()

        self._cap, self._qcap, self._cand = cap, qcap, cand
        if self._profiler is not None:
            self._profiler.stop()
        if grown_occ is not None:
            # a run that ended on the sync after a growth
            self._telemetry_occupancy_hist(grown_occ, at="growth")
        if rec is not None and occ_every:
            # close the occupancy time series with the final table (an
            # explicit D2H pull, taken only when sampling was requested)
            self._telemetry_occupancy(carry.table_fp, at="final",
                                      transferred=True)
        # Keep final buffers on device; pulling the table/queue to the host
        # costs far more than the run's last batches, so snapshots and
        # parent maps materialize lazily on demand.
        self._final_carry = carry
        self._results = {
            "unique": unique,
            "states": scount,
            "disc": np.asarray(disc),
            "depth": maxdepth,
        }
        if self._por and self._live_por is not None:
            self._results["por"] = dict(self._live_por)
        if self._spill:
            from ..spill import SPILL_V

            snap_sp = self._spill_snapshot()
            self._results["spill"] = snap_sp
            if rec is not None:
                rec.record(
                    "spill", v=SPILL_V, event="final",
                    spilled_fps=snap_sp["spilled_fps"],
                    host_bytes=snap_sp["host_bytes"],
                    disk_bytes=snap_sp["disk_bytes"],
                    dups=snap_sp["resolved_dups"],
                    novel=snap_sp["resolved_novel"],
                )
                self._refresh_spill()
        if self._cartography and getattr(self, "_live_cart", None):
            self._results["cartography"] = self._live_cart
            if rec is not None:
                rec.record("cartography", at="final", **self._live_cart)
        if self._mem_ledger is not None:
            # close the memory time series (fresh live watermark)
            self._mem_ledger.finalize()
        if rec is not None:
            # a deadline-cut run stopped; it did not finish — leave the
            # health phase where the run actually was
            rec.close_run(done=not self._timed_out)
        self._warn_small_space()
        self._done.set()

    @property
    def _final_snapshot(self) -> dict:
        return self._carry_to_snapshot(self._final_carry, self._cap, self._qcap)

    def _table_np(self):
        return (
            np.asarray(self._final_carry.table_fp),
            np.asarray(self._final_carry.table_parent),
        )

    def _device_table(self):
        """The final carry's table, for reconstruction on the device -
        on one chip or sharded by bucket range over a mesh (the walk then
        runs under the table's own sharding, ``_base._chains``) - but not
        once the spill tier evicted (an eviction clears the WHOLE hot
        table, the init states with it, so every chain leaves it)."""
        if self._spilled():
            return None
        return self._final_carry.table_fp, self._final_carry.table_parent

    # -- live progress + checkpointing ---------------------------------------

    def state_count(self) -> int:
        if self._results:
            return self._results["states"]
        return self._live[0]

    def unique_state_count(self) -> int:
        if self._results:
            return self._results["unique"]
        return self._live[1]

    # stop()/checkpoint() come from WavefrontChecker; this engine serves
    # _ckpt_req in its host sync loop and defines _final_snapshot above.


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p

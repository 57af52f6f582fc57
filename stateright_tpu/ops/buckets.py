"""Bucketized device visited-set: one-shot insert, no probe loop.

The round-1 visited set (an open-addressing table with a ``lax.while_loop``
scatter-min claim protocol, since removed) probed per conflict; on real TPU
hardware each probe iteration
costs a full-size scatter (~6 ms per 61k-candidate scatter on v5e), and the
loop runs for the *longest* probe chain in the batch — measured ~600 ms per
batch, 50× the cost of everything else combined.  XLA scatters on TPU are
effectively index-serial, so the fix is architectural, not incremental:

 - The table is an array of **buckets** of ``SLOTS`` fingerprints each; a
   fingerprint's bucket is the HIGH bits of ``mix64(fp)`` (one extra
   splitmix64 round).  An earlier table-size anomaly traced to
   the previous derivation — the fingerprint's raw low bits — clustering:
   splitmix64's final odd multiply avalanches upward only (bit ``k`` of the
   product depends on input bits ``0..k``), so the low bits of structurally
   close rows collide ~6x past Poisson and buckets overflowed ``SLOTS`` at
   25% load.  The remix costs 2 multiplies + 3 shift-xors per candidate and
   the bucket reads from the multiply's high (fully avalanched) bits;
   the pinned 2PC-7 occupancy series is back at the Poisson expectation
   (``tests/test_telemetry.py``), and ``tests/test_buckets.py`` pins
   avalanche + chi-square on the derivation itself.  Membership is ONE wide
   gather (the ``[M, ROW_LANES]`` rows that hold the candidates' buckets) +
   a vectorized lane compare — ROW gathers are cheap on TPU (the measured
   cost is scatters, and ELEMENT gathers: the values that follow a sort ride
   through it as operands, ``bucket_insert``).
 - Batch candidates are sorted ONCE by their remixed key (bucket bits are
   the key's MSBs; EMPTY lanes pin to the maximal key), which simultaneously
   (a) groups equal fingerprints adjacently for first-occurrence dedup,
   (b) groups same-bucket candidates adjacently so per-bucket insertion
   ranks are a cumulative-sum away, and (c) keeps valid candidates a sorted
   prefix.
 - Every novel candidate's slot is ``occupancy(bucket) + rank`` — slots fill
   densely and never free, so a bucket's occupancy is just the non-EMPTY
   count of its (already gathered) line: no separate counts array exists,
   and no occupancy update is ever written.  Ranks are computed vectorially
   and the fp/payload writes go through a *windowed chunked* scatter that
   touches only ~``n_new`` entries instead of all ``M`` candidates (scatter
   cost scales with indices, so writing only what's new is the big win).
 - A bucket overflowing its ``SLOTS`` raises an overflow flag; the caller
   grows the table and rehashes host-side.  At the engine's ≤25% load factor
   the Poisson tail P(bucket > 16 | λ=4) ≈ 1e-7 makes that a rare event.

**Where the layout is fixed** (PR 38).  The table is ONE flat
``uint64[nbuckets * SLOTS]`` array (slot ``s`` of bucket ``b`` at ``b * SLOTS
+ s``) from the engine's init program to the end of its run program, in the
snapshot, and on the host; on the TPU that is two ``u32[cap]`` planes tiled
``T(1024)``: 1,024 consecutive slots a tile.  Inside the step's loop body only
two kinds of operation touch it, and neither reads or writes O(``cap``)
elements: the membership loop's row gather and the chunked scatters (in
place).  The membership loop used to view the table as ``[nbuckets, SLOTS]``
lines; the compiler gives that operand the layout ``{0,1:T(8,128)}`` (sixteen
planes, a "line" being sixteen strided words: minor dimension 16 would pad to
128 otherwise), so EVERY step re-laid both planes, a reshape and a copy each:
1.5 ms a step at 2^23 slots, 15 ms at 2^26, whatever the batch.  It now views
it as ``[cap / ROW_LANES, ROW_LANES]`` rows of 128 slots, whose tiled layout
``{1,0:T(8,128)}`` IS the flat plane's (a tile is 8 rows x 128 lanes = the same
1,024 consecutive slots): the reshape is a bitcast, the gather fetches one
512-byte row a plane, and the seven other buckets of the row are masked to
EMPTY before the compare (in a 2pc-8 check the row gathers cost 0.199 s
where the line gathers cost 0.180, and the masked 128-lane compare 0.064
where the 16-lane one cost 0.007: 0.08 s back of the 1.38 s the relayout
cost; my chip runs, PR 38).  Alone on one v5e (ms a
call, 40 inserts in a ``fori_loop`` with the table as the carry, min of 5,
window 2,048; tables 2^21 / 2^23 / 2^26): lines 0.883 / 2.544 / 15.901, rows
0.658 / 0.732 / 1.287 (at 2^26 the planes no longer fit the chip's VMEM, where
the compiler keeps them when it can: the gathers and scatters go to HBM).
Also timed there and dearer: a ``[nbuckets, SLOTS]`` carry with 2-D scatters
(the scatter is flattened to 1-D, so the relayout moves into the WRITE loop:
1.162 / 40.670 at 2^23 / 2^26), a ``[SLOTS, nbuckets]`` carry (1.396), and a
windowed gather with ``slice_sizes=(SLOTS,)`` from the flat table or from
``[cap / 128, 128]`` (expanded into a serial ``while`` of ``window`` trips:
26.4 / 29.1).  ``tests/test_table_layout.py`` pins it: no equation of the
step's loop body but the row view, the gather and the scatters touches ``cap``
elements, and the program compiled for a described v5e holds two table-sized
operations in its loop (the scatters) against the old body's nine.

Reference analogue: the lock-striped ``DashMap`` visited set
(``src/checker/bfs.rs:26``); payload = parent fingerprint for trace
reconstruction, as there.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry.spans import STAGE_GROW
from .hashing import EMPTY, mix64, mix64_np

SLOTS = 16  # fingerprints per bucket (one 128-byte line of u64s)
ROW_LANES = 128  # slots the membership loop fetches at once: the TPU's lane
#                  count, so 8 buckets a row (see "Where the layout is fixed")


def bucket_key(fps: jnp.ndarray) -> jnp.ndarray:
    """Sort/derivation key: ``mix64(fp)`` with EMPTY lanes pinned to the
    maximal key.  A bucket is the key's high ``bucket_bits`` bits, so
    sorting by the key groups candidates by bucket with equal fingerprints
    adjacent AND keeps valid candidates a sorted prefix (EMPTY sorts last).
    The one valid fp whose mix64 equals EMPTY remaps to ``EMPTY - 1`` —
    same bucket (high bits agree), prefix invariant preserved; colliding
    with it is the same accepted 2^-64 risk class as the EMPTY sentinel
    itself (``ops/hashing.py``)."""
    k = mix64(fps)
    k = jnp.where(k == EMPTY, EMPTY - jnp.uint64(1), k)
    return jnp.where(fps == EMPTY, EMPTY, k)


def window_unique(fps: jnp.ndarray) -> jnp.ndarray:
    """Intra-window pre-dedup: mask duplicate fingerprints to EMPTY, keeping
    the FIRST occurrence (lowest lane index) of each.

    ``bucket_insert`` already dedups within its window (the first-occurrence
    mask over the sorted candidates), so this is purely a *traffic* reducer:
    engine candidate windows are mostly duplicates of each other (BLEST-style
    frontier duplication — siblings regenerate the same successors), and
    every duplicate lane left valid pays full price through the compaction
    budget, the membership gathers, and the rank pipeline.  EMPTYing them
    here shrinks the insert loop's EFFECTIVE window to the unique count.

    Exactness contract (pinned by tests): because the kept lane is the first
    occurrence by original index — the same lane ``bucket_insert``'s stable
    sort would have picked as the survivor, in both table order and
    generation order — the inserted (fp, payload) set, ``sel`` prefix, and
    ``n_new`` are bit-identical with or without the filter.  Only
    ``cand_overflow`` pressure changes (it can only drop).  EMPTY lanes pass
    through unchanged.  One extra sort + bool scatter per window; on TPU the
    sort is cheap next to the table gathers it avoids.
    """
    m = fps.shape[0]
    order = jnp.argsort(fps)  # stable: ties keep original index order
    sfp = fps[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sfp[1:] != sfp[:-1]])
    # (fps != fps) is an all-False array DERIVED from the input, so the
    # mask stays mesh-varying inside shard_map (a zeros() literal would be
    # replicated-typed; cf. the membership-loop carries in bucket_insert)
    keep = (fps != fps).at[order].set(first)
    return jnp.where(keep, fps, EMPTY)


def lane_compact(mask: jnp.ndarray, width: int, carry=(), pos=None):
    """Order-preserving lane compaction: ``(idx, live, count, *carried)``
    with the first ``width`` True lanes of ``mask`` at the front: ``idx``
    their lane indices, each ``carry`` array's values at those lanes
    (``live`` flags which output lanes are real, ``count`` the total True
    lanes; dead lanes of ``idx`` are in range: they list the False lanes,
    in order, and ``carried`` holds those lanes' values).  ``pos`` (unique
    int32 in ``[0, 2^31)``, default the lane index) orders the lanes and
    is what ``idx`` then lists.  The one compaction of this module:
    ``bucket_insert``'s candidate budget, its novel compaction and the
    spill tier's pending-deferral append all call it.

    ONE sort of a packed u32 key — bit 31 = lane invalid, low 31 bits =
    ``pos`` — so valid lanes sort first and stay in order: the keys are
    unique, so no stability, no ``argsort``, no scatter.  A value that
    has to follow its lane can ride through the sort as an operand
    (``carry``) in place of ``x[idx]`` afterwards: on one v5e an element
    gather costs 3.6 ns a lane alone in a program and 7.1 inside the step
    program, whatever it gathers from (PR 36; ms a call alone on the
    chip, at 86,016 -> 32,768 lanes / 68,608 -> 32,768: this sort + four
    u32 gathers 0.526 / 0.528, the sort carrying the four words 0.118 /
    0.115, the sort alone 0.054) — but every operand of a sort over
    16,384 lanes adds ~10 s to the program's compile (this sandbox's TPU
    compiler: 1 / 3 / 5 / 7 u32 operands at 32,768 lanes and beyond 3 /
    19 / 44 / 79 s; at 8,192 lanes 3 s at most), so
    ``bucket_insert`` carries through its CB-wide compaction and not
    through its ``M``-wide one.
    It replaced ``cumsum`` + ``searchsorted(running count, 1..width)``:
    ``width`` binary searches are ~17 DEPENDENT random-access gather
    rounds, and gather latency is what this chip charges for, while a
    sort is a fixed network of vector compare-exchanges.  Alone on one
    v5e, inside one program, at the benchmark cells' shapes (PR 25; ms a
    call, the same at 10 / 30 / 50% valid), old search -> this sort:
    86,016 -> 32,768: 4.00 -> 0.044; 122,880 -> 16,384: 2.00 -> 0.056;
    61,440 -> 8,192: 0.95 -> 0.028.  Also timed there and dearer: the
    same sort stable (2x), a two-level count over 128-lane blocks
    (0.29 / 0.16 / 0.10), ``searchsorted(method='sort')`` (0.92 / 1.00 /
    0.51).  Not ``jnp.nonzero(size=...)``: JAX builds it from a scatter.
    """
    m = mask.shape[0]
    assert width <= m < (1 << 31), "lane index must fit the key's low 31 bits"
    count = jnp.sum(mask, dtype=jnp.int32)
    if pos is None:
        pos = jnp.arange(m, dtype=jnp.uint32)
    key = jnp.where(mask, jnp.uint32(0), jnp.uint32(1 << 31)) | pos.astype(jnp.uint32)
    # keys are unique, so an unstable sort has exactly one result
    first, *carried = (
        x[:width]
        for x in jax.lax.sort((key, *carry), num_keys=1, is_stable=False)
    )
    idx = (first & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    live = jnp.arange(width, dtype=jnp.int32) < count
    return idx, live, count, *carried


def bucket_of(fps, nbuckets: int) -> np.ndarray:
    """Host-side bucket derivation (numpy): the bucket ``bucket_insert``
    and ``host_bucket_rehash`` place ``fps`` in for an ``nbuckets``-bucket
    table.  Shared by the rehash, the tests' collision construction, and
    the chi-square diagnostics."""
    assert nbuckets & (nbuckets - 1) == 0
    bits = int(nbuckets).bit_length() - 1
    k = mix64_np(fps)
    k = np.where(k == np.uint64(EMPTY), np.uint64(EMPTY) - np.uint64(1), k)
    return (k >> np.uint64(64 - bits)).astype(np.int64)


def bucket_insert(
    table_fp: jnp.ndarray,  # uint64[nbuckets * SLOTS]; EMPTY = free
    table_payload: jnp.ndarray,  # uint64[nbuckets * SLOTS]
    fps: jnp.ndarray,  # uint64[M] candidates (EMPTY = invalid lane)
    payloads: jnp.ndarray,  # uint64[M]
    window: int,  # scatter chunk size (≈ expected novel per batch)
    generation_order: bool = False,  # compact novel rows in generation order
    #                            (needed for symmetry runs; see below)
    compact: int = None,  # optional valid-candidate budget CB: compact valid
    #                       lanes first and run the pipeline at width CB
    probe_dot: bool = False,  # BLEST one-hot membership probe (ops/mxu.py):
    #                           the membership/occupancy reductions over the
    #                           gathered table rows become ONE blocked
    #                           bitmapped dot_general — bit-identical
    #                           (present, base) per window, pinned by test.
    #                           Off adds zero ops (the prededup contract).
):
    """Insert all valid candidates; returns ``(table_fp, table_payload,
    sel, n_new, overflow, cand_overflow)``.

    ``sel[:n_new]`` holds the ORIGINAL indices (into ``fps``) of the
    inserted candidates — table order for plain runs, generation order
    (original batch position) with ``generation_order=True``; entries past
    ``n_new`` are arbitrary in-range indices (callers overwrite or mask
    whatever they gather with them).  On ``overflow`` (a bucket clustered
    past SLOTS) or ``cand_overflow`` (more valid candidates than the
    ``compact`` budget) NOTHING was written, ``n_new`` is 0, and the
    table returns unchanged — the caller grows the table / its
    candidate budget and replays the batch, so no work is lost.

    ``compact=CB`` first compacts the valid lanes into a CB-wide buffer
    (order-preserving: :func:`lane_compact` — no scatters, no search) and
    runs the whole sort/membership/rank/write pipeline at width CB.
    Engine batches are >90% EMPTY padding (static action arity vs ~2-9
    enabled actions per state), and on TPU the step's LATENCY scales with
    array width — u64 sorts, random-access table gathers, and index
    arithmetic all pay for the padding lanes — so running at the real
    candidate count is a multi-x step-time win on hardware.

    **What is carried where, and what is still fetched** (PR 36).  A value
    that has to follow a sort's permutation is an OPERAND of that sort,
    never ``x[perm]`` afterwards: an element gather costs 7.1 ns a lane
    inside the step program on one v5e (3.6 alone in a program), a u64
    twice that, whatever it gathers from; a sort operand costs a small
    fraction at run time — and ~10 s of COMPILE time each (this sandbox's
    TPU compiler, sorts over 16,384 lanes), which is the other half of
    every choice below.

     1. the budget compaction (``lane_compact`` at ``M`` lanes) carries
        nothing: ``fps[lane]`` stays a gather at CB lanes (two u32
        gathers).  Carrying ``fps`` and ``payloads`` through it was timed
        (alone on the chip, ms a call, 86,016 -> 32,768 lanes: sort + four
        gathers 0.526, the sort carrying four words 0.118) and is faster
        by 0.4 ms a step, but a five-operand sort at ``M`` lanes adds 40 s
        to the compile of EVERY step program (5.4 -> 45 s at 61,440 ->
        8,192), and a check from the defaults compiles one a rung.  Its
        ``idx`` is the ORIGINAL lane, which every later step keeps, so
        ``sel`` is never mapped back;
     2. the key sort (stable, at CB lanes) carries ``fps`` and the
        original lane beside the key;
     3. the novel compaction (``lane_compact`` at CB lanes) carries the
        target slot, the fingerprint and the original lane (under
        ``generation_order`` the original lane is its ordering position);
     4. the payload follows NO sort: it is needed only where something is
        written, so the write loop fetches ``payloads[sel]`` by original
        lane, ``window`` lanes a chunk (~n_new lanes a step, not CB).

    The per-bucket rank's segment base is a running max, not a look-up.
    The old body fetched all of these by index: eighteen u32 gathers at
    CB lanes, 3.62 s of a 7.3 s-busy 2pc-8 check (eleven call sites; kept
    verbatim as ``tests/test_buckets.py:ref_bucket_insert``, which this
    body is held to bit for bit).  Alone on the chip at 86,016 -> 32,768
    lanes, ms a call, old -> everything carried: compaction + key sort
    1.743 -> 0.189; novel compaction 0.97 -> 0.04; segment base 0.29 ->
    0.05.  What is left beside the two kept fetches: the membership loop's
    ``[window, ROW_LANES]`` row gather and the chunked scatters (the
    whole-table passes went in PR 38: module docstring, "Where the layout
    is fixed").
    """
    m_orig = fps.shape[0]
    cand_overflow = jnp.bool_(False)
    lane = jnp.arange(m_orig, dtype=jnp.int32)  # ORIGINAL lanes, all the way
    if compact is not None and compact < m_orig:
        lane, live, n_valid_orig = lane_compact(fps != EMPTY, compact)
        cand_overflow = n_valid_orig > jnp.int32(compact)
        fps = jnp.where(live, fps[lane], EMPTY)
    m = fps.shape[0]
    window = min(window, m)
    nslots = table_fp.shape[0]
    nbuckets = nslots // SLOTS
    assert nbuckets & (nbuckets - 1) == 0, "bucket count must be a power of two"
    bucket_bits = int(nbuckets).bit_length() - 1

    # stable: among equal fingerprints the LOWEST original lane comes first
    # (compaction kept lane order), which decides the parent a state records
    # and, under generation_order, which class member is explored
    skey, sfp, order = jax.lax.sort(
        (bucket_key(fps), fps, lane), num_keys=1, is_stable=True
    )
    valid = sfp != EMPTY
    first = jnp.concatenate([jnp.ones((1,), bool), sfp[1:] != sfp[:-1]]) & valid
    bucket = (skey >> jnp.uint64(64 - bucket_bits)).astype(jnp.int32)
    n_valid = jnp.sum(valid).astype(jnp.int32)

    # membership + occupancy-base gathers, windowed over the VALID PREFIX
    # only (EMPTY rotates to all-ones and sorts last, so valid candidates
    # are a prefix of the sorted order).  Random-access HBM gathers are the
    # step's latency bottleneck on TPU — measured 11.4 ms for an M=61k-row
    # gather from an 8M-slot table where only ~4k lanes were valid; padding
    # lanes pay full price in a monolithic gather, and this read-only loop
    # (typically 2-3 windows) makes the cost track the real candidate
    # count.  Writes stay outside: the atomic nothing-written-on-overflow
    # contract the engines' growth protocols rely on is untouched.
    #
    # The fetch is a ROW of the flat table, not a line: ``ROW_LANES``
    # consecutive slots, the ``per_row`` buckets that share them, with the
    # other buckets' lanes masked to EMPTY so that the compare and the count
    # below see the candidate's own bucket alone.  The row view is the one
    # 2-D shape whose tiled layout is the flat plane's own (module
    # docstring, "Where the layout is fixed"): no relayout of the table
    # exists in the step, whatever its size.
    row_lanes = min(ROW_LANES, nslots)
    per_row = row_lanes // SLOTS  # a power of two, as nslots and SLOTS are
    table_rows = table_fp.reshape(nslots // row_lanes, row_lanes)
    lane_bucket = np.arange(row_lanes, dtype=np.int32) // SLOTS
    mpad_w = (-m) % window
    pbucket = bucket if mpad_w == 0 else jnp.concatenate(
        [bucket, jnp.zeros((mpad_w,), jnp.int32)]
    )
    psfp = sfp if mpad_w == 0 else jnp.concatenate(
        [sfp, jnp.full((mpad_w,), EMPTY, jnp.uint64)]
    )

    def mem_body(state):
        k, present, base = state
        off = k * window
        wbkt = jax.lax.dynamic_slice(pbucket, (off,), (window,))
        wfp = jax.lax.dynamic_slice(psfp, (off,), (window,))
        # a shift and a mask, not ``//`` and ``%``: a signed floor division
        # is a dozen equations to trace and lower, in every step program a
        # fresh engine builds (0.3 s of a cold linreg check, PR 38)
        row = wbkt >> (per_row.bit_length() - 1)
        mine = lane_bucket[None, :] == (wbkt & (per_row - 1))[:, None]
        lines = jnp.where(mine, table_rows[row], EMPTY)
        if probe_dot:
            # BLEST one-hot probe (ops/mxu.py): one blocked bitmapped
            # matmul over the candidate x slot comparison tile replaces
            # the reduce_or/reduce_sum pair — same (present, base) bits,
            # but a genuine dot-class op for the MXU to chew on-chip
            from .mxu import blest_probe

            p, b = blest_probe(lines, wfp, EMPTY)
        else:
            p = jnp.any(lines == wfp[:, None], axis=-1)
            # occupancy comes free from the same gathered line: slots fill
            # densely from 0 and never free, so non-EMPTY count == next slot
            b = jnp.sum(lines != EMPTY, axis=-1).astype(jnp.int32)
        present = jax.lax.dynamic_update_slice(present, p, (off,))
        base = jax.lax.dynamic_update_slice(base, b, (off,))
        return k + 1, present, base

    # initial carries derive from the (possibly mesh-varying) inputs so the
    # loop types check inside shard_map: a literal zeros() is replicated-
    # typed while the body's output varies over the mesh axis
    _, present, base = jax.lax.while_loop(
        lambda s: s[0] * window < n_valid,
        mem_body,
        (
            jnp.int32(0),
            jnp.zeros((m + mpad_w,), bool) | (n_valid < 0),
            jnp.zeros((m + mpad_w,), jnp.int32) + n_valid * 0,
        ),
    )
    present, base = present[:m], base[:m]
    novel = first & ~present

    # per-bucket insertion rank among this batch's novel candidates: the
    # novel-count before the bucket's first row is non-decreasing along the
    # sorted lanes, so a running max carries it down the bucket's rows
    bstart = jnp.concatenate([jnp.ones((1,), bool), bucket[1:] != bucket[:-1]])
    csum = jnp.cumsum(novel.astype(jnp.int32))
    seg_base = jax.lax.cummax(jnp.where(bstart, csum - novel, 0))
    rank = jnp.where(novel, csum - 1 - seg_base, 0)

    slot = base + rank
    overflow = jnp.any(novel & (slot >= SLOTS))
    blocked = overflow | cand_overflow
    # n_new = 0 on any overflow: the write loops below key on it, so the
    # nothing-written atomicity holds for the candidate budget too
    n_new = jnp.where(blocked, 0, jnp.sum(novel)).astype(jnp.int32)

    # Compact novel candidates to the front.  Plain runs keep sorted-fp
    # order (bucket-contiguous); the visited SET is order-independent there.  Symmetry
    # runs compact in GENERATION order (original batch position): the dedup
    # key is the canonical fp of a not-necessarily-class-invariant
    # representative, so enqueue order decides which class member gets
    # explored — generation order makes the reduced search reproducible by
    # a host FIFO oracle (tests/test_tensor_models.py).  Windowed chunked
    # scatters write only ~n_new entries either way.
    # One sort carries target slot, fingerprint and original lane to the
    # front (under generation_order the original lane is the position
    # itself); its keys are unique, so the lanes past n_new are
    # deterministic too.
    tgt = jnp.where(novel, bucket * SLOTS + slot, nslots)
    if generation_order:
        sel, _, _, tgt, cfp = lane_compact(novel, m, carry=(tgt, sfp), pos=order)
    else:
        _, _, _, tgt, cfp, sel = lane_compact(novel, m, carry=(tgt, sfp, order))

    # Pad to a whole number of windows: ``dynamic_slice`` clamps its start
    # index, which would silently misalign the final chunk against its
    # ``in_range`` mask (dropping the last novel entries).
    pad = (-m) % window

    def padded(x, fill):
        if pad == 0:
            return x
        return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])

    def chunk_cond(state):
        k, *_ = state
        return k * window < n_new  # n_new is 0 on overflow: nothing written

    # The payload is needed only where something is written, so it follows
    # no sort: it is fetched by original lane, ``window`` lanes a chunk.
    ptgt = padded(tgt, nslots)
    pcfp = padded(cfp, EMPTY)
    psel = padded(sel, 0)

    def chunk_body(state):
        k, tfp, tpl = state
        off = k * window
        t = jax.lax.dynamic_slice(ptgt, (off,), (window,))
        f = jax.lax.dynamic_slice(pcfp, (off,), (window,))
        p = payloads[jax.lax.dynamic_slice(psel, (off,), (window,))]
        in_range = jnp.arange(window, dtype=jnp.int32) + off < n_new
        t = jnp.where(in_range, t, nslots)
        tfp = tfp.at[t].set(f, mode="drop")
        tpl = tpl.at[t].set(p, mode="drop")
        return k + 1, tfp, tpl

    _, table_fp, table_payload = jax.lax.while_loop(
        chunk_cond, chunk_body, (jnp.int32(0), table_fp, table_payload)
    )

    return table_fp, table_payload, sel, n_new, overflow, cand_overflow


# how a chain of :func:`parent_chains` ended
CHAIN_ROOT, CHAIN_MISS, CHAIN_BOUND = 0, 1, 2


@functools.partial(jax.jit, static_argnames="bound")
def parent_chains(
    table_fp: jnp.ndarray,  # uint64[nbuckets * SLOTS], as it lies in the carry
    table_payload: jnp.ndarray,  # uint64[nbuckets * SLOTS]
    starts: jnp.ndarray,  # uint64[K] fingerprints to trace; 0 = none
    bound: int,  # static: the longest chain there is room for
):
    """Follow the parent links of ``starts`` in the table, where it lies:
    ``(chains, lens, ends)``.

    ``chains[k, :lens[k]]`` is ``starts[k]``, its parent, its parent's
    parent ... down to an init state (the LEAF first: the host reverses
    it); ``ends[k]`` says how the walk stopped — ``CHAIN_ROOT`` at a state
    whose payload is 0 ("is an init state"; a start of 0 is a chain of
    length 0 that ends so), ``CHAIN_MISS`` at a fingerprint that is not in
    its bucket (``chains[k, lens[k]]``, not counted), ``CHAIN_BOUND`` after
    ``bound`` states with the last one's parent still to resolve.  The
    caller decides what a miss or a bound hit means; nothing is truncated
    in silence.

    The table has no probe chain (module docstring): a fingerprint lives
    in the ``SLOTS`` consecutive slots of the bucket its key names, and its
    payload IS its parent's fingerprint.  So one link is one ``SLOTS``-wide
    ``dynamic_slice`` of each array and a compare: the walk itself touches
    O(sum of ``lens``) elements whatever the table holds, the two arrays
    are arguments (not donated, not viewed in another shape), and what
    comes back is ``K * bound`` words.  The chains are walked one after the
    other, a scalar loop each: a handful of discoveries of a few dozen
    states.

    **On the TPU the call is still one pass over the table** (PR 47).  The
    chip has no 64-bit words: every program that takes a ``u64`` argument
    begins by writing it out as two ``u32`` planes (``X64SplitLow`` /
    ``X64SplitHigh``; the run program does that to its whole carry on
    every device call), and no spelling of the read moves the slice before
    the split.  ``tests/test_table_layout.py`` holds the compiled module
    to exactly that: the four splits in its entry, NOTHING of ``cap``
    elements inside its loops, the planes its only temporaries (0.4 / 34 /
    202 / 1,074 MB at 2^21 / 2^23 / 2^24 / 2^26 slots by
    ``memory_analysis()``; the allocator's peak does not hold them).  Alone
    on one v5e, a forged table, ms a call with its sync and three pulls
    (min / median of 30): 2.3 / 2.6 at 2^21 slots, 2.8 / 4.8 at 2^23, 3.8 /
    4.2 at 2^24, 5.5 / 5.9 at 2^25, 8.8 / 9.1 at 2^26 - where pulling both
    arrays to the host and building a dict of them took 0.15 s to 4.6 s in
    the benchmark's cells, and 21-24 s at 2^28.

    Module-level and keyed by shapes and ``bound`` alone: one program a
    table capacity, property count and (power-of-two) bound, whatever the
    model or the twin object, so a fresh model object in a warm process
    finds it compiled."""
    nslots = table_fp.shape[0]
    nbuckets = nslots // SLOTS
    assert nbuckets & (nbuckets - 1) == 0, "bucket count must be a power of two"
    bucket_bits = int(nbuckets).bit_length() - 1
    n_chains = starts.shape[0]
    walking = jnp.int32(-1)

    def parent_of(fp):
        # (found, parent): the one slot of fp's bucket that holds it
        if bucket_bits:
            key = bucket_key(fp) >> jnp.uint64(64 - bucket_bits)
            off = key.astype(jnp.int32) * SLOTS
        else:
            off = jnp.int32(0)
        hit = (jax.lax.dynamic_slice(table_fp, (off,), (SLOTS,)) == fp) & (
            fp != EMPTY
        )
        pay = jax.lax.dynamic_slice(table_payload, (off,), (SLOTS,))
        return jnp.any(hit), jnp.max(jnp.where(hit, pay, jnp.uint64(0)))

    def link(state):
        n, fp, row, _ = state
        found, parent = parent_of(fp)
        row = jax.lax.dynamic_update_slice(row, fp[None], (n,))
        n = n + found.astype(jnp.int32)
        end = jnp.where(
            ~found, CHAIN_MISS,
            jnp.where(parent == 0, CHAIN_ROOT,
                      jnp.where(n >= bound, CHAIN_BOUND, walking)),
        ).astype(jnp.int32)
        return n, parent, row, end

    def chain(k, out):
        chains, lens, ends = out
        start = starts[k]
        n, _, row, end = jax.lax.while_loop(
            lambda state: state[3] == walking,
            link,
            (
                jnp.int32(0), start, jnp.zeros((bound,), jnp.uint64),
                jnp.where(start == 0, CHAIN_ROOT, walking).astype(jnp.int32),
            ),
        )
        chains = jax.lax.dynamic_update_slice(chains, row[None], (k, 0))
        return chains, lens.at[k].set(n), ends.at[k].set(end)

    return jax.lax.fori_loop(
        0, n_chains, chain,
        (
            jnp.zeros((n_chains, bound), jnp.uint64),
            jnp.zeros((n_chains,), jnp.int32),
            jnp.zeros((n_chains,), jnp.int32),
        ),
    )


@functools.lru_cache(maxsize=None)
def sharded_parent_chains(sharding):
    """:func:`parent_chains` for a table that lies sharded over a mesh
    (``sharding``: the ``NamedSharding`` both table arrays came out of the
    run program with): the same walk, jitted with the table's own
    sharding as ``in_shardings`` - so no chip is handed the whole table
    before the call - and every output replicated, one dispatch a run.
    How a chip reads sixteen slots of another chip's bucket range is the
    partitioner's business (no collective is written here); the ``starts``
    and the chains are a few hundred bytes on every chip."""
    everywhere = jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec()
    )
    return jax.jit(
        parent_chains.__wrapped__, static_argnums=3,  # ``bound``
        in_shardings=(sharding, sharding, everywhere),
        out_shardings=everywhere,
    )


@functools.partial(jax.jit, static_argnames="new_nbuckets")
def bucket_split(
    table_fp: jnp.ndarray,  # uint64[nbuckets * SLOTS], as it lies in the carry
    table_payload: jnp.ndarray,  # uint64[nbuckets * SLOTS]
    new_nbuckets: int,  # static: a power-of-two multiple of nbuckets
):
    """Grow the table to ``new_nbuckets`` buckets where it lies:
    ``(table_fp, table_payload, histogram)``, the two arrays bit for bit
    what :func:`host_bucket_rehash` returns for the same table, and the
    ``SLOTS + 1``-bin per-bucket occupancy histogram of the NEW table
    (``occupancy_stats``'s ``histogram``: a reduction this has in hand).

    No sort, no scatter and no gather: a bucket is the TOP bits of
    :func:`bucket_key`, so a table of ``factor`` times the buckets takes
    ``log2(factor)`` more of them and old bucket ``b`` splits into the new
    buckets ``b * factor .. b * factor + factor - 1``, adjacent, and into
    no other.  The new table viewed as ``[nbuckets, factor * SLOTS]`` is
    therefore, bucket by bucket, a STABLE PARTITION of that bucket's
    ``SLOTS`` entries by those bits: an entry's new slot is its rank among
    the earlier entries of its sub-bucket (a compare over ``[SLOTS,
    SLOTS]`` pairs a bucket), and each new slot takes the one entry whose
    target it is (a one-hot compare-and-or over ``[factor * SLOTS, SLOTS]``
    a bucket).  The host's rehash fills each new bucket densely in the old
    table's slot order (a stable argsort over entries in table order),
    which is exactly that partition; and a split can never overflow a
    bucket, since a new bucket holds a subset of one old one (the host's
    ``ValueError`` arm does not exist here).  An element gather costs
    7-22 ns a lane on one v5e and a compare-and-select under 0.5 (ROADMAP
    Queue 1); a sort costs ~10 s of compile an operand past 16,384 lanes
    (:func:`lane_compact`).

    Not donated: jax 0.9.0 gives a donated input only to an output of its
    own size, and a split has none; the caller drops the old arrays as it
    takes the new ones.  Module-level and keyed by shapes and
    ``new_nbuckets`` alone, so a fresh model object in a warm process
    finds every rung's program compiled (:func:`parent_chains`).  Its
    operations carry the ``sr.grow`` scope: a stage of their own in the
    profiler's trace."""
    nslots = table_fp.shape[0]
    nbuckets = nslots // SLOTS
    factor = new_nbuckets // nbuckets
    assert nbuckets * factor == new_nbuckets and factor > 1, (
        "a split multiplies the bucket count"
    )
    assert new_nbuckets & (new_nbuckets - 1) == 0, (
        "bucket count must be a power of two"
    )
    new_bits = int(new_nbuckets).bit_length() - 1
    with jax.named_scope(STAGE_GROW):
        fp = table_fp.reshape(nbuckets, SLOTS)
        pl = table_payload.reshape(nbuckets, SLOTS)
        occ = fp != EMPTY
        # the sub-bucket: the bits a table of ``new_nbuckets`` reads below
        # the old bucket's own
        sub = (
            bucket_key(fp) >> jnp.uint64(64 - new_bits)
        ).astype(jnp.int32) & (factor - 1)
        slot = np.arange(SLOTS, dtype=np.int32)
        # rank among the EARLIER occupied entries of the same sub-bucket
        earlier = (
            (sub[:, :, None] == sub[:, None, :])
            & occ[:, None, :]
            & (slot[None, :] < slot[:, None])
        )
        rank = jnp.sum(earlier, axis=-1, dtype=jnp.int32)
        target = jnp.where(occ, sub * SLOTS + rank, -1)
        # each new slot takes the one entry it is the target of: at most
        # one lane of the reduced axis is set, so an OR is a select.  The
        # fingerprint goes through complemented, since EMPTY is all ones:
        # a slot nobody targets reads ~0 = EMPTY, its payload 0.
        hit = (
            target[:, None, :]
            == np.arange(factor * SLOTS, dtype=np.int32)[None, :, None]
        )
        zero = jnp.uint64(0)
        new_fp = ~jax.lax.reduce(
            jnp.where(hit, ~fp[:, None, :], zero), zero,
            jax.lax.bitwise_or, (2,),
        )
        new_pl = jax.lax.reduce(
            jnp.where(hit, pl[:, None, :], zero), zero,
            jax.lax.bitwise_or, (2,),
        )
        # occupancy of the new buckets, and how many hold 0 .. SLOTS; counted
        # from the old view (the new table viewed ``[new_nbuckets, SLOTS]``
        # is a relayout on the TPU: 420 MB of temporaries at 2^23 slots
        # where this holds 186)
        count = jnp.sum(
            occ[:, None, :]
            & (sub[:, None, :] == np.arange(factor, dtype=np.int32)[None, :, None]),
            axis=-1, dtype=jnp.int32,
        )
        hist = jnp.sum(
            count[:, :, None] == np.arange(SLOTS + 1, dtype=np.int32),
            axis=(0, 1), dtype=jnp.int32,
        )
        return new_fp.reshape(-1), new_pl.reshape(-1), hist


def occupancy_stats(table_fp) -> dict:
    """Bucket-occupancy counters for a visited table (numpy, JSON-safe).

    The engines' growth protocol keys on load factor and single-bucket
    overflow, but the *distribution* was never observable — and runs
    were seen growing tables earlier than the ≤25% Poisson model
    predicts.  This is the diagnostic handle on it:
    exposed via ``WavefrontChecker.occupancy_stats()``, the Explorer's
    ``/.status`` (``"table"``), and the audit report metrics.

    ``histogram[k]`` counts buckets holding exactly ``k`` fingerprints;
    a heavy tail vs Poisson(λ = occupied/nbuckets) means the bucket
    derivation (high bits of ``mix64(fp)``; see :func:`bucket_of`) is
    clustering — exactly the round-5 anomaly signature the old low-bit
    derivation produced.
    """
    t = np.asarray(table_fp).reshape(-1, SLOTS)
    per_bucket = (t != EMPTY).sum(axis=1)
    return occupancy_from_histogram(np.bincount(per_bucket, minlength=SLOTS + 1))


def occupancy_from_histogram(histogram) -> dict:
    """:func:`occupancy_stats` from the table's per-bucket occupancy
    histogram alone (``histogram[k]`` buckets hold exactly ``k``
    fingerprints): every counter of the record is a function of it, so a
    table that was never pulled (:func:`bucket_split` returns the new
    table's histogram) is described in the same fields."""
    hist = np.asarray(histogram, np.int64)
    nbuckets = int(hist.sum())
    occupied = int((hist * np.arange(hist.size)).sum())
    lam = occupied / nbuckets if nbuckets else 0.0
    # Poisson tail mass at/over SLOTS for the observed load — the model the
    # ≤25%-load growth policy assumes; compare with full_buckets/nbuckets
    tail = 0.0
    if lam > 0:
        import math

        p = math.exp(-lam)
        cum = p
        for k in range(1, SLOTS):
            p *= lam / k
            cum += p
        tail = max(0.0, 1.0 - cum)
    return {
        "nbuckets": nbuckets,
        "slots_per_bucket": SLOTS,
        "occupied": occupied,
        "load_factor": occupied / (nbuckets * SLOTS) if nbuckets else 0.0,
        "mean_bucket": lam,
        "max_bucket": int(np.flatnonzero(hist).max()) if nbuckets else 0,
        "full_buckets": int(hist[SLOTS:].sum()),
        "poisson_full_expect": tail * nbuckets,
        "histogram": hist.tolist(),
    }


def host_bucket_rehash(
    table_fp: np.ndarray, table_payload: np.ndarray, new_nbuckets: int
):
    """Rebuild the bucketized table with ``new_nbuckets`` buckets (numpy).
    Returns ``(table_fp, table_payload)``: slots fill densely per bucket,
    so occupancy is implicit in the table itself."""
    assert new_nbuckets & (new_nbuckets - 1) == 0
    occ = table_fp != EMPTY
    f = table_fp[occ]
    p = table_payload[occ]
    out_fp = np.full(new_nbuckets * SLOTS, EMPTY, np.uint64)
    out_pl = np.zeros(new_nbuckets * SLOTS, np.uint64)
    bucket = bucket_of(f, new_nbuckets)
    order = np.argsort(bucket, kind="stable")
    bucket, f, p = bucket[order], f[order], p[order]
    start = np.searchsorted(bucket, bucket, side="left")
    rank = np.arange(f.size) - start
    if rank.size and rank.max() >= SLOTS:
        raise ValueError("bucket overflow during rehash; grow further")
    out_fp[bucket * SLOTS + rank] = f
    out_pl[bucket * SLOTS + rank] = p
    return out_fp, out_pl

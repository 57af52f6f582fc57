"""Pallas TPU kernel for the visited-set insert (the north-star hot op).

Drop-in replacement for the fp/payload windowed-scatter ``while_loop`` in
``ops/buckets.bucket_insert`` (reference analogue: the lock-striped
``DashMap`` insert, ``src/checker/bfs.rs:26``).  The XLA path expresses the
insert as chunked ``scatter``s; this kernel instead walks the novel
candidates once, streaming each touched **block** of the table
HBM→VMEM→HBM with explicit DMA:

 - the tables stay in HBM (``pl.ANY``) and are updated **in place** via
   ``input_output_aliases`` — no table-sized copies, no scatter lowering;
 - a block is 8 line groups = 1024 u64 slots (Mosaic tiles 2-D i32 HBM
   memrefs as (8, 128), so DMA slices must cover whole 8-row tiles);
 - per candidate the update is a masked select on the VPU over the
   (8, 256)-lane block;
 - candidate metadata stays in HBM and is streamed into a fixed
   512-candidate SMEM window per DMA, so the kernel's VMEM footprint is
   batch-independent;
 - the trip count is the *dynamic* novel count — padding lanes cost
   nothing, so one compiled kernel serves every batch.

**The DMA walk is pipelined** (round 4; the round-3 serial walk paid ~2
blocking DMA latencies per touched block, which at engine scale — ~5k
distinct blocks per 8k-candidate batch against an 8M-slot table —
dominated the whole step).  The wrapper sorts candidates by target slot,
making touched blocks *ascending and unique*, and derives the
distinct-block sequence ("runs").  The kernel keeps a ring of ``NBUF``
resident block buffers: entering run ``r`` starts an async flush of the
evicted run and an async prefetch of run ``r + NBUF - 1``, so up to
``NBUF-1`` fetches and flushes are in flight while the VPU applies
selects to the resident block.  Re-sorting is safe for every caller:
target slots are distinct, so write order cannot matter, and exploration
order is carried by ``sel``, which is computed in ``bucket_insert``
before the kernel runs.

An earlier micro-benchmark (v5e, 8M-slot table, 8192 novel/batch; not
re-measured on today's code or the installed Mosaic): serial walk
54.1 ms/insert → pipelined 37.3 ms/insert → **XLA windowed scatter
0.14 ms/insert**.  The XLA path remains the default and the recommended
one; ``docs/pallas-insert-verdict.md`` explains why tile-granularity DMA
read-modify-write loses to the native scatter by construction at the
engine's ~1-candidate-per-block densities, and what narrower regime the
kernel shape would suit.

``uint64`` is not a native Pallas/TPU dtype, so the wrapper bitcasts the
u64 tables and candidate words to pairs of u32 lanes (little-endian: lane
``2k`` = low word of slot ``k``).

No occupancy metadata exists to maintain: slots fill densely and never
free, so a bucket's occupancy is implicit in its line (``ops/buckets.py``
derives it from the membership gather) — the u64 fp/payload writes this
kernel performs are the whole visited-set update.

Correctness contract (same as the XLA scatters): target slots are distinct
(bucket * SLOTS + per-bucket rank) and candidates are pre-deduplicated and
pre-screened for membership.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .buckets import SLOTS

# one line group = 8 buckets x 16 slots = 128 u64 slots = 256 u32 lanes
GROUP_BUCKETS = 8
GROUP_SLOTS = GROUP_BUCKETS * SLOTS
GROUP_LANES = 2 * GROUP_SLOTS  # u32 lanes per group
# one DMA block = 8 line groups (the (8, 128) i32 HBM tile height)
BLOCK_GROUPS = 8
BLOCK_SLOTS = BLOCK_GROUPS * GROUP_SLOTS
# candidates per meta SMEM window (multiple of the 128-lane tile width)
META_WINDOW = 512
# meta rows: run, row-in-block, lane, fplo, fphi, pllo, plhi, pad
META_ROWS = 8
# resident block buffers (ring): up to NBUF-1 prefetches in flight
NBUF = 8
# distinct-block ids per runs SMEM window (1-D i32 memrefs tile by 1024
# lanes, and DMA slices must cover whole tiles)
RUNW = 1024
# state_ref cells
_R_CUR, _R_PF, _R_WIN = 0, 1, 2


def interpret_mode() -> bool:
    """Mosaic compiles the kernel only for a TPU; on any other backend
    ``pallas_call`` runs it INTERPRETED — a correctness aid, orders of
    magnitude slower.  The engine publishes this next to ``pallas`` in
    the recorder meta and the report flags, so an interpreted run is
    never read as a kernel run."""
    return jax.default_backend() != "tpu"


def _insert_kernel(
    scal_ref,  # SMEM (2,) i32: [novel count, run count]
    meta_hbm,  # ANY  [META_ROWS, Mpad] i32 (streamed in windows)
    runs_hbm,  # ANY  [Rpad] i32: ascending distinct block ids
    tfp_hbm,  # ANY  [nblocks * BLOCK_GROUPS, GROUP_LANES] u32 (aliased out 0)
    tpl_hbm,  # ANY  (aliased out 1)
    tfp_out,
    tpl_out,
    meta_win,  # SMEM scratch (META_ROWS, META_WINDOW) i32 — SMEM because the
    #            kernel reads single elements at dynamic lane offsets, which
    #            Mosaic only supports for scalar memory
    runs_win,  # SMEM scratch (RUNW,) i32
    blk_ring,  # SMEM scratch (NBUF,) i32: block id resident in each buffer
    state,  # SMEM scratch (4,) i32: r_cur, r_pf, loaded runs-window id
    fp_buf,  # VMEM scratch (NBUF, BLOCK_GROUPS, GROUP_LANES) u32
    pl_buf,
    fetch_sem,  # DMA semaphores (NBUF, 2): fp / payload fetch per buffer
    flush_sem,  # DMA semaphores (NBUF, 2)
    win_sem,  # DMA semaphores (2,): meta / runs window loads
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = scal_ref[0]
    n_runs = scal_ref[1]
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK_GROUPS, GROUP_LANES), 0
    )
    lanes = jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK_GROUPS, GROUP_LANES), 1
    )
    nbuf = jnp.int32(NBUF)

    def load_runs_window(w):
        cp = pltpu.make_async_copy(
            runs_hbm.at[pl.ds(w * jnp.int32(RUNW), RUNW)],
            runs_win,
            win_sem.at[jnp.int32(1)],
        )
        cp.start()
        cp.wait()
        state[_R_WIN] = w

    def start_fetch(r):
        """Begin streaming run ``r``'s block into its ring buffer.  The
        caller guarantees runs_win holds ``r``'s window and the buffer's
        previous flush (if any) has been waited."""
        b = jax.lax.rem(r, nbuf)
        blk = runs_win[r - state[_R_WIN] * jnp.int32(RUNW)]
        blk_ring[b] = blk
        g0 = blk * jnp.int32(BLOCK_GROUPS)
        pltpu.make_async_copy(
            tfp_out.at[pl.ds(g0, BLOCK_GROUPS)],
            fp_buf.at[b],
            fetch_sem.at[b, jnp.int32(0)],
        ).start()
        pltpu.make_async_copy(
            tpl_out.at[pl.ds(g0, BLOCK_GROUPS)],
            pl_buf.at[b],
            fetch_sem.at[b, jnp.int32(1)],
        ).start()

    def wait_fetch(r):
        b = jax.lax.rem(r, nbuf)
        g0 = blk_ring[b] * jnp.int32(BLOCK_GROUPS)
        pltpu.make_async_copy(
            tfp_out.at[pl.ds(g0, BLOCK_GROUPS)],
            fp_buf.at[b],
            fetch_sem.at[b, jnp.int32(0)],
        ).wait()
        pltpu.make_async_copy(
            tpl_out.at[pl.ds(g0, BLOCK_GROUPS)],
            pl_buf.at[b],
            fetch_sem.at[b, jnp.int32(1)],
        ).wait()

    def start_flush(r):
        b = jax.lax.rem(r, nbuf)
        g0 = blk_ring[b] * jnp.int32(BLOCK_GROUPS)
        pltpu.make_async_copy(
            fp_buf.at[b],
            tfp_out.at[pl.ds(g0, BLOCK_GROUPS)],
            flush_sem.at[b, jnp.int32(0)],
        ).start()
        pltpu.make_async_copy(
            pl_buf.at[b],
            tpl_out.at[pl.ds(g0, BLOCK_GROUPS)],
            flush_sem.at[b, jnp.int32(1)],
        ).start()

    def wait_flush(r):
        b = jax.lax.rem(r, nbuf)
        g0 = blk_ring[b] * jnp.int32(BLOCK_GROUPS)
        pltpu.make_async_copy(
            fp_buf.at[b],
            tfp_out.at[pl.ds(g0, BLOCK_GROUPS)],
            flush_sem.at[b, jnp.int32(0)],
        ).wait()
        pltpu.make_async_copy(
            pl_buf.at[b],
            tpl_out.at[pl.ds(g0, BLOCK_GROUPS)],
            flush_sem.at[b, jnp.int32(1)],
        ).wait()

    def prefetch_next():
        """Issue at most one fetch, keeping ≤ NBUF-2 ahead of r_cur: the
        last slot of slack means run q+NBUF-1's refetch (which waits
        flush(q-1)) is issued one full run AFTER flush(q-1) started, so a
        flush is never waited in the same advance that issued it."""
        r_pf = state[_R_PF]

        @pl.when((r_pf < n_runs) & (r_pf < state[_R_CUR] + nbuf - jnp.int32(1)))
        def _():
            w = r_pf // jnp.int32(RUNW)

            @pl.when(w != state[_R_WIN])
            def _():
                load_runs_window(w)

            # the buffer's previous occupant (run r_pf - NBUF < r_cur) was
            # evicted earlier; its flush must land before the refetch
            @pl.when(r_pf >= nbuf)
            def _():
                wait_flush(r_pf - nbuf)

            start_fetch(r_pf)
            state[_R_PF] = r_pf + jnp.int32(1)

    def body(j, _):
        r = meta_win[0, j]

        @pl.when(r != state[_R_CUR])
        def _():
            # runs advance one at a time (every run has ≥1 candidate)
            start_flush(state[_R_CUR])
            state[_R_CUR] = r
            prefetch_next()
            wait_fetch(r)

        bi = jax.lax.rem(r, nbuf)
        shape = (BLOCK_GROUPS, GROUP_LANES)
        lo = jnp.full(shape, 0, jnp.int32) + meta_win[3, j]
        hi = jnp.full(shape, 0, jnp.int32) + meta_win[4, j]
        plo = jnp.full(shape, 0, jnp.int32) + meta_win[5, j]
        phi = jnp.full(shape, 0, jnp.int32) + meta_win[6, j]
        here = rows == meta_win[1, j]
        lane = meta_win[2, j]
        sel_lo = here & (lanes == 2 * lane)
        sel_hi = here & (lanes == 2 * lane + 1)
        fp_buf[bi] = jnp.where(
            sel_lo, lo.astype(jnp.uint32),
            jnp.where(sel_hi, hi.astype(jnp.uint32), fp_buf[bi]),
        )
        pl_buf[bi] = jnp.where(
            sel_lo, plo.astype(jnp.uint32),
            jnp.where(sel_hi, phi.astype(jnp.uint32), pl_buf[bi]),
        )
        return 0

    def window(w, _):
        cp = pltpu.make_async_copy(
            meta_hbm.at[:, pl.ds(w * jnp.int32(META_WINDOW), META_WINDOW)],
            meta_win,
            win_sem.at[jnp.int32(0)],
        )
        cp.start()
        cp.wait()
        count = jnp.minimum(n - w * jnp.int32(META_WINDOW),
                            jnp.int32(META_WINDOW))
        return jax.lax.fori_loop(0, count, body, 0)

    @pl.when(n > 0)
    def _():
        # initial fill: fetch the first min(n_runs, NBUF) runs, then block
        # only on run 0 (the rest stream in behind the VPU work)
        load_runs_window(jnp.int32(0))
        state[_R_CUR] = jnp.int32(0)
        state[_R_PF] = jnp.int32(0)

        def ifetch(r, _):
            start_fetch(r)
            state[_R_PF] = r + jnp.int32(1)
            return 0

        jax.lax.fori_loop(0, jnp.minimum(n_runs, nbuf - jnp.int32(1)), ifetch, 0)
        wait_fetch(jnp.int32(0))

        nwin = (n + jnp.int32(META_WINDOW - 1)) // jnp.int32(META_WINDOW)
        jax.lax.fori_loop(0, nwin, window, 0)

        # drain: flush the final resident block, then retire every DMA the
        # pipeline still has in flight (prefetched-but-unentered fetches;
        # flushes no refetch ever waited on)
        r_cur = state[_R_CUR]
        r_pf = state[_R_PF]
        start_flush(r_cur)

        def dfetch(r, _):
            wait_fetch(r)
            return 0

        jax.lax.fori_loop(r_cur + 1, r_pf, dfetch, 0)

        def dflush(r, _):
            wait_flush(r)
            return 0

        jax.lax.fori_loop(
            jnp.maximum(jnp.int32(0), r_pf - nbuf), r_cur + 1, dflush, 0
        )


def pallas_scatter_insert(
    table_fp,  # u64 [nslots]
    table_payload,  # u64 [nslots]
    tgt,  # i32 [M] target slot per candidate (nslots = invalid/pad)
    cfp,  # u64 [M] fingerprints, novel-compacted
    cpl,  # u64 [M]
    n_new,  # i32 scalar: number of valid candidates (prefix of the arrays)
):
    """Write ``cfp/cpl`` to ``tgt`` slots as one Pallas kernel invocation.
    Equivalent to (and validated against) the fp/payload windowed-scatter
    path in :func:`ops.buckets.bucket_insert`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nslots = table_fp.shape[0]
    # pad tiny tables up to one whole DMA block (larger-than-one-block
    # tables are already powers of two, hence multiples); padding copies,
    # but only on toy sizes — engine-scale tables alias in place
    spad = (-nslots) % BLOCK_SLOTS
    if spad:
        table_fp = jnp.concatenate(
            [table_fp, jnp.zeros((spad,), jnp.uint64)]
        )
        table_payload = jnp.concatenate(
            [table_payload, jnp.zeros((spad,), jnp.uint64)]
        )
    ngroups = table_fp.shape[0] // GROUP_SLOTS
    m = tgt.shape[0]

    # -- vector-side prep (cheap XLA) --------------------------------------
    # Sort by target slot: valid candidates (tgt < nslots) form a prefix
    # and their blocks are ascending AND unique-per-run, which is what lets
    # the kernel prefetch ahead without write-order hazards.  Distinct
    # target slots make the re-ordering semantically free.
    order = jnp.argsort(tgt)
    tgt = tgt[order]
    cfp = cfp[order]
    cpl = cpl[order]
    vmask = jnp.arange(m, dtype=jnp.int32) < n_new
    slot = jnp.minimum(tgt, nslots - 1)
    g = slot // GROUP_SLOTS
    block = g // BLOCK_GROUPS
    row = g - block * BLOCK_GROUPS
    lane = slot - g * GROUP_SLOTS
    # distinct-block runs over the valid prefix
    newrun = vmask & jnp.concatenate(
        [jnp.ones((1,), bool), block[1:] != block[:-1]]
    )
    run_idx = jnp.cumsum(newrun.astype(jnp.int32)) - 1
    n_runs = jnp.sum(newrun).astype(jnp.int32)
    # run r's block = block of its first candidate (monotone run_idx over
    # the valid prefix ⇒ a vectorized binary search finds the boundary)
    run_seq = jnp.where(vmask, run_idx, jnp.int32(m))
    first_of_run = jnp.minimum(
        jnp.searchsorted(
            run_seq, jnp.arange(m, dtype=jnp.int32), side="left"
        ).astype(jnp.int32),
        jnp.int32(m - 1),
    )
    run_blocks = block[first_of_run].astype(jnp.int32)
    rpad = (-m) % RUNW
    if rpad:
        run_blocks = jnp.concatenate(
            [run_blocks, jnp.zeros((rpad,), jnp.int32)]
        )

    f32 = jax.lax.bitcast_convert_type(cfp, jnp.uint32).astype(jnp.int32)
    p32 = jax.lax.bitcast_convert_type(cpl, jnp.uint32).astype(jnp.int32)
    zero = jnp.zeros((m,), jnp.int32)
    # transposed layout [META_ROWS, M]: the kernel DMA-streams fixed-width
    # column windows, and a full-height slice keeps every window tile-aligned
    meta = jnp.stack(
        [
            jnp.where(vmask, run_idx, -1),
            row,
            lane,
            f32[:, 0],
            f32[:, 1],
            p32[:, 0],
            p32[:, 1],
            zero,
        ],
        axis=0,
    ).astype(jnp.int32)
    mpad = (-m) % META_WINDOW
    if mpad:
        pad = jnp.full((META_ROWS, mpad), -1, jnp.int32)
        meta = jnp.concatenate([meta, pad], axis=1)

    tfp32 = jax.lax.bitcast_convert_type(table_fp, jnp.uint32).reshape(
        ngroups, GROUP_LANES
    )
    tpl32 = jax.lax.bitcast_convert_type(table_payload, jnp.uint32).reshape(
        ngroups, GROUP_LANES
    )

    out_fp, out_pl = pl.pallas_call(
        _insert_kernel,
        out_shape=[
            jax.ShapeDtypeStruct(tfp32.shape, jnp.uint32),
            jax.ShapeDtypeStruct(tpl32.shape, jnp.uint32),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.SMEM((META_ROWS, META_WINDOW), jnp.int32),
            pltpu.SMEM((RUNW,), jnp.int32),
            pltpu.SMEM((NBUF,), jnp.int32),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((NBUF, BLOCK_GROUPS, GROUP_LANES), jnp.uint32),
            pltpu.VMEM((NBUF, BLOCK_GROUPS, GROUP_LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((NBUF, 2)),
            pltpu.SemaphoreType.DMA((NBUF, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret_mode(),
    )(
        jnp.stack([n_new.astype(jnp.int32), n_runs]).reshape(2),
        meta,
        run_blocks,
        tfp32,
        tpl32,
    )
    padded = nslots + spad
    table_fp = jax.lax.bitcast_convert_type(
        out_fp.reshape(padded, 2), jnp.uint64
    ).reshape(padded)[:nslots]
    table_payload = jax.lax.bitcast_convert_type(
        out_pl.reshape(padded, 2), jnp.uint64
    ).reshape(padded)[:nslots]
    return table_fp, table_payload

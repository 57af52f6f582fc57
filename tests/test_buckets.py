"""Bucketized visited-set unit tests: the one-shot insert must agree with a
straightforward host-side set on arbitrary candidate streams (duplicates
in-batch, duplicates vs the table, EMPTY lanes, bucket collisions)."""

import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.analysis.jaxpr_audit import _iter_eqns
from stateright_tpu.ops.buckets import (
    ROW_LANES,
    SLOTS,
    bucket_insert,
    bucket_key,
    bucket_of,
    bucket_split,
    host_bucket_rehash,
    lane_compact,
    occupancy_stats,
)
from stateright_tpu.ops.hashing import EMPTY, mix64_np, unmix64

sys.path.insert(0, str(Path(__file__).parent))
from test_paxos_tensor import gather_call_sites  # noqa: E402


def np_u64(x):
    return np.asarray(x, np.uint64)


def fresh(nbuckets):
    return (
        jnp.full((nbuckets * SLOTS,), EMPTY, jnp.uint64),
        jnp.zeros((nbuckets * SLOTS,), jnp.uint64),
    )


def insert(state, fps, payloads=None, window=8, compact=None):
    tfp, tpl = state
    fps = jnp.asarray(np_u64(fps))
    if payloads is None:
        payloads = fps ^ jnp.uint64(7)
    else:
        payloads = jnp.asarray(np_u64(payloads))
    tfp, tpl, sel, n_new, overflow, cand_overflow = bucket_insert(
        tfp, tpl, fps, payloads, window=window, compact=compact
    )
    inserted = np.asarray(fps)[np.asarray(sel)][: int(n_new)]
    return (
        (tfp, tpl),
        inserted,
        int(n_new),
        bool(overflow) or bool(cand_overflow),
    )


def table_contents(state):
    tfp, tpl = state
    tfp, tpl = np.asarray(tfp), np.asarray(tpl)
    occ = tfp != EMPTY
    return dict(zip(tfp[occ].tolist(), tpl[occ].tolist()))


def same_bucket_fps(count, nbuckets):
    """``count`` distinct fingerprints the derivation places in bucket 0."""
    fps, x = [], 1
    while len(fps) < count:
        if int(bucket_of(np.uint64(x), nbuckets)) == 0:
            fps.append(x)
        x += 1
    return fps


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_stream_matches_host_set(seed):
    rng = np.random.default_rng(seed)
    nbuckets = 64
    state = fresh(nbuckets)
    seen = {}
    for _ in range(20):
        m = int(rng.integers(1, 50))
        fps = rng.integers(1, 1 << 40, m).astype(np.uint64)
        # salt in EMPTY lanes and in-batch duplicates
        fps[rng.random(m) < 0.2] = EMPTY
        if m > 3:
            fps[0] = fps[m // 2]
        pay = rng.integers(1, 1 << 40, m).astype(np.uint64)
        state, inserted, n_new, overflow = insert(state, fps, pay)
        assert not overflow
        expected_new = []
        batch_seen = set()
        for f, p in zip(fps.tolist(), pay.tolist()):
            if f == int(EMPTY) or f in seen or f in batch_seen:
                continue
            batch_seen.add(f)
            expected_new.append(f)
            seen[f] = None  # payload: first writer in *sorted* order wins
        assert n_new == len(expected_new)
        assert sorted(inserted.tolist()) == sorted(expected_new)
    contents = table_contents(state)
    assert sorted(contents) == sorted(int(k) for k in seen)


def test_payloads_stored_for_novel_entries():
    state = fresh(16)
    state, _, n_new, _ = insert(state, [10, 20, 30], [1, 2, 3])
    assert n_new == 3
    assert table_contents(state) == {10: 1, 20: 2, 30: 3}
    # duplicates keep the original payload
    state, _, n_new, _ = insert(state, [20, 40], [99, 4])
    assert n_new == 1
    assert table_contents(state) == {10: 1, 20: 2, 30: 3, 40: 4}


def test_bucket_overflow_is_clean():
    nbuckets = 4
    # SLOTS+1 distinct fps the mix64 derivation places in the SAME bucket
    fps = same_bucket_fps(SLOTS + 1, nbuckets=nbuckets)
    state = fresh(nbuckets)
    state, _, n_new, overflow = insert(state, fps)
    assert overflow
    # nothing was written: the table is untouched
    assert table_contents(state) == {}


def test_window_chunking_covers_large_batches():
    state = fresh(1 << 10)
    fps = np.arange(1, 401, dtype=np.uint64) * 97
    state, inserted, n_new, overflow = insert(state, fps, window=32)
    assert not overflow and n_new == 400
    assert sorted(table_contents(state)) == sorted(fps.tolist())


@pytest.mark.parametrize(
    "seed", [pytest.param(0, marks=pytest.mark.medium), 1]
)
def test_compacted_stream_matches_host_set(seed):
    """``compact=CB`` (the engines' padded-batch fast path) must agree with
    the host set exactly, including EMPTY-heavy lanes, in-batch duplicates,
    and duplicates vs the table."""
    rng = np.random.default_rng(seed)
    state = fresh(64)
    seen = set()
    for _ in range(12):
        m = int(rng.integers(8, 80))
        fps = rng.integers(1, 1 << 40, m).astype(np.uint64)
        fps[rng.random(m) < 0.7] = EMPTY  # mostly padding, like a batch
        if m > 3:
            fps[0] = fps[m // 2]
        state, inserted, n_new, overflow = insert(
            state, fps, window=8, compact=32
        )
        assert not overflow
        expected = [
            f for i, f in enumerate(fps.tolist())
            if f != int(EMPTY) and f not in seen
            and f not in set(fps[:i].tolist())
        ]
        assert n_new == len(expected)
        assert sorted(inserted.tolist()) == sorted(expected)
        seen.update(expected)
    assert sorted(table_contents(state)) == sorted(seen)


def test_cand_overflow_writes_nothing():
    """More valid candidates than the compaction budget: atomically refuse
    (nothing written, n_new 0) so the caller can grow + replay."""
    state = fresh(1 << 6)
    fps = np.arange(1, 41, dtype=np.uint64) * 97  # 40 valid > compact=16
    state, inserted, n_new, overflow = insert(
        state, fps, window=8, compact=16
    )
    assert overflow and n_new == 0 and len(inserted) == 0
    assert table_contents(state) == {}
    # and the same stream succeeds once the budget covers it
    state, _, n_new, overflow = insert(state, fps, window=8, compact=64)
    assert not overflow and n_new == 40


def test_compacted_generation_order_is_preserved():
    """generation_order=True with compaction: sel[:n_new] lists inserted
    candidates by ORIGINAL batch position (symmetry runs key on it)."""
    state = fresh(64)
    fps = np.array(
        [int(EMPTY), 901, int(EMPTY), 17, 445, int(EMPTY), 23], np.uint64
    )
    tfp, tpl = state
    tfp, tpl, sel, n_new, ofl, cofl = bucket_insert(
        tfp,
        tpl,
        jnp.asarray(fps),
        jnp.asarray(fps),
        window=4,
        generation_order=True,
        compact=4,
    )
    assert not bool(ofl) and not bool(cofl) and int(n_new) == 4
    assert np.asarray(sel)[:4].tolist() == [1, 3, 4, 6]


def test_host_rehash_round_trip():
    state = fresh(16)  # max per-bucket load for this stream is 13 < SLOTS
    fps = (np.arange(1, 200, dtype=np.uint64) * 1315423911) & np.uint64(
        (1 << 50) - 1
    )
    fps = np.unique(fps)
    state, _, n_new, overflow = insert(state, fps, window=64)
    assert not overflow
    before = table_contents(state)
    tfp, tpl = host_bucket_rehash(
        np.asarray(state[0]), np.asarray(state[1]), 32
    )
    occ = tfp != EMPTY
    after = dict(zip(tfp[occ].tolist(), tpl[occ].tolist()))
    assert after == before
    # slots fill densely per bucket (occupancy implicit in the table)
    lines = tfp.reshape(32, SLOTS) != EMPTY
    filled = lines.sum(axis=1)
    assert all(lines[b, :filled[b]].all() for b in range(32))
    # and the rehashed table keeps accepting inserts consistently
    state2 = (jnp.asarray(tfp), jnp.asarray(tpl))
    state2, _, n_new2, _ = insert(state2, [123456789, int(fps[0])])
    assert n_new2 == 1


# ---------------------------------------------------------------------------
# growth where the table lies (PR 48): bucket_split == host_bucket_rehash
# ---------------------------------------------------------------------------

SPLIT_FROM = 64  # buckets of the table a split starts from


def dense_table(fps, nbuckets):
    """The ``nbuckets``-bucket table that holds ``fps`` (distinct; no more
    than ``SLOTS`` a bucket), filled densely in their order as the insert
    fills it, payloads derived from the fingerprints."""
    fps = np_u64(fps)
    return host_bucket_rehash(fps, fps ^ np.uint64(0x5A5A), nbuckets)


def split_case(kind):
    rng = np.random.default_rng(48)
    if kind == "empty":
        fps = []
    elif kind == "load25":
        fps = np.unique(
            rng.integers(1, 1 << 63, SPLIT_FROM * SLOTS // 4, dtype=np.uint64)
        )
        rng.shuffle(fps)
    elif kind == "full_buckets":
        # several buckets at SLOTS entries, the rest sparse: what a table
        # looks like when one bucket's overflow is what asked for the growth
        draw = rng.integers(1, 1 << 63, 40_000, dtype=np.uint64)
        home = bucket_of(draw, SPLIT_FROM)
        fps = np.concatenate(
            [draw[home == b][:SLOTS] for b in (0, 7, 31, SPLIT_FROM - 1)]
            + [draw[home == b][:3] for b in (1, 8, 30)]
        )
        assert fps.size == 4 * SLOTS + 9
        rng.shuffle(fps)
    else:
        # the fingerprints whose key is, or collides with, EMPTY's remap:
        # mix64(fp) == EMPTY is keyed EMPTY - 1, as is the one fingerprint
        # that mixes to EMPTY - 1 itself; both live in the LAST bucket,
        # beside the keys just below them
        assert kind == "empty_remap"
        top = int(EMPTY)
        fps = np.asarray(
            unmix64(jnp.asarray(np_u64([top - i for i in range(6)])))
        )
        assert (bucket_of(fps, 1 << 20) == (1 << 20) - 1).all()
    return dense_table(fps, SPLIT_FROM)


@pytest.mark.parametrize(
    "kind", ["empty", "load25", "full_buckets", "empty_remap"]
)
@pytest.mark.parametrize("factor", [2, 4, 8])
def test_bucket_split_equals_host_rehash_bit_for_bit(factor, kind):
    """The device's growth transform against the host's, both arrays and
    every slot: a split is the stable partition of each bucket by the next
    key bits, which is what the host's stable argsort over entries in
    table order leaves.  The histogram it returns describes the new table
    as ``occupancy_stats`` describes the pulled one."""
    tfp, tpl = split_case(kind)
    want_fp, want_pl = host_bucket_rehash(tfp, tpl, SPLIT_FROM * factor)
    got_fp, got_pl, hist = bucket_split(
        jnp.asarray(tfp), jnp.asarray(tpl), new_nbuckets=SPLIT_FROM * factor
    )
    np.testing.assert_array_equal(np.asarray(got_fp), want_fp)
    np.testing.assert_array_equal(np.asarray(got_pl), want_pl)
    assert got_fp.dtype == jnp.uint64 and got_pl.dtype == jnp.uint64
    assert np.asarray(hist).tolist() == occupancy_stats(want_fp)["histogram"]


def test_bucket_split_compiles_to_no_sort_scatter_or_gather():
    """The compile-time fence: a TPU sort costs ~10 s of compile an
    operand past 16,384 lanes (PR 36), and a table-wide scatter or gather
    7-22 ns a lane; the split is compares, selects and reductions along a
    16-lane axis alone - in the module as lowered and as compiled."""
    tfp, tpl = fresh(SPLIT_FROM)

    def fenced_ops(lowered):
        return {
            op
            for text in (lowered.as_text(), lowered.compile().as_text())
            for op in re.findall(r"\b(sort|scatter|gather|while)\b", text)
        }

    assert not fenced_ops(
        bucket_split.lower(tfp, tpl, new_nbuckets=SPLIT_FROM * 4)
    )
    # the fence sees what it is there to stop
    control = jax.jit(lambda x: jnp.sort(x).at[jnp.argsort(x)].set(x))
    assert fenced_ops(control.lower(tfp)) == {"sort", "scatter"}
    control = jax.jit(lambda x: x[jnp.argsort(x)])
    assert fenced_ops(control.lower(tfp)) == {"sort", "gather"}

# ---------------------------------------------------------------------------
# the bucket-mix fix (ROADMAP table-size anomaly): avalanche + chi-square
# ---------------------------------------------------------------------------


def test_mix64_avalanche():
    """Flipping any single input bit must flip ~half the output bits of the
    remix the bucket derivation reads (mean avalanche weight near 32, and
    every input bit must propagate into the TOP bits, where the bucket
    lives — the raw low-bit derivation failed exactly this)."""
    rng = np.random.default_rng(7)
    xs = rng.integers(0, 1 << 63, 256, dtype=np.uint64)
    base = mix64_np(xs)
    top16 = np.uint64(0xFFFF_0000_0000_0000)
    for bit in range(64):
        flipped = mix64_np(xs ^ np.uint64(1 << bit))
        diff = base ^ flipped
        # mean bits flipped across samples, whole word and top-16 slice
        weights = np.array([bin(int(d)).count("1") for d in diff])
        assert 24 <= weights.mean() <= 40, (bit, weights.mean())
        top = np.array([bin(int(d & top16)).count("1") for d in diff])
        assert top.mean() >= 4, (bit, top.mean())  # ~8 expected of 16


@pytest.mark.parametrize(
    "stream",
    [
        np.arange(1, (1 << 14) + 1, dtype=np.uint64),  # dense counter
        np.arange(1, (1 << 14) + 1, dtype=np.uint64) * np.uint64(97),
        (np.arange(1, (1 << 14) + 1, dtype=np.uint64) << np.uint64(12)),
    ],
    ids=["counter", "strided", "shifted"],
)
def test_bucket_chi_square_on_structured_streams(stream):
    """The bucket derivation must spread STRUCTURED fingerprint streams
    uniformly: chi-square over 256 buckets at 64 expected per bucket.  The
    pre-fix low-bit derivation fails all three of these catastrophically
    (the dense counter puts everything in 256 consecutive buckets of the
    fingerprint's low bits)."""
    nbuckets = 256
    counts = np.bincount(bucket_of(stream, nbuckets), minlength=nbuckets)
    expect = stream.size / nbuckets
    chi2 = float(((counts - expect) ** 2 / expect).sum())
    # df = 255: mean 255, sd ~22.6; 400 is a > 6-sigma ceiling
    assert chi2 < 400.0, chi2
    # and no bucket anywhere near a SLOTS-deep pile-up at this load
    assert counts.max() < 2 * expect


# -- intra-window pre-dedup (ops/buckets.window_unique) -----------------------


def test_window_unique_keeps_first_occurrence_and_empty_lanes():
    from stateright_tpu.ops.buckets import window_unique

    fps = np_u64([5, EMPTY, 9, 5, 7, 9, 5, EMPTY])
    out = np.asarray(window_unique(jnp.asarray(fps)))
    # first occurrence (lowest lane) survives; later copies become EMPTY
    assert out.tolist() == np_u64(
        [5, EMPTY, 9, EMPTY, 7, EMPTY, EMPTY, EMPTY]
    ).tolist()


@pytest.mark.parametrize("seed", [0, 1])
def test_window_unique_then_insert_is_bit_identical(seed):
    """The equivalence contract behind the engines' prededup flag: running
    ``bucket_insert`` on a pre-deduped window must produce the identical
    table, payloads, n_new, and selected prefix — in BOTH compaction
    orders — because the filter keeps exactly the lane the insert's
    stable sort would have kept."""
    from stateright_tpu.ops.buckets import window_unique

    rng = np.random.default_rng(seed)
    fps = rng.integers(1, 50, size=256, dtype=np.uint64)  # heavy duplication
    fps[rng.random(256) < 0.3] = np.uint64(EMPTY)
    payloads = np_u64(np.arange(1, 257))
    for generation_order in (False, True):
        for compact in (None, 224):  # budget sized so neither side overflows
            tfp0, tpl0 = fresh(16)
            plain = bucket_insert(
                tfp0, tpl0, jnp.asarray(fps), jnp.asarray(payloads),
                window=32, generation_order=generation_order,
                compact=compact,
            )
            tfp1, tpl1 = fresh(16)
            dedup = bucket_insert(
                tfp1, tpl1, window_unique(jnp.asarray(fps)),
                jnp.asarray(payloads), window=32,
                generation_order=generation_order, compact=compact,
            )
            assert not bool(plain[5]) and not bool(dedup[5])  # no cand ovfl
            assert int(plain[3]) == int(dedup[3])  # n_new
            n = int(plain[3])
            assert np.array_equal(np.asarray(plain[0]), np.asarray(dedup[0]))
            assert np.array_equal(np.asarray(plain[1]), np.asarray(dedup[1]))
            assert np.array_equal(
                np.asarray(plain[2])[:n], np.asarray(dedup[2])[:n]
            )  # the consumed sel prefix
            assert not bool(plain[4]) and not bool(dedup[4])


def test_window_unique_shrinks_candidate_pressure():
    """The point of the filter: a duplicate-heavy window that cand-
    overflows a tight compaction budget FITS once pre-deduped (fewer
    growth/replay events on the engines, never more)."""
    from stateright_tpu.ops.buckets import window_unique

    rng = np.random.default_rng(3)
    fps = rng.integers(1, 33, size=256, dtype=np.uint64)  # ~32 unique
    payloads = np_u64(np.arange(1, 257))
    tfp, tpl = fresh(16)
    plain = bucket_insert(
        tfp, tpl, jnp.asarray(fps), jnp.asarray(payloads),
        window=32, compact=64,
    )
    assert bool(plain[5]) and int(plain[3]) == 0  # overflowed, wrote nothing
    tfp, tpl = fresh(16)
    dedup = bucket_insert(
        tfp, tpl, window_unique(jnp.asarray(fps)), jnp.asarray(payloads),
        window=32, compact=64,
    )
    assert not bool(dedup[5]) and int(dedup[3]) > 0


# -- BLEST one-hot membership probe (ops/mxu.py; docs/roofline.md) ------------


def _insert_all(state, fps, payloads, probe_dot, window=8):
    tfp, tpl = state
    return bucket_insert(
        tfp, tpl, jnp.asarray(np_u64(fps)), jnp.asarray(np_u64(payloads)),
        window=window, probe_dot=probe_dot,
    )


# all seeds ride the daily tiers: a 20-window random-stream sweep is
# integration-shaped fuzzing, not a fast-tier unit pin (870s budget)
@pytest.mark.parametrize(
    "seed",
    [pytest.param(0, marks=pytest.mark.medium),
     pytest.param(1, marks=pytest.mark.slow),
     pytest.param(2, marks=pytest.mark.slow)],
)
def test_blest_probe_matches_bucket_insert_on_random_streams(seed):
    """``probe_dot=True`` must be a pure op-class recast: every output of
    ``bucket_insert`` — table fingerprints, payloads, sel, n_new, both
    overflow flags — bit-identical across 20 random windows salted with
    EMPTY lanes and in-batch duplicates, tables evolved independently."""
    rng = np.random.default_rng(seed)
    nbuckets = 32
    plain, dotted = fresh(nbuckets), fresh(nbuckets)
    for _ in range(20):
        m = int(rng.integers(1, 48))
        fps = rng.integers(1, 1 << 40, m).astype(np.uint64)
        fps[rng.random(m) < 0.25] = EMPTY
        if m > 3:
            fps[0] = fps[m // 2]  # in-batch duplicate
        pay = rng.integers(1, 1 << 40, m).astype(np.uint64)
        a = _insert_all(plain, fps, pay, probe_dot=False)
        b = _insert_all(dotted, fps, pay, probe_dot=True)
        plain, dotted = (a[0], a[1]), (b[0], b[1])
        assert int(a[3]) == int(b[3])  # n_new
        n = int(a[3])
        assert np.array_equal(
            np.asarray(a[2])[:n], np.asarray(b[2])[:n]
        )  # consumed sel prefix
        assert bool(a[4]) == bool(b[4]) and bool(a[5]) == bool(b[5])
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_blest_probe_full_bucket_overflow_parity():
    """A bucket driven past SLOTS must overflow identically (flag on and
    off) and leave both tables untouched."""
    nbuckets = 4
    fps = same_bucket_fps(SLOTS + 1, nbuckets=nbuckets)
    pay = list(range(1, len(fps) + 1))
    a = _insert_all(fresh(nbuckets), fps, pay, probe_dot=False)
    b = _insert_all(fresh(nbuckets), fps, pay, probe_dot=True)
    assert bool(a[4]) and bool(b[4])  # both overflow
    assert int(a[3]) == int(b[3]) == 0
    assert table_contents((a[0], a[1])) == table_contents((b[0], b[1])) == {}
    # and a FULL-but-not-overfull bucket still probes exactly
    a = _insert_all(fresh(nbuckets), fps[:SLOTS], pay[:SLOTS], probe_dot=False)
    b = _insert_all(fresh(nbuckets), fps[:SLOTS], pay[:SLOTS], probe_dot=True)
    assert not bool(a[4]) and not bool(b[4])
    assert int(a[3]) == int(b[3]) == SLOTS
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    # re-probing the full bucket classifies every candidate a duplicate
    a2 = _insert_all((a[0], a[1]), fps[:SLOTS], pay[:SLOTS], probe_dot=False)
    b2 = _insert_all((b[0], b[1]), fps[:SLOTS], pay[:SLOTS], probe_dot=True)
    assert int(a2[3]) == int(b2[3]) == 0 and not bool(b2[4])


def test_blest_probe_unit_matches_reduction_pair():
    """:func:`ops.mxu.blest_probe` against the reduce_or/reduce_sum pair
    it replaces, on a hand-built line window: EMPTY lanes, full lines,
    absent and present fingerprints."""
    from stateright_tpu.ops.mxu import blest_probe

    E = np.uint64(EMPTY)
    lines = np_u64([
        [E] * SLOTS,                              # empty line
        [7] + [E] * (SLOTS - 1),                  # singleton, hit
        [7] + [E] * (SLOTS - 1),                  # singleton, miss
        list(range(100, 100 + SLOTS)),            # full line, hit at end
        list(range(200, 200 + SLOTS)),            # full line, miss
    ])
    wfp = np_u64([3, 7, 9, 100 + SLOTS - 1, 5])
    p, b = blest_probe(jnp.asarray(lines), jnp.asarray(wfp), EMPTY)
    p, b = np.asarray(p), np.asarray(b)
    exp_p = np.any(lines == wfp[:, None], axis=-1)
    exp_b = np.sum(lines != E, axis=-1).astype(np.int32)
    assert np.array_equal(p, exp_p) and p.tolist() == [
        False, True, False, True, False
    ]
    assert np.array_equal(b, exp_b) and b.tolist() == [
        0, 1, 1, SLOTS, SLOTS
    ]


# --- lane_compact: one packed-key sort (PR 25) -------------------------------
#
# The compaction used to be cumsum + searchsorted(running count, 1..width):
# `width` binary searches, ~17 dependent gather rounds on the TPU.  The old
# formulation lives on here, as the reference the new one is held to.


def ref_lane_compact(mask, width):
    """The pre-PR-25 compaction, verbatim."""
    m = mask.shape[0]
    csum = jnp.cumsum(mask.astype(jnp.int32))
    count = csum[m - 1]
    idx = jnp.searchsorted(
        csum, jnp.arange(1, width + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    idx = jnp.minimum(idx, jnp.int32(m - 1))
    live = jnp.arange(width, dtype=jnp.int32) < count
    return idx, live, count


def share_mask(m, share, seed=0):
    rng = np.random.default_rng(seed)
    if share == "none":
        return np.zeros(m, bool)
    if share == "one":
        mask = np.zeros(m, bool)
        mask[int(rng.integers(m))] = True
        return mask
    if share == "all":
        return np.ones(m, bool)
    return rng.random(m) < share


SMALL_M = 257  # not a multiple of anything: no padding luck
CELL_SHAPES = {  # (batch * actions, candidate budget) of the benchmark's cells
    "twopc8-presized": (86016, 32768),
    "paxos3-presized": (122880, 16384),
    "paxos3-defaults": (61440, 8192),
}


@pytest.mark.parametrize(
    "m,width,share",
    [
        (SMALL_M, width, share)
        for width in (1, SMALL_M // 4, SMALL_M - 1, SMALL_M)
        for share in ("none", "one", 0.1, 0.5, "all")
    ]
    + [
        (m, width, share)
        for (m, width) in CELL_SHAPES.values()
        for share in (0.1, 0.5)  # 0.5 is over every cell's budget
    ],
)
def test_lane_compact_matches_cumsum_searchsorted(m, width, share):
    """Same ``(idx, live, count)`` as the old formulation on the live
    lanes; dead lanes stay in range (they are gathered, then masked)."""
    mask = jnp.asarray(share_mask(m, share, seed=m + width))
    idx, live, count = lane_compact(mask, width)
    ridx, rlive, rcount = ref_lane_compact(mask, width)
    assert idx.dtype == jnp.int32 and idx.shape == (width,)
    assert int(count) == int(rcount) == int(np.sum(np.asarray(mask)))
    live, rlive = np.asarray(live), np.asarray(rlive)
    assert np.array_equal(live, rlive)
    idx, ridx = np.asarray(idx), np.asarray(ridx)
    assert np.array_equal(idx[live], ridx[rlive])
    assert np.array_equal(idx[live], np.flatnonzero(np.asarray(mask))[:width])
    assert np.all((idx >= 0) & (idx < m))


@pytest.mark.parametrize("generation_order", [False, True])
@pytest.mark.parametrize("n_valid", ["under", "exact", "over"])
def test_compacted_insert_is_bit_identical_to_reference_pipeline(
    n_valid, generation_order
):
    """``bucket_insert(compact=CB)`` against the same insert fed by the OLD
    compaction by hand: tables, ``sel[:n_new]``, ``n_new`` and both overflow
    flags bit-identical; over the budget, nothing written."""
    cb, m = 32, 96
    rng = np.random.default_rng(7)
    k = {"under": 20, "exact": cb, "over": cb + 9}[n_valid]
    fps = np.full(m, EMPTY, np.uint64)
    lanes = np.sort(rng.choice(m, k, replace=False))
    fps[lanes] = rng.integers(1, 1 << 40, k).astype(np.uint64)
    fps[lanes[3]] = fps[lanes[0]]  # an in-batch duplicate
    pls = np.arange(1000, 1000 + m, dtype=np.uint64)
    tfp0, tpl0 = fresh(64)
    # something already in the table, one of them a candidate again
    tfp0, tpl0, *_ = bucket_insert(
        tfp0, tpl0, jnp.asarray(np_u64([5, 6, int(fps[lanes[1]])])),
        jnp.asarray(np_u64([1, 2, 3])), window=8,
    )
    fps_j, pls_j = jnp.asarray(fps), jnp.asarray(pls)

    tfp, tpl, sel, n_new, ovf, covf = bucket_insert(
        tfp0, tpl0, fps_j, pls_j, window=8,
        generation_order=generation_order, compact=cb,
    )

    cidx, live, count = ref_lane_compact(fps_j != EMPTY, cb)
    assert bool(covf) == (int(count) > cb) == (n_valid == "over")
    if n_valid == "over":
        assert int(n_new) == 0
        assert np.array_equal(np.asarray(tfp), np.asarray(tfp0))
        assert np.array_equal(np.asarray(tpl), np.asarray(tpl0))
        return
    rtfp, rtpl, rsel, rn_new, rovf, _ = bucket_insert(
        tfp0, tpl0, jnp.where(live, fps_j[cidx], EMPTY), pls_j[cidx],
        window=8, generation_order=generation_order,
    )
    rsel = cidx[rsel]
    n = int(n_new)
    assert n == int(rn_new) and n > 0
    assert bool(ovf) == bool(rovf) is False
    assert np.array_equal(np.asarray(sel)[:n], np.asarray(rsel)[:n])
    assert np.array_equal(np.asarray(tfp), np.asarray(rtfp))
    assert np.array_equal(np.asarray(tpl), np.asarray(rtpl))
    if generation_order:
        assert np.all(np.diff(np.asarray(sel)[:n]) > 0)


# --- bucket_insert carries its values through its sorts (PR 36) ---------------
#
# The insert used to fetch every value AFTER a sort by the permutation the sort
# produced: ``x[order]``, ``x[perm]``, ``x[cidx]`` - eighteen element gathers at
# the candidate width, 3.6 s of an 8.76 s 2pc-8 check on the chip.  The old body
# lives on here, verbatim, as the reference the new one is held to bit for bit.


def ref_bucket_insert(
    table_fp, table_payload, fps, payloads, window,
    generation_order=False, compact=None, probe_dot=False,
):
    """The pre-PR-36 ``bucket_insert`` body, verbatim."""
    m_orig = fps.shape[0]
    cand_overflow = jnp.bool_(False)
    cidx = None
    if compact is not None and compact < m_orig:
        cidx, live, n_valid_orig = lane_compact(fps != EMPTY, compact)
        cand_overflow = n_valid_orig > jnp.int32(compact)
        fps = jnp.where(live, fps[cidx], EMPTY)
        payloads = payloads[cidx]  # dead lanes masked by the EMPTY fp above
    m = fps.shape[0]
    window = min(window, m)
    nslots = table_fp.shape[0]
    nbuckets = nslots // SLOTS
    assert nbuckets & (nbuckets - 1) == 0, "bucket count must be a power of two"
    bucket_bits = int(nbuckets).bit_length() - 1

    key = bucket_key(fps)
    order = jnp.argsort(key)
    sfp = fps[order]
    skey = key[order]
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
    table_lines = table_fp.reshape(nbuckets, SLOTS)
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
        lines = table_lines[wbkt]
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

    # per-bucket insertion rank among this batch's novel candidates
    idx = jnp.arange(m, dtype=jnp.int32)
    bstart = jnp.concatenate([jnp.ones((1,), bool), bucket[1:] != bucket[:-1]])
    seg_start = jax.lax.cummax(jnp.where(bstart, idx, 0))
    csum = jnp.cumsum(novel.astype(jnp.int32))
    rank = jnp.where(novel, csum - 1 - (csum - novel)[seg_start], 0)
    # (csum - novel)[seg_start] = novel-count before the bucket's first row

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
    if generation_order:
        keys = jnp.where(novel, order.astype(jnp.int32), jnp.int32(m))
    else:
        keys = jnp.where(novel, idx, jnp.int32(m))
    perm = jnp.argsort(keys)
    tgt = jnp.where(novel, bucket * SLOTS + slot, nslots)[perm]
    cfp = sfp[perm]
    cpl = payloads[order][perm]

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

    ptgt = padded(tgt, nslots)
    pcfp = padded(cfp, EMPTY)
    pcpl = padded(cpl, 0)

    def chunk_body(state):
        k, tfp, tpl = state
        off = k * window
        t = jax.lax.dynamic_slice(ptgt, (off,), (window,))
        f = jax.lax.dynamic_slice(pcfp, (off,), (window,))
        p = jax.lax.dynamic_slice(pcpl, (off,), (window,))
        in_range = jnp.arange(window, dtype=jnp.int32) + off < n_new
        t = jnp.where(in_range, t, nslots)
        tfp = tfp.at[t].set(f, mode="drop")
        tpl = tpl.at[t].set(p, mode="drop")
        return k + 1, tfp, tpl

    _, table_fp, table_payload = jax.lax.while_loop(
        chunk_cond, chunk_body, (jnp.int32(0), table_fp, table_payload)
    )

    sel = order[perm]
    if cidx is not None:
        sel = cidx[sel]  # map compacted positions back to original indices
    return table_fp, table_payload, sel, n_new, overflow, cand_overflow


REF_M, REF_CB, REF_WINDOW, REF_NBUCKETS = 101, 32, 7, 64  # 7 divides neither


def jitted(fn):
    return jax.jit(fn, static_argnames=("window", "generation_order", "compact"))


INSERTS = {"new": jitted(bucket_insert), "ref": jitted(ref_bucket_insert)}


def seeded_table(in_table):
    tfp, tpl = fresh(REF_NBUCKETS)
    fps = jnp.asarray(np_u64([5, 6] + list(in_table)))
    tfp, tpl, *_ = bucket_insert(tfp, tpl, fps, fps + jnp.uint64(1), window=8)
    return tfp, tpl


def both_inserts(tfp0, tpl0, fps, pls, generation_order, compact,
                 window=REF_WINDOW):
    out = {
        name: fn(
            tfp0, tpl0, jnp.asarray(fps), jnp.asarray(pls), window=window,
            generation_order=generation_order, compact=compact,
        )
        for name, fn in INSERTS.items()
    }
    (tfp, tpl, sel, n_new, ovf, covf), (rtfp, rtpl, rsel, rn_new, rovf, rcovf) = (
        out["new"], out["ref"]
    )
    n = int(n_new)
    assert n == int(rn_new)
    assert bool(ovf) == bool(rovf) and bool(covf) == bool(rcovf)
    # (the old body's ``sel`` was int64 without a budget: ``argsort``'s dtype)
    assert sel.dtype == jnp.int32 and sel.shape == rsel.shape
    assert np.array_equal(np.asarray(sel)[:n], np.asarray(rsel)[:n])
    assert np.all((np.asarray(sel) >= 0) & (np.asarray(sel) < len(fps)))
    assert np.array_equal(np.asarray(tfp), np.asarray(rtfp))
    assert np.array_equal(np.asarray(tpl), np.asarray(rtpl))
    if bool(ovf) or bool(covf):  # nothing written
        assert n == 0
        assert np.array_equal(np.asarray(tfp), np.asarray(tfp0))
        assert np.array_equal(np.asarray(tpl), np.asarray(tpl0))
    return out["new"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("budget", ["none", "under", "exact", "over"])
@pytest.mark.parametrize("generation_order", [False, True])
def test_insert_is_bit_identical_to_the_gathering_reference(
    generation_order, budget, seed
):
    """Tables, ``sel[:n_new]``, ``n_new`` and both flags equal to the old
    body's, with in-batch duplicates (the LOWEST lane wins: its payload is
    the one stored), candidates already in the table, EMPTY lanes and a
    ``window`` that divides neither ``m`` nor the budget; over the budget
    nothing is written."""
    rng = np.random.default_rng(100 + seed)
    k = {"none": 60, "under": 20, "exact": REF_CB, "over": REF_CB + 9}[budget]
    fps = np.full(REF_M, EMPTY, np.uint64)
    lanes = np.sort(rng.choice(REF_M, k, replace=False))
    fps[lanes] = rng.integers(1, 1 << 40, k).astype(np.uint64)
    lo, mid, hi = lanes[2], lanes[7], lanes[k - 1]
    fps[mid] = fps[hi] = fps[lo]  # one fingerprint on three lanes
    fps[lanes[4]] = fps[lanes[3]]
    pls = np.arange(1000, 1000 + REF_M, dtype=np.uint64)
    tfp0, tpl0 = seeded_table([int(fps[lanes[1]]), int(fps[lanes[5]])])
    compact = None if budget == "none" else REF_CB
    tfp, tpl, sel, n_new, ovf, covf = both_inserts(
        tfp0, tpl0, fps, pls, generation_order, compact
    )
    assert bool(covf) == (budget == "over") and not bool(ovf)
    if budget == "over":
        return
    n = int(n_new)
    assert n == len(set(fps[lanes].tolist())) - 2
    chosen = np.asarray(sel)[:n].tolist()
    assert lo in chosen and mid not in chosen and hi not in chosen
    assert table_contents((tfp, tpl))[int(fps[lo])] == int(pls[lo])
    if generation_order:
        assert chosen == sorted(chosen)


@pytest.mark.parametrize("budget", ["none", "under"])
@pytest.mark.parametrize("generation_order", [False, True])
def test_insert_bucket_overflow_matches_the_gathering_reference(
    generation_order, budget
):
    """A bucket driven past SLOTS by the batch (some of its slots already
    taken, one candidate a duplicate of the table): both flags as the old
    body's, nothing written; one candidate fewer and the same batch lands."""
    crowd = same_bucket_fps(SLOTS + 1, REF_NBUCKETS)
    tfp0, tpl0 = seeded_table(crowd[:3])
    rng = np.random.default_rng(9)
    for extra, overflows in ((SLOTS + 1, True), (SLOTS, False)):
        fps = np.full(REF_M, EMPTY, np.uint64)
        lanes = np.sort(rng.choice(REF_M, 24, replace=False))
        fps[lanes] = rng.integers(1 << 41, 1 << 42, 24).astype(np.uint64)
        fps[lanes[: extra - 2]] = crowd[2:extra]  # crowd[2] is in the table
        pls = np.arange(1, REF_M + 1, dtype=np.uint64)
        out = both_inserts(
            tfp0, tpl0, fps, pls, generation_order,
            None if budget == "none" else REF_CB,
        )
        assert bool(out[4]) == overflows and not bool(out[5])
        assert (int(out[3]) == 0) == overflows


def primitive_names(fn, *args):
    """Every primitive of ``fn``'s traced program, sub-jaxprs included."""
    return [e.primitive.name for e in _iter_eqns(jax.make_jaxpr(fn)(*args))]


@pytest.mark.parametrize("generation_order", [False, True])
def test_insert_fetches_nothing_a_sort_can_carry(generation_order):
    """The static pin of PR 36: a value that follows a sort's permutation
    rides through the sort as an operand, it is not fetched afterwards by
    ``x[perm]``.  The traced ``bucket_insert(compact=CB)`` holds three
    gathers - the membership loop's ``[window, ROW_LANES]`` ROW gather (PR
    38: eight buckets a fetch, the flat table's own tiled layout), the
    budget compaction's ``fps[lane]`` at ``(CB,)`` (carrying it through the
    ``m``-wide sort compiles 40 s slower a step program, PR 36) and the
    write loop's ``payloads[sel chunk]`` at ``(window,)`` (a payload is
    needed only where something is written) - and three sorts (budget
    compaction at ``m``, key sort and novel compaction at ``CB``); no
    search either (a ``searchsorted`` traces to a ``while``/``scan``):
    just the membership and the chunk-write ``while``.  The old body,
    traced the same way, holds its eighteen u32 gathers at ``(CB,)`` as
    eleven call sites."""
    m, cb, window = 96, 32, 8
    tfp, tpl = fresh(64)
    fps = jnp.full((m,), EMPTY, jnp.uint64)

    def trace(fn, compact):
        return jax.make_jaxpr(
            lambda a, b, c, d: fn(
                a, b, c, d, window=window, compact=compact,
                generation_order=generation_order,
            )
        )(tfp, tpl, fps, fps)

    loops = ("while", "scan")
    for compact, element, widths in (
        (cb, [(cb,), (window,)], [m, cb, cb]),
        (None, [(window,)], [m, m]),
    ):
        jaxpr = trace(bucket_insert, compact)
        assert sorted(gather_call_sites(jaxpr)) == sorted(
            element + [(window, ROW_LANES)]
        )
        assert [s[0] for s in gather_call_sites(jaxpr, "sort")] == widths
        names = [e.primitive.name for e in _iter_eqns(jaxpr)]
        assert sorted(p for p in names if p in loops) == ["while", "while"]
    old = gather_call_sites(trace(ref_bucket_insert, cb))
    assert sorted(old) == [(window, SLOTS)] + [(cb,)] * 11  # seven of them u64
    # and the old compaction's own formulation does hold a search loop
    searched = primitive_names(lambda x: ref_lane_compact(x, 32), fps != EMPTY)
    assert any(p in loops for p in searched)

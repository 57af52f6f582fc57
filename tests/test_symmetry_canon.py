"""The symmetry canonicalisers permute their per-actor columns by a stable
RANK from compares and place them by selects or shifts (PR 34) — no
``argsort``, no ``take_along_axis``.  The host finds a class again by the
canonical row's fingerprint (``_base.py:_symmetry_key``), so the row itself
is the contract, not only the partition: the implementations these replaced
(``argsort(stable)`` + one element gather a field) live on here as the
oracles, and every row must come out bit for bit.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.tensor_model import (
    pack_by_rank,
    place_by_rank,
    stable_rank,
)

sys.path.insert(0, str(Path(__file__).parent))
from test_paxos_tensor import gather_call_sites  # noqa: E402


# -- the helper alone ---------------------------------------------------------


def _keys(kind, n, lanes, dtype, rng):
    hi = np.iinfo(dtype).max
    if kind == "equal":
        return np.full((lanes, n), hi - 3, dtype)
    if kind == "distinct":  # a random permutation of n far-apart values a lane
        base = (np.arange(n, dtype=np.uint64) * np.uint64(hi // max(n, 1))).astype(dtype)
        return rng.permuted(np.broadcast_to(base, (lanes, n)), axis=1)
    return rng.integers(0, 4, (lanes, n)).astype(dtype) * dtype(hi // 4)  # ties


@pytest.mark.parametrize("kind", ["equal", "distinct", "ties"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint64], ids=["s32", "u64"])
@pytest.mark.parametrize("n", [1, 2, 13, 29])
def test_placement_by_stable_rank_is_the_stable_argsort_gather(n, dtype, kind):
    """For every column: placing it by ``stable_rank(keys)`` — by selects
    (``place_by_rank``) or, for a bit field, by shifts (``pack_by_rank``) —
    equals ``take_along_axis(col, argsort(keys, stable=True))``, and the
    rank is the inverse permutation of that argsort."""
    rng = np.random.default_rng(100 * n + np.dtype(dtype).itemsize)
    lanes = 37
    keys = _keys(kind, n, lanes, dtype, rng)
    cols = rng.integers(0, np.iinfo(dtype).max, (lanes, n), dtype=dtype, endpoint=True)
    bits = rng.integers(0, 4, (lanes, n)).astype(np.int32)
    order = jnp.argsort(jnp.asarray(keys), axis=-1, stable=True)

    def run(keys, cols, bits):
        ranks = stable_rank([keys[:, i] for i in range(n)])
        placed = place_by_rank([cols[:, i] for i in range(n)], ranks)
        word = pack_by_rank([bits[:, i] for i in range(n)], ranks, 2)
        return jnp.stack(ranks, -1), jnp.stack(placed, -1), word

    for fn in (run, jax.jit(run)):
        ranks, placed, word = fn(jnp.asarray(keys), jnp.asarray(cols), jnp.asarray(bits))
        assert ranks.dtype == jnp.int32 and placed.dtype == dtype
        assert np.array_equal(np.asarray(ranks), np.asarray(jnp.argsort(order, axis=-1)))
        want = np.asarray(jnp.take_along_axis(jnp.asarray(cols), order, axis=-1))
        assert np.array_equal(np.asarray(placed), want)
        sbits = np.asarray(jnp.take_along_axis(jnp.asarray(bits), order, axis=-1))
        packed = sum(sbits[:, p].astype(np.uint64) << np.uint64(2 * p) for p in range(n))
        assert word.dtype == (jnp.uint32 if 2 * n <= 32 else jnp.uint64)
        assert np.array_equal(np.asarray(word).astype(np.uint64), packed)


# -- TwoPhaseTensor.representative_rows ---------------------------------------


def parent_representative_rows(tw, rows):
    """``TwoPhaseTensor.representative_rows`` as PR 33's tree had it: a
    stable ``argsort`` of ``3 - code`` over the ``[..., n]`` axis and three
    ``take_along_axis`` (``two_phase_commit.py:271-311`` at 6487f22)."""
    n, pk = tw.n, tw.packer
    u64 = jnp.uint64
    rm = pk.get(rows, "rm")
    tp = pk.get(rows, "tm_prepared")
    mp = pk.get(rows, "msg_prepared")
    rmv = jnp.stack(
        [((rm >> u64(2 * i)) & u64(3)).astype(jnp.int32) for i in range(n)], -1
    )
    tpv = jnp.stack([((tp >> u64(i)) & u64(1)).astype(jnp.int32) for i in range(n)], -1)
    mpv = jnp.stack([((mp >> u64(i)) & u64(1)).astype(jnp.int32) for i in range(n)], -1)
    order = jnp.argsort(3 - rmv, axis=-1, stable=True)  # new -> old
    rms = jnp.take_along_axis(rmv, order, axis=-1)
    tps = jnp.take_along_axis(tpv, order, axis=-1)
    mps = jnp.take_along_axis(mpv, order, axis=-1)
    zero = jnp.zeros_like(rm)
    rm_new, tp_new, mp_new = zero, zero, zero
    for i in range(n):
        rm_new = rm_new | (rms[..., i].astype(u64) << u64(2 * i))
        tp_new = tp_new | (tps[..., i].astype(u64) << u64(i))
        mp_new = mp_new | (mps[..., i].astype(u64) << u64(i))
    rows = pk.set(rows, "rm", rm_new)
    rows = pk.set(rows, "tm_prepared", tp_new)
    rows = pk.set(rows, "msg_prepared", mp_new)
    return rows


def _assert_same_rows(tw, rows):
    rows = jnp.asarray(rows)
    want = np.asarray(jax.jit(lambda r: parent_representative_rows(tw, r))(rows))
    for fn in (tw.representative_rows, jax.jit(tw.representative_rows)):
        got = fn(rows)
        assert got.dtype == jnp.uint64 and got.shape == rows.shape
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n", [1, 2, 3])
def test_2pc_representative_rows_exhaustively(n):
    """EVERY value of the row's 4 n + 4 used bits (all field values, the
    unreachable ones and ``tm`` = 3 included)."""
    tw = TwoPhaseSys(n).tensor_model()
    assert tw.width == 1
    _assert_same_rows(tw, np.arange(1 << (4 * n + 4), dtype=np.uint64)[:, None])


def _random_2pc_rows(tw, lead, rng):
    """Seeded rows with every field random AND the unused high bits of each
    word set at random: what the canonicaliser does not own it must keep."""
    rows = rng.integers(0, 1 << 64, lead + (tw.width,), dtype=np.uint64)
    n = tw.n
    # lanes that exercise ties: a few distinct codes, long runs of one code
    codes = rng.integers(0, 4, lead + (n,)) * (rng.random(lead + (n,)) < 0.7)
    rm = sum(codes[..., i].astype(np.uint64) << np.uint64(2 * i) for i in range(n))
    word, off, bits = tw.packer.layout["rm"]
    mask = np.uint64(((1 << bits) - 1) << off)
    half = rows[: len(rows) // 2]
    half[..., word] = (half[..., word] & ~mask) | (rm[: len(half)] << np.uint64(off))
    return rows


@pytest.mark.parametrize("lead", [(512,), (16, 23)], ids=["R", "BxA"])
@pytest.mark.parametrize("n", [5, 13, 16, 17, 29])
def test_2pc_representative_rows_on_seeded_rows(n, lead):
    """n = 16 / 17 are the two sides of ``rm``'s 32-bit edge (``pack_by_rank``
    builds the word as a u32 up to 16 RMs and as a u64 beyond), 29 the widest
    twin (two-word rows)."""
    tw = TwoPhaseSys(n).tensor_model()
    assert tw.width == (1 if n <= 15 else 2)
    _assert_same_rows(tw, _random_2pc_rows(tw, lead, np.random.default_rng(3400 + n)))


def test_2pc_representative_rows_has_no_gather_and_no_sort():
    """The counter that the mechanism engaged, at the benchmark cell's shape
    (``twopc13sym-presized``: batch 1024 x 67 actions x 1 word): the parent
    holds three ``gather`` at ``[1024, 67, 13]`` and a ``sort``."""
    tw = TwoPhaseSys(13).tensor_model()
    rows = jnp.zeros((1024, tw.max_actions, tw.width), jnp.uint64)
    assert rows.shape == (1024, 67, 1)
    parent = jax.make_jaxpr(lambda r: parent_representative_rows(tw, r))(rows)
    assert gather_call_sites(parent) == [(1024, 67, 13)] * 3
    assert len(gather_call_sites(parent, "sort")) == 1
    jaxpr = jax.make_jaxpr(tw.representative_rows)(rows)
    assert gather_call_sites(jaxpr) == []
    assert gather_call_sites(jaxpr, "sort") == []


# -- the compiled twin's virtual row ------------------------------------------


def parent_virtual_rows(tm, rows):
    """``CompiledActorTensor._representative_rows_impl`` as PR 33's tree had
    it (``actor_compiler.py:1212-1274`` at 6487f22): ``order = argsort(keys,
    stable)``, ``mapping = argsort(order)``, the universe codes and the timer
    bits gathered by ``order``."""
    from stateright_tpu.parallel.actor_tensor import (
        COUNT_BITS,
        COUNT_MASK,
        SLOT_EMPTY,
        slot_canonicalize,
    )

    cst = tm._sym_consts()
    i32, u64 = jnp.int32, jnp.uint64
    pk, n = tm.pk, tm.n_actors
    fact = tm._sym_tables["fact"]
    ar = jnp.arange(n, dtype=i32)
    ucodes = jnp.stack(
        [cst["umaps"][i][pk.get(rows, f"a{i}").astype(i32)] for i in range(n)], axis=-1
    )
    keys = cst["keys"][ucodes]
    order = jnp.argsort(keys, axis=-1, stable=True)  # new -> old
    mapping = jnp.argsort(order, axis=-1)  # old -> new
    lead = ucodes.shape[:-1]
    perm_id = jnp.zeros(lead, i32)
    for k in range(n):
        c = jnp.zeros(lead, i32)
        for j in range(k + 1, n):
            c = c + (mapping[..., j] < mapping[..., k]).astype(i32)
        perm_id = perm_id + c * jnp.int32(fact[k])
    usorted = jnp.take_along_axis(ucodes, order, axis=-1)
    codes2 = cst["rw"][perm_id[..., None], usorted]
    if tm._has_timers:
        tb = pk.get(rows, "timers").astype(i32)
        bits = (tb[..., None] >> ar) & 1
        bits = jnp.take_along_axis(bits, order, axis=-1)
        tword = jnp.sum(bits << ar, axis=-1)
    else:
        tword = jnp.zeros(lead, i32)
    slots = rows[..., tm.pw :]
    occ = slots != u64(SLOT_EMPTY)
    e = jnp.where(occ, (slots >> u64(COUNT_BITS)).astype(i32), 0)
    cnt = slots & u64(COUNT_MASK)
    e2 = cst["ev"][perm_id[..., None], e]
    slot2 = jnp.where(occ, (e2.astype(u64) << u64(COUNT_BITS)) | cnt, u64(SLOT_EMPTY))
    slot2 = slot_canonicalize(slot2)
    return jnp.concatenate(
        [codes2.astype(u64), tword[..., None].astype(u64), slot2], axis=-1
    )


@pytest.fixture(scope="module")
def raft3():
    """The raft model of ``tests/test_raft.py`` and a seeded sample of its
    reachable rows (a bounded BFS crawl: 7 levels)."""
    from stateright_tpu.models.raft import raft_model

    m = raft_model(3)
    tm = m.tensor_model()
    tm.init_rows()
    assert hasattr(tm, "representative_rows")  # builds the permutation tables
    tm._sym_consts()  # ... and uploads them outside any trace (it caches what it makes)
    states, frontier = [], list(m.init_states())
    seen = set(frontier)
    for _ in range(7):
        states += frontier
        nxt = []
        for s in frontier:
            for t in m.next_states(s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    states += frontier
    rng = np.random.default_rng(34)
    pick = rng.choice(len(states), size=min(len(states), 960), replace=False)
    rows = np.asarray([tm.encode_state(states[i]) for i in pick], np.uint64)
    return tm, rows


@pytest.mark.parametrize("lead", ["R", "BxA"])
def test_compiled_twin_virtual_rows_on_reachable_raft_rows(raft3, lead):
    tm, rows = raft3
    assert tm._has_timers and tm.n_actors == 3 and len(rows) >= 480
    if lead == "BxA":
        rows = rows[: len(rows) // 20 * 20].reshape(-1, 20, rows.shape[-1])
    rows = jnp.asarray(rows)
    want = np.asarray(jax.jit(lambda r: parent_virtual_rows(tm, r))(rows))
    # the sample permutes: rows whose actors are NOT already in key order
    got = jax.jit(tm.representative_rows)(rows)
    assert got.dtype == jnp.uint64
    np.testing.assert_array_equal(np.asarray(got), want)
    ucodes = np.stack(
        [
            np.asarray(tm._sym_tables["umaps"][i])[
                np.asarray(tm.pk.get(rows, f"a{i}")).astype(np.int64)
            ]
            for i in range(tm.n_actors)
        ],
        -1,
    )
    keys = np.asarray(tm._sym_tables["keys"])[ucodes]
    assert (np.diff(keys.astype(np.float64), axis=-1) < 0).any()


def test_compiled_twin_canonicaliser_sorts_no_actor_axis_and_gathers_only_tables(raft3):
    """No ``sort`` but ``slot_canonicalize``'s over the slots, and exactly
    the table look-ups' gathers, by shape: one ``umaps`` look-up an actor at
    ``[B, A]``, ``keys`` and ``rw`` at ``[B, A, actors]``, ``ev`` at
    ``[B, A, slots]`` (ROADMAP Queue 1 item 3d's matter).  The parent holds
    two more gathers at ``[B, A, actors]`` (the codes and the timer bits by
    ``order``) and two more sorts (``order``, ``mapping``)."""
    tm, _ = raft3
    B, A, n = 8, tm.max_actions, tm.n_actors
    ns = tm.width - tm.pw
    rows = jnp.zeros((B, A, tm.width), jnp.uint64)
    tables = sorted([(B, A)] * n + [(B, A, n)] * 2 + [(B, A, ns)])
    jaxpr = jax.make_jaxpr(tm.representative_rows)(rows)
    assert sorted(gather_call_sites(jaxpr)) == tables
    assert gather_call_sites(jaxpr, "sort") == [(B, A, ns)]
    parent = jax.make_jaxpr(lambda r: parent_virtual_rows(tm, r))(rows)
    assert sorted(gather_call_sites(parent)) == sorted(tables + [(B, A, n)] * 2)
    assert len(gather_call_sites(parent, "sort")) == 3

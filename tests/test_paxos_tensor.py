"""Paxos tensor-twin equivalence + engine parity (the benchmark model).

Same obligations as the 2pc twin (``test_tensor_models.py``) on the much
harder encoding: actor states + multiset network + linearizability-tester
history in fixed-width rows (SURVEY §7.1).  Pinned parity: 16,668 unique
states @ 2 clients / 3 servers (reference ``examples/paxos.rs:291,311``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.fingerprint import hash_words
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.parallel.tensor_model import select_along_axis


def crawl_and_check(m, tm, max_levels=None):
    """BFS the object form, asserting per state: encode/decode round-trip,
    device/host fingerprint agreement, and successor-set equality."""
    seen = {}
    frontier = list(m.init_states())
    for s in frontier:
        seen[m.fingerprint_state(s)] = s
    level = 0
    while frontier and (max_levels is None or level < max_levels):
        rows = np.asarray([tm.encode_state(s) for s in frontier], np.uint64)
        succ, valid = tm.step_rows(jnp.asarray(rows))
        succ, valid = np.asarray(succ), np.asarray(valid)
        masks = np.asarray(tm.property_masks(jnp.asarray(rows)))
        nxt = []
        for i, s in enumerate(frontier):
            assert tm.decode_state(rows[i]) == s
            assert m.fingerprint_state(s) == hash_words(
                int(w) for w in rows[i]
            )
            obj_succs = sorted(
                tuple(tm.encode_state(t)) for t in m.next_states(s)
            )
            dev_succs = sorted(
                tuple(int(w) for w in succ[i, a])
                for a in range(tm.max_actions)
                if valid[i, a]
            )
            assert dev_succs == obj_succs, (level, i)
            for p, prop in enumerate(m.properties()):
                assert bool(masks[i, p]) == bool(prop.condition(m, s)), (
                    prop.name,
                    s,
                )
            for t in m.next_states(s):
                fp = m.fingerprint_state(t)
                if fp not in seen:
                    seen[fp] = t
                    nxt.append(t)
        frontier = nxt
        level += 1
    return seen


@pytest.mark.slow
def test_paxos1_full_equivalence():
    m = paxos_model(1, 3)
    tm = m.tensor_model()
    seen = crawl_and_check(m, tm)
    assert len(seen) == 265


@pytest.mark.slow
def test_paxos2_prefix_equivalence():
    # First 6 wavefronts of the 2-client system: covers puts, prepare/prepared
    # quorums, accepts, and the first decisions.
    m = paxos_model(2, 3)
    tm = m.tensor_model()
    crawl_and_check(m, tm, max_levels=6)


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_paxos2_tpu_checker_pinned_count():
    m = paxos_model(2, 3)
    checker = m.checker().spawn_tpu(
        sync=True, capacity=1 << 16, frontier_capacity=1 << 12
    )
    assert checker.unique_state_count() == 16668
    assert set(checker.discoveries()) == {"value chosen"}
    # the "value chosen" example is a real witness
    path = checker.discovery("value chosen")
    assert m.property_by_name("value chosen").condition(m, path.final_state())
    checker.assert_properties()


@pytest.mark.medium
def test_paxos2_mesh_matches():
    m = paxos_model(2, 3)
    checker = m.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 16, frontier_capacity=1 << 12
    )
    assert checker.unique_state_count() == 16668
    assert set(checker.discoveries()) == {"value chosen"}


@pytest.mark.slow
def test_paxos2_cpu_bfs_agrees():
    # CPU oracle on the same fingerprint function (row encoding)
    m = paxos_model(2, 3)
    cpu = m.checker().spawn_bfs().join()
    assert cpu.unique_state_count() == 16668
    assert set(cpu.discoveries()) == {"value chosen"}


@pytest.mark.parametrize("dtype", [np.int32, np.uint64], ids=["s32", "u64"])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_select_along_axis_is_take_along_axis_in_range(n, dtype):
    """The select chain reproduces the gather on in-range indices, bit for
    bit: every column is read by some lane, high u64 bits included."""
    rng = np.random.default_rng(1000 * n + np.dtype(dtype).itemsize)
    B, A = 13, 30
    cols = rng.integers(0, np.iinfo(dtype).max, (B, n), dtype=dtype, endpoint=True)
    idx = rng.integers(0, n, (B, A), dtype=np.int32)
    idx[:n, 0] = np.arange(n)  # each column read at least once
    cols, idx = jnp.asarray(cols), jnp.asarray(idx)
    want = np.asarray(jnp.take_along_axis(cols, idx, axis=1))
    for got in (select_along_axis(cols, idx), jax.jit(select_along_axis)(cols, idx)):
        assert got.dtype == dtype and got.shape == (B, A)
        assert np.array_equal(np.asarray(got), want)


def gather_call_sites(jaxpr, primitive="gather") -> list:
    """Result shape of every ``gather`` (or other ``primitive``, ``sort``
    say) the program RUNS: a sub-jaxpr is walked once per equation that
    calls it (``jnp.take_along_axis`` is a cached jit, so ten calls share
    one jaxpr object and ``analysis/jaxpr_audit._walk_jaxprs`` reports them
    as one)."""
    shapes = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == primitive:
            shapes.append(tuple(eqn.outvars[0].aval.shape))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    shapes += gather_call_sites(sub, primitive)
    return shapes


def test_gather_call_sites_counts_each_call_of_a_shared_jaxpr():
    cols, idx = jnp.zeros((7, 3), jnp.int32), jnp.zeros((7, 30), jnp.int32)

    def ten(cols, idx):
        return sum(jnp.take_along_axis(cols + k, idx, axis=1) for k in range(10))

    assert gather_call_sites(jax.make_jaxpr(ten)(cols, idx)) == [(7, 30)] * 10


@pytest.mark.parametrize("clients", [2, 3])
def test_step_rows_gathers_nothing_at_batch_by_actions(clients):
    """The per-server fields and the client phase at ``dst`` were ten element
    gathers at ``[B, A]`` lanes, 3.07 s of a 6.55 s busy paxos-3 check
    (ledger, PR 30); they are selects over the 3 columns now, and no other
    look-up at those lanes has come in."""
    tm = paxos_model(clients, 3).tensor_model()
    B, A = 7, tm.max_actions
    rows = jnp.zeros((B, tm.width), jnp.uint64)
    for step in (tm.step_rows, tm.step_rows_coalesced):
        shapes = gather_call_sites(jax.make_jaxpr(step)(rows))
        assert not [s for s in shapes if int(np.prod(s)) >= B * A], shapes


def test_paxos_tensor_eligibility():
    from stateright_tpu.actor import Network
    from stateright_tpu.models.paxos_tensor import PaxosTensor
    from stateright_tpu.parallel.actor_compiler import CompiledActorTensor

    # benchmark shape -> hand-tuned twin; other shapes -> mechanical compiler
    assert isinstance(paxos_model(2, 3).tensor_model(), PaxosTensor)
    assert isinstance(paxos_model(2, 4).tensor_model(), CompiledActorTensor)
    # ordered networks go through the compiler's rank-in-slot FIFO encoding
    tm = paxos_model(2, 3, Network.new_ordered()).tensor_model()
    assert isinstance(tm, CompiledActorTensor) and tm.ordered
    # duplicating networks make ballots unbounded -> no twin (structural CPU)
    assert (
        paxos_model(2, 3, Network.new_unordered_duplicating()).tensor_model()
        is None
    )


def test_paxos_compiled_4_servers_matches_cpu():
    """The mechanically compiled twin (4 servers is outside the hand twin)
    agrees with the CPU oracle end to end."""
    m = paxos_model(1, 4)
    cpu = m.checker().spawn_bfs().join()
    tpu = m.checker().spawn_tpu(
        sync=True, capacity=1 << 14, frontier_capacity=1 << 10
    )
    assert cpu.unique_state_count() == tpu.unique_state_count() == 1169
    assert set(cpu.discoveries()) == set(tpu.discoveries())


@pytest.mark.slow
def test_paxos3_prefix_equivalence():
    # C=3 exercises the closure linearizability verdict and the full
    # 2C-bit snapshot encoding; crawl_and_check validates property_masks
    # directly against prop.condition on real C=3 rows (the C=2 prefix test
    # cannot reach C=3-specific encoding bugs).
    m = paxos_model(3, 3)
    tm = m.tensor_model()
    crawl_and_check(m, tm, max_levels=5)


@pytest.mark.slow
def test_paxos4_prefix_equivalence():
    # C=4 is past the old (2C)! permutation cap: exercises the closure
    # verdict and the C-parameterized field widths on real rows.
    m = paxos_model(4, 3)
    tm = m.tensor_model()
    crawl_and_check(m, tm, max_levels=4)


@pytest.mark.slow
def test_paxos6_prefix_equivalence():
    # the reference bench config (``paxos check 6``, bench.sh): a shallow
    # crawl proving the widened encoding + closure verdict hold at C=6.
    m = paxos_model(6, 3)
    tm = m.tensor_model()
    crawl_and_check(m, tm, max_levels=2)


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_paxos3_twin_equivalence_bounded():
    """FAST-TIER pin of the flagship config's twin (the driver benchmark is
    ``paxos check 3``): a bounded per-level crawl asserting encode/decode
    round-trips, host=device fingerprints, successor-set equality, and
    property-mask agreement on real C=3 rows — so the per-push tier fails
    if the paxos-3 twin drifts, even when the full 1,194,428-state run
    (slow tier / bench) can't validate it."""
    m = paxos_model(3, 3)
    tm = m.tensor_model()
    seen = crawl_and_check(m, tm, max_levels=5)
    assert len(seen) > 100  # depth-5 reachable set, all states cross-checked


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_paxos3_tpu_vs_cpu_sample():
    """3-client config (the driver benchmark): spot-check engine agreement on
    a bounded prefix via target_state_count."""
    m = paxos_model(3, 3)
    t = m.checker().target_states(3000).spawn_tpu(sync=True)
    assert t.unique_state_count() >= 3000
    # property kernel sanity on visited rows: no linearizability violation
    assert "linearizable" not in t.discoveries()


@pytest.mark.slow
def test_paxos6_device_engine_prefix():
    """The reference bench config (paxos check 6) runs end-to-end on the
    device engine: C=6 twin compiles, expands, dedups and evaluates the
    closure linearizability verdict with no slot-overflow rows and no false
    violations on a bounded prefix."""
    m = paxos_model(6, 3)
    c = m.checker().target_states(4000).spawn_tpu(
        sync=True, capacity=1 << 16, frontier_capacity=1 << 9
    )
    assert c.unique_state_count() >= 4000
    assert "linearizable" not in c.discoveries()
    # every enqueued row is clean: the network never overflowed its slots
    tm = c.tensor
    rows = np.asarray(c._final_carry.q_rows)
    tail = int(np.asarray(c._final_carry.tail))
    for r in rows[:tail:37]:  # stride-sample the queue
        assert tm.pk.unpack(r[: tm.pw])["overflow"] == 0


@pytest.mark.slow
def test_paxos3_full_space_device_vs_cpu():
    """THE flagship parity result: the COMPLETE paxos-3 space — 1,194,428
    unique states, the driver benchmark's primary config run to exhaustion
    — enumerated by both the CPU oracle and the device engine with equal
    counts and discoveries.  (The bench pins the device side of this number
    every run; this test pins it against the object-form oracle.)"""
    m = paxos_model(3, 3)
    tpu = m.checker().spawn_tpu(
        sync=True, capacity=1 << 23, queue_capacity=1 << 21, batch=2048
    )
    assert tpu.unique_state_count() == 1_194_428
    cpu = m.checker().spawn_bfs().join()
    assert cpu.unique_state_count() == 1_194_428
    assert cpu.state_count() == tpu.state_count()
    assert set(cpu.discoveries()) == set(tpu.discoveries()) == {"value chosen"}

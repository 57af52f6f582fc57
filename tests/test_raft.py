"""Raft leader election — the actor compiler's GENERAL fragment.

Beyond the reference's example set.  Pins: the host state space, election
safety (always) + leader-elected witness (sometimes), and full
device/host parity for a timeout-driven, history-free actor system whose
twin is compiled mechanically (timer bits, Timeout actions, factored
property tables — ``parallel/actor_compiler.py`` general mode).
"""

import pytest

from stateright_tpu.actor import ActorModel, Network
from stateright_tpu.actor.device_props import exists_actor
from stateright_tpu.core import Expectation
from stateright_tpu.models.raft import LEADER, RaftServer, raft_model

RAFT3_UNIQUE = 5_725  # 3 servers, max_term=2, unordered non-duplicating


def test_raft3_host_pinned_count_and_properties():
    c = raft_model(3).checker().spawn_bfs().join()
    assert c.unique_state_count() == RAFT3_UNIQUE
    # election safety holds (no counterexample); a leader is reachable
    assert sorted(c.discoveries()) == ["a leader is elected"]
    c.assert_properties()


def test_raft3_twin_crawl_equivalence():
    """Per-level successor/fingerprint/property parity of the compiled
    twin, incl. Timeout actions and timer-bit round-trips."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_paxos_tensor import crawl_and_check

    m = raft_model(3)
    tm = m.tensor_model()
    assert tm is not None and tm._has_timers
    crawl_and_check(m, tm, max_levels=4)


def test_raft3_engine_full_parity():
    """Full-space device enumeration matches the host oracle, and the
    leader-election witness re-executes."""
    m = raft_model(3)
    c = m.checker().spawn_tpu(
        sync=True, capacity=1 << 15, frontier_capacity=1 << 9
    )
    assert c.unique_state_count() == RAFT3_UNIQUE
    assert sorted(c.discoveries()) == ["a leader is elected"]
    path = c.discoveries()["a leader is elected"]
    c.assert_discovery("a leader is elected", list(path.actions()))
    assert path.final_state().actor_states[int(path.actions()[-1].dst)].role == LEADER


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_raft3_lossy_engine_parity():
    """Message loss adds Drop actions; host and device agree on the
    enlarged space and still find a leader (drops are optional)."""
    m = raft_model(3)
    m.lossy_network(True)
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(
        sync=True, capacity=1 << 16, frontier_capacity=1 << 10
    )
    assert h.unique_state_count() == c.unique_state_count()
    assert sorted(h.discoveries()) == sorted(c.discoveries())


@pytest.mark.parametrize("net", ["ordered", "unordered_duplicating"])
def test_raft2_engine_parity_across_network_semantics(net):
    """Timer-fragment compilation composes with every network semantics:
    host and device enumerate the same space under ordered FIFO and
    duplicating redelivery too."""
    m = raft_model(2, network=Network.from_name(net))
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert h.unique_state_count() == c.unique_state_count() > 0
    assert sorted(h.discoveries()) == sorted(c.discoveries())


def test_raft2_no_split_brain_two_servers():
    """With 2 servers a majority is 2: no term can elect two leaders, and
    the safety property discovers nothing on host or device."""
    m = raft_model(2)
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert h.unique_state_count() == c.unique_state_count()
    assert "election safety" not in h.discoveries()
    assert "election safety" not in c.discoveries()


def test_factored_within_boundary_compiles_and_agrees():
    """A factored ``within_boundary`` compiles: the device engine masks
    out-of-boundary successors exactly like the host checkers (boundary
    filter before counting; fully-masked states are terminal)."""
    from stateright_tpu.actor.device_props import forall_actors

    m = raft_model(3)
    m.within_boundary_(forall_actors(lambda i, s: s.term <= 1))
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert h.unique_state_count() == c.unique_state_count()
    assert 0 < h.unique_state_count() < RAFT3_UNIQUE
    assert sorted(h.discoveries()) == sorted(c.discoveries())


RAFT3_SYM_FIFO = 2_926  # BFS-order symmetry-reduced classes (FIFO oracle)


def test_mechanical_symmetry_partition_matches_host():
    """The compiled twin's mechanical canonicalizer (permutation tables
    over the union state universe) induces EXACTLY the host
    ``representative()`` partition — checked state-by-state over a
    bounded crawl."""
    import numpy as np
    import jax.numpy as jnp

    from stateright_tpu.fingerprint import stable_hash
    from stateright_tpu.ops import row_hash

    m = raft_model(3)
    tm = m.tensor_model()
    tm.init_rows()
    assert hasattr(tm, "representative_rows")
    # bounded BFS sample of the space
    states, frontier = [], list(m.init_states())
    seen = set(frontier)
    for _ in range(5):
        states += frontier
        nxt = []
        for s in frontier:
            for t in m.next_states(s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    states += frontier
    hkeys = [stable_hash(s.representative()) for s in states]
    rows = np.asarray([tm.encode_state(s) for s in states], np.uint64)
    dkeys = np.asarray(row_hash(tm.representative_rows(jnp.asarray(rows))))
    # identical partitions: same-key pairs agree in both directions
    import collections

    hgroup = collections.defaultdict(set)
    dgroup = collections.defaultdict(set)
    for i, (h, d) in enumerate(zip(hkeys, dkeys)):
        hgroup[h].add(i)
        dgroup[int(d)].add(i)
    assert sorted(map(sorted, hgroup.values())) == sorted(
        map(sorted, dgroup.values())
    )


def test_mechanical_symmetry_engine_matches_fifo_oracle():
    """Device symmetry reduction on the compiled Raft twin: counts match
    the engine-independent FIFO oracle, the reduced search still finds
    the leader witness, and the trace reconstructs through the
    class-matching walk."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent))
    from test_tensor_models import host_fifo_sym_oracle

    m = raft_model(3)
    assert host_fifo_sym_oracle(m) == RAFT3_SYM_FIFO
    c = m.checker().symmetry().spawn_tpu(sync=True, capacity=1 << 14)
    assert c.unique_state_count() == RAFT3_SYM_FIFO
    assert sorted(c.discoveries()) == ["a leader is elected"]
    path = c.discoveries()["a leader is elected"]
    assert len(path.actions()) >= 3  # timeout + vote round trip


@pytest.mark.parametrize("width", [2, 8])
def test_mechanical_symmetry_mesh_equals_the_one_chip_count(width):
    """Symmetry on the compiled twin over a mesh: reduced counts are
    visit-order-dependent when the representative is not class-invariant,
    and the mesh engine runs the one-device program — so at EVERY width
    the count is the one-device count, which is the host FIFO oracle's
    (2,926).  (The deleted ``shard_map`` engine visited in another order
    at each width: 2,960 at 2, 3,015 at 8.)"""
    c = raft_model(3).checker().symmetry().spawn_tpu(
        sync=True, devices=width, capacity=1 << 14, frontier_capacity=1 << 9
    )
    assert c.n_devices == width
    assert c.unique_state_count() == RAFT3_SYM_FIFO
    assert sorted(c.discoveries()) == ["a leader is elected"]


def test_eventually_property_parity_general_fragment():
    """Liveness bookkeeping (ebits) composes with the general fragment:
    with a single term two servers can split their votes and stop
    campaigning, a terminal path electing nobody — host and device both
    discover the 'eventually' counterexample on the same space."""
    m = raft_model(2, max_term=1)
    m.property(
        Expectation.EVENTUALLY,
        "eventually elects",
        exists_actor(lambda i, s: s.role == LEADER),
    )
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert h.unique_state_count() == c.unique_state_count() == 25
    assert "eventually elects" in h.discoveries()
    assert "eventually elects" in c.discoveries()
    # the counterexample ends terminal with no leader (reference ebits
    # semantics: bits still set at a terminal state flush as discoveries)
    final = h.discoveries()["eventually elects"].final_state()
    assert all(s.role != LEADER for s in final.actor_states)


def test_history_free_model_requires_factored_properties():
    from stateright_tpu.parallel.actor_compiler import (
        CompileError,
        compile_actor_model,
    )

    m = ActorModel(cfg=None, init_history=None)
    m.actor(RaftServer(peers=[], cluster=1, max_term=1))
    m.init_network_(Network.new_unordered_nonduplicating())
    m.property(
        Expectation.ALWAYS, "opaque", lambda model, s: True  # not factored
    )
    with pytest.raises(CompileError, match="factored"):
        compile_actor_model(m)


def test_factored_predicates_evaluate_on_host():
    """The same predicate object drives host checking directly."""
    m = raft_model(3)
    checker = m.checker().spawn_dfs().join()
    assert checker.unique_state_count() == RAFT3_UNIQUE
    # exists_actor works as a plain condition
    cond = exists_actor(lambda i, s: s.role == LEADER)
    final = checker.discoveries()["a leader is elected"].final_state()
    assert cond(m, final)


def test_exists_actor_pair_quantifier():
    """Coverage for the fourth factored quantifier: a sometimes-property
    over actor PAIRS (two servers granted to the same candidate) agrees
    host=device."""
    from stateright_tpu.actor.device_props import exists_actor_pair

    m = raft_model(3)
    m.property(
        Expectation.SOMETIMES,
        "two granted the same candidate",
        exists_actor_pair(
            lambda i, si, j, sj: si.voted_for != -1
            and si.voted_for == sj.voted_for
        ),
    )
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 14)
    assert "two granted the same candidate" in h.discoveries()
    assert "two granted the same candidate" in c.discoveries()


def test_too_tight_compile_bound_fails_loudly():
    """A state_bound that cuts REACHABLE states must fail the run, not
    silently truncate the space (poisoned rows previously deduped onto
    self-loops and produced a plausible-looking wrong count)."""
    from stateright_tpu.parallel.actor_compiler import compile_actor_model

    m = raft_model(3)  # reaches term 2; bound it at 1
    tm = compile_actor_model(
        m,
        state_bound=lambda i, s: s.term <= 1,
        env_bound=lambda e: e.msg[1] <= 1,
    )
    m.tensor_model = lambda: tm
    with pytest.raises(RuntimeError, match="poisoned"):
        m.checker().spawn_tpu(sync=True, capacity=1 << 14)
    with pytest.raises(RuntimeError, match="poisoned"):
        m.checker().spawn_tpu(
            sync=True, devices=8, capacity=1 << 14,
            frontier_capacity=1 << 9,
        )

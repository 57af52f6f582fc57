"""Process-parallel BFS (``checker/mp.py``): parity with the thread oracle.

The mp checker is the honest multi-core CPU baseline;
its per-state semantics must be indistinguishable from ``spawn_bfs`` —
pinned unique counts, same discoveries, valid reconstructed paths — while
its plumbing (fp-ownership sharding, all-to-all rounds, double-barrier
termination) is the CPU analogue of the mesh engine's table sharding.
"""

import pytest

from stateright_tpu.checker.mp import spawn_mp_bfs
from stateright_tpu.core import Model, Property
from stateright_tpu.fingerprint import stable_hash

from fixtures import LinearEquation


class TwoPhase3:
    def __new__(cls):
        from stateright_tpu.models.two_phase_commit import TwoPhaseSys

        return TwoPhaseSys(3)


def test_mp_pinned_counts_and_discovery_parity():
    # 2pc @ 3 RMs: 288 unique (reference examples/2pc.rs:128)
    c = spawn_mp_bfs(TwoPhase3(), workers=3)
    assert c.unique_state_count() == 288
    ref = TwoPhase3().checker().spawn_bfs().join()
    assert sorted(c.discoveries()) == sorted(ref.discoveries())
    assert c.state_count() == ref.state_count()


def test_mp_paths_are_valid_and_reach_discovery():
    m = LinearEquation(2, 10, 14)
    c = spawn_mp_bfs(m, workers=2)
    ref = m.checker().spawn_bfs().join()
    # early exit (all properties discovered) lands at ROUND granularity in
    # BSP, so the mp run may overshoot the thread checker's mid-block stop
    # by up to one wavefront — same relaxation the device engines get
    assert c.unique_state_count() >= ref.unique_state_count()
    for name, path in c.discoveries().items():
        prop = m.property_by_name(name)
        # the path re-executes the model by construction (Path
        # reconstruction raises on an invalid trace); its final state must
        # actually witness the property
        assert prop.condition(m, path.final_state())


def test_mp_target_states_stops_early():
    # 0x + 0y = 1 is unsolvable, so only the target can stop the run short
    # of the full 65,536-state space
    c = spawn_mp_bfs(LinearEquation(0, 0, 1), workers=2,
                     target_states=500)
    # BSP rounds overshoot by at most one wavefront, never undershoot
    assert 500 <= c.unique_state_count() < 65_536


class _Exploding(Model):
    def init_states(self):
        return [0]

    def actions(self, state):
        return [1]

    def next_state(self, state, action):
        if state >= 3:
            raise RuntimeError("model bug at depth 3")
        return state + action

    def properties(self):
        return [Property.always("fine", lambda m, s: True)]


def test_mp_worker_error_propagates():
    with pytest.raises(RuntimeError, match="model bug at depth 3"):
        spawn_mp_bfs(_Exploding(), workers=2)


def test_mp_visitor_observes_every_state_thread_bfs_visits():
    """Multi-core CPU + visitor (the reference forces a choice: its
    visitor hook exists only on the thread checkers): workers record
    per-round visit order and the parent replays it, so a StateRecorder
    sees exactly the full explored space."""
    from stateright_tpu.checker.visitor import StateRecorder

    m = TwoPhase3()
    rec_mp = StateRecorder()
    c = m.checker().visitor(rec_mp).spawn_mp_bfs(processes=3).join()
    assert c.unique_state_count() == 288
    rec_ref = StateRecorder()
    TwoPhase3().checker().visitor(rec_ref).spawn_bfs().join()
    assert len(rec_mp.states) == len(rec_ref.states) == 288
    assert set(map(stable_hash, rec_mp.states)) == set(
        map(stable_hash, rec_ref.states)
    )


def test_mp_visitor_paths_are_valid_and_deterministic():
    """Replayed visit paths re-execute the model (Path reconstruction
    raises otherwise) and the visit SEQUENCE — order included — is
    identical run to run for a fixed worker count (StateRecorder keeps
    insertion order, unlike PathRecorder's set)."""
    from stateright_tpu.checker.visitor import StateRecorder

    seqs = []
    for _ in range(2):
        rec = StateRecorder()
        m = TwoPhase3()
        m.checker().visitor(rec).spawn_mp_bfs(processes=2).join()
        seqs.append([stable_hash(s) for s in rec.states])
    assert seqs[0] == seqs[1]  # exact order, not just the same multiset
    assert len(seqs[0]) == 288


def test_mp_visitor_composes_with_symmetry():
    """Visitor + symmetry + multi-core together (impossible in the
    reference, where symmetry is DFS-only and visitors thread-only):
    the recorder sees one ORIGINAL state per symmetry class."""
    from stateright_tpu.checker.visitor import StateRecorder
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    rec = StateRecorder()
    c = (
        TwoPhaseSys(5)
        .checker()
        .symmetry()
        .visitor(rec)
        .spawn_mp_bfs(processes=2)
        .join()
    )
    assert c.unique_state_count() == TPC5_SYM_BY_WORKERS[2]
    assert len(rec.states) == TPC5_SYM_BY_WORKERS[2]


# Reduced counts are visit-order-dependent (representatives are not
# class-invariant), but the BSP schedule is deterministic for a fixed
# worker count, so counts pin EXACTLY per n.  n=1 is FIFO BFS order and
# equals the host FIFO oracle — the engine-independent parity signal the
# device engines are pinned against too.
TPC5_SYM_BY_WORKERS = {1: 508, 2: 723, 4: 665}


def test_mp_symmetry_reduces_and_matches_fifo_oracle():
    """Multi-core CPU + symmetry (reference: DFS-only, ``dfs.rs:260-269``;
    the round-4 fence ``mp.py:34-36`` is gone): dedup on the class key
    ``stable_hash(representative(state))`` routed to class owners."""
    import sys as _sys
    from pathlib import Path as _P

    _sys.path.insert(0, str(_P(__file__).parent))
    from test_tensor_models import host_fifo_sym_oracle

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    assert host_fifo_sym_oracle(TwoPhaseSys(5)) == TPC5_SYM_BY_WORKERS[1]
    for n, expected in TPC5_SYM_BY_WORKERS.items():
        c = TwoPhaseSys(5).checker().symmetry().spawn_mp_bfs(processes=n)
        assert c.unique_state_count() == expected, (n, c.unique_state_count())
        assert sorted(c.discoveries()) == [
            "abort agreement", "commit agreement",
        ]


def test_mp_symmetry_paths_are_original_state_traces():
    """The search continues with ORIGINAL states (the ``dfs.rs:394-483``
    regression subtlety): parent pointers chain real fingerprints, so
    discovery paths re-execute without a class-matching walk and their
    final states witness the property."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(5)
    c = m.checker().symmetry().spawn_mp_bfs(processes=2)
    for name, path in c.discoveries().items():
        prop = m.property_by_name(name)
        assert prop.condition(m, path.final_state())
        assert len(path.actions()) >= 1

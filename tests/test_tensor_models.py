"""Object-form ⇄ tensor-form equivalence, and TPU-engine parity vs CPU oracle.

The equivalence obligation (SURVEY §7.1): for every reachable state, the
tensor twin's encode/decode round-trips, its jitted ``step_rows`` produces
exactly the object model's successor set, and host/device fingerprints agree.
Then the wavefront engine must reproduce the reference's pinned unique-state
counts (288 @ 3 RMs, 8,832 @ 5 RMs — reference ``examples/2pc.rs:125-140``)
and the CPU checkers' discovery behavior.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from stateright_tpu.fingerprint import hash_words
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops import row_hash


def reachable_states(model, limit=100_000):
    seen = {}
    frontier = list(model.init_states())
    for s in frontier:
        seen[model.fingerprint_state(s)] = s
    while frontier:
        nxt = []
        for s in frontier:
            for t in model.next_states(s):
                fp = model.fingerprint_state(t)
                if fp not in seen:
                    seen[fp] = t
                    nxt.append(t)
        frontier = nxt
        assert len(seen) < limit
    return list(seen.values())


@pytest.mark.parametrize("n", [2, 3])
def test_tensor_2pc_equivalence(n):
    sys = TwoPhaseSys(n)
    tensor = sys.tensor_model()
    states = reachable_states(sys)

    rows = np.asarray([tensor.encode_state(s) for s in states], np.uint64)
    succ, valid = tensor.step_rows(jnp.asarray(rows))
    succ, valid = np.asarray(succ), np.asarray(valid)
    dev_fps = np.asarray(row_hash(jnp.asarray(rows)))

    for i, s in enumerate(states):
        # encode/decode round-trip
        assert tensor.decode_state(rows[i]) == s
        # host fingerprint = device fingerprint = hash of encoded words
        assert sys.fingerprint_state(s) == int(dev_fps[i])
        assert sys.fingerprint_state(s) == hash_words(int(w) for w in rows[i])
        # successor sets agree (as multisets of encoded rows)
        obj_succs = sorted(
            tuple(tensor.encode_state(t)) for t in sys.next_states(s)
        )
        dev_succs = sorted(
            tuple(int(w) for w in succ[i, a])
            for a in range(tensor.max_actions)
            if valid[i, a]
        )
        assert dev_succs == obj_succs


def test_tensor_2pc_property_masks_match_object_conditions():
    sys = TwoPhaseSys(3)
    tensor = sys.tensor_model()
    states = reachable_states(sys)
    rows = jnp.asarray(
        np.asarray([tensor.encode_state(s) for s in states], np.uint64)
    )
    masks = np.asarray(tensor.property_masks(rows))
    for i, s in enumerate(states):
        for p, prop in enumerate(sys.properties()):
            assert bool(masks[i, p]) == bool(prop.condition(sys, s)), (
                prop.name,
                s,
            )


@pytest.mark.parametrize("n,expected", [(3, 288), (5, 8832)])
def test_tpu_checker_2pc_pinned_counts(n, expected):
    sys = TwoPhaseSys(n)
    checker = sys.checker().spawn_tpu(sync=True)
    assert checker.unique_state_count() == expected
    # full parity with the CPU oracle, including duplicate-counting semantics
    cpu = sys.checker().spawn_bfs().join()
    assert cpu.unique_state_count() == expected
    assert checker.state_count() == cpu.state_count()
    # same discoveries; "consistent" never violated, both agreements found
    assert set(checker.discoveries()) == set(cpu.discoveries()) == {
        "abort agreement",
        "commit agreement",
    }
    checker.assert_properties()


def test_tpu_checker_discovery_paths_are_valid_and_shortest():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(sync=True)
    cpu = sys.checker().spawn_bfs().join()  # single-thread BFS: shortest paths
    for name in ("abort agreement", "commit agreement"):
        path = checker.discovery(name)
        cond = sys.property_by_name(name).condition
        assert cond(sys, path.final_state())
        # wavefront discovery is level-synchronous => shortest, like 1-thread BFS
        assert len(path) == len(cpu.discovery(name))


def test_tpu_checker_capacity_overflow_restarts():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(
        sync=True, capacity=1 << 6, frontier_capacity=1 << 3
    )
    assert checker.unique_state_count() == 288
    assert checker._cap >= 512  # grew past 288/load-factor
    checker.assert_properties()


def test_tpu_checker_target_state_count():
    sys = TwoPhaseSys(5)
    checker = sys.checker().target_states(1000).spawn_tpu(sync=True)
    assert 1000 <= checker.unique_state_count() < 8832


def test_tpu_checker_honors_builder_timeout():
    """``timeout()`` parity with the pool checkers: the device run stops
    cooperatively at a host sync with partial counts, and its final
    snapshot resumes to the full space (a timed-out run loses no work)."""
    sys = TwoPhaseSys(5)
    c = sys.checker().timeout(0.0).spawn_tpu(
        sync=True, steps_per_call=1, frontier_capacity=1 << 6
    )
    assert c.is_done()
    assert c.unique_state_count() < 8832
    snap = c.checkpoint()
    resumed = sys.checker().spawn_tpu(
        sync=True, steps_per_call=1, frontier_capacity=1 << 6, resume=snap
    )
    assert resumed.unique_state_count() == 8832


def test_tpu_checker_requires_tensor_form():
    from stateright_tpu import Model

    class Plain(Model):
        def init_states(self):
            return [0]

        def actions(self, s):
            return []

    with pytest.raises(TypeError, match="tensor form"):
        Plain().checker().spawn_tpu(sync=True)


# -- device-side symmetry reduction -----------------------------------------


def host_fifo_sym_oracle(model):
    """FIFO BFS over ORIGINAL states deduped on the representative's
    structural hash — the engine-independent semantics the device engine
    implements.  (Symmetry-reduced *counts* are visit-order-dependent when
    the representative is not class-invariant — the reference's own DFS
    count, 665 @ 5 RMs with 2pc's sort-by-rm-state representative, differs
    from any BFS engine's for the same reason — so the device pins BFS-order
    counts against this oracle instead.)"""
    from collections import deque

    from stateright_tpu.fingerprint import stable_hash

    key = lambda s: stable_hash(s.representative())  # noqa: E731
    seen, q = set(), deque()
    for s in model.init_states():
        k = key(s)
        if k not in seen:
            seen.add(k)
            q.append(s)
    while q:
        s = q.popleft()
        for t in model.next_states(s):
            k = key(t)
            if k not in seen:
                seen.add(k)
                q.append(t)
    return len(seen)


def test_2pc_tpu_symmetry_matches_host_oracle():
    """Device symmetry reduction (representative_rows): counts match the
    host FIFO+representative-dedup oracle exactly (508 @ 5 RMs, vs 9 832
    unreduced and 665 on the reference's DFS ordering), and discoveries
    survive the reduction with genuine traces."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    checker = TwoPhaseSys(5).checker().symmetry().spawn_tpu(
        sync=True, capacity=1 << 14, frontier_capacity=1 << 9
    )
    assert checker.unique_state_count() == 508
    assert checker.unique_state_count() == host_fifo_sym_oracle(TwoPhaseSys(5))
    assert set(checker.discoveries()) == {"abort agreement", "commit agreement"}
    # discovery traces are genuine model paths (canonical-class matching)
    for name, path in checker.discoveries().items():
        m = TwoPhaseSys(5)
        assert m.property_by_name(name).condition(m, path.final_state())


@pytest.mark.medium
def test_2pc_mesh_symmetry_reduces_and_discovers():
    """Symmetry reduction on eight devices: the reduced count is the
    one-device engine's (the mesh engine runs its program), below the
    full space, with valid discoveries."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    checker = TwoPhaseSys(4).checker().symmetry().spawn_tpu(
        devices=8, sync=True, capacity=1 << 13, frontier_capacity=1 << 8
    )
    full = TwoPhaseSys(4).checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert checker.unique_state_count() < full.unique_state_count()
    solo = TwoPhaseSys(4).checker().symmetry().spawn_tpu(
        sync=True, capacity=1 << 13
    )
    assert checker.unique_state_count() == solo.unique_state_count()
    assert set(checker.discoveries()) == {"abort agreement", "commit agreement"}


def test_representative_rows_matches_object():
    """Device canonicalizer == encode(representative(decode(row))) on every
    reachable state of the 3-RM system."""
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(3)
    tm = m.tensor_model()
    seen, frontier = set(), list(m.init_states())
    states = []
    while frontier:
        s = frontier.pop()
        fp = m.fingerprint_state(s)
        if fp in seen:
            continue
        seen.add(fp)
        states.append(s)
        frontier.extend(m.next_states(s))
    rows = jnp.asarray(
        np.asarray([tm.encode_state(s) for s in states], np.uint64)
    )
    got = np.asarray(tm.representative_rows(rows))
    want = np.asarray(
        [tm.encode_state(s.representative()) for s in states], np.uint64
    )
    np.testing.assert_array_equal(got, want)


def test_custom_symmetry_fn_rejected_on_device():
    import pytest

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    b = TwoPhaseSys(3).checker().symmetry_with(lambda s: s)
    with pytest.raises(NotImplementedError, match="symmetry_with"):
        b.spawn_tpu(sync=True)

"""Multi-device sharded wavefront engine parity (8 virtual CPU devices).

The sharded engine (mesh + all-to-all fingerprint routing,
``stateright_tpu/parallel/sharded.py``) must reproduce exactly the counts and
discoveries of the single-device engine and the CPU oracle — the same parity
bar the reference pins for its multithreaded checkers (reference
``examples/2pc.rs:125-140``).
"""

import numpy as np
import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.sharded import ShardedTpuChecker, default_mesh


def test_default_mesh_uses_all_devices():
    mesh = default_mesh()
    assert mesh.shape["d"] == len(jax.devices()) == 8


@pytest.mark.parametrize("n,expected", [(3, 288), (5, 8832)])
def test_sharded_2pc_pinned_counts(n, expected):
    sys = TwoPhaseSys(n)
    checker = sys.checker().spawn_tpu(devices=8, sync=True)
    assert isinstance(checker, ShardedTpuChecker)
    assert checker.unique_state_count() == expected
    cpu = sys.checker().spawn_bfs().join()
    assert cpu.unique_state_count() == expected
    assert checker.state_count() == cpu.state_count()
    assert set(checker.discoveries()) == set(cpu.discoveries()) == {
        "abort agreement",
        "commit agreement",
    }
    checker.assert_properties()


def test_sharded_discovery_paths_are_valid_and_shortest():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(devices=8, sync=True)
    cpu = sys.checker().spawn_bfs().join()  # single-thread BFS: shortest paths
    for name in ("abort agreement", "commit agreement"):
        path = checker.discovery(name)
        cond = sys.property_by_name(name).condition
        assert cond(sys, path.final_state())
        # level-synchronous wavefront => shortest witness, like 1-thread BFS
        assert len(path) == len(cpu.discovery(name))


def test_sharded_capacity_overflow_grows():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 8, frontier_capacity=1 << 5
    )
    assert checker.unique_state_count() == 288
    checker.assert_properties()


@pytest.mark.medium
def test_sharded_growth_preserves_work_mid_flight():
    """Capacities far below the state space force mid-run growth events;
    the atomic-step + host-grow protocol must preserve all work: pinned
    counts, discovery parity with the CPU oracle, and a monotone unique
    counter across every growth boundary (the old engine restarted from
    scratch and reset counters)."""
    sys = TwoPhaseSys(5)
    checker = sys.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 10, frontier_capacity=1 << 7,
        steps_per_call=1,
    )
    assert checker.unique_state_count() == 8832  # examples/2pc.rs:133
    cpu = sys.checker().spawn_bfs().join()
    assert checker.state_count() == cpu.state_count()
    assert set(checker.discoveries()) == set(cpu.discoveries())
    # growth really happened mid-flight, and never lost progress
    assert checker.growth_events, "capacities were too generous to test growth"
    uniq = [u for _, u in checker.growth_events]
    assert uniq == sorted(uniq)
    assert all(0 < u <= 8832 for u in uniq)


@pytest.mark.medium
def test_sharded_growth_boundary_checkpoint_resume():
    """A snapshot carrying a growth-boundary flag (status != OK) must grow
    on resume and still finish with pinned counts.  A checkpoint request
    served at a growth boundary produces exactly this snapshot shape; the
    boundary statuses are forced here so the test is deterministic."""
    kw = dict(devices=8, capacity=1 << 13, frontier_capacity=1 << 9,
              steps_per_call=1)
    running = TwoPhaseSys(5).checker().spawn_tpu(**kw)
    snap = running.checkpoint(timeout=120.0)
    running.stop().join()
    assert 0 < int(snap["unique"]) < 8832, "checkpoint was not mid-run"
    for status in (2, 1):  # _TABLE_OVERFLOW (shard rehash), _FRONTIER (pad)
        s = dict(snap)
        s["status"] = np.int32(status)
        resumed = TwoPhaseSys(5).checker().spawn_tpu(
            sync=True, resume=s, **kw
        )
        assert resumed.unique_state_count() == 8832
        resumed.assert_properties()


def test_sharded_target_state_count():
    sys = TwoPhaseSys(5)
    checker = sys.checker().target_states(1000).spawn_tpu(devices=8, sync=True)
    assert 1000 <= checker.unique_state_count() < 8832


def test_sharded_matches_single_device_table_contents():
    """Every fingerprint the single-device engine visits must appear in the
    union of the sharded engine's table shards, and vice versa."""
    sys = TwoPhaseSys(3)
    single = sys.checker().spawn_tpu(sync=True)
    sharded = sys.checker().spawn_tpu(devices=8, sync=True)
    assert set(single._parents()) == set(sharded._parents())
    # parent pointers may differ (different wave tie-breaks) but each parent
    # must itself be a visited state or 0 (init marker)
    visited = set(sharded._parents())
    for fp, parent in sharded._parents().items():
        assert parent == 0 or parent in visited


def test_sharded_on_two_devices():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(devices=2, sync=True)
    assert checker.unique_state_count() == 288


def test_sharded_live_progress_counters():
    """The chunked host loop surfaces live counters mid-run (the old
    whole-run jit call hid everything until completion)."""
    import time

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    checker = TwoPhaseSys(5).checker().spawn_tpu(
        devices=8, capacity=1 << 17, frontier_capacity=1 << 12,
        steps_per_call=1,
    )
    samples = []
    while not checker.is_done():
        samples.append(checker.unique_state_count())
        time.sleep(0.05)
    checker.join()
    assert checker.unique_state_count() == 8832
    # monotone live counters (no overflow restart at these capacities)
    assert samples == sorted(samples)


@pytest.mark.medium
def test_sharded_checkpoint_resume_matches_uninterrupted():
    """Stop a sharded run mid-flight, snapshot, resume on a fresh checker:
    final counts and discoveries must match the uninterrupted run."""
    import numpy as np

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    kw = dict(devices=8, capacity=1 << 15, frontier_capacity=1 << 10,
              steps_per_call=1)
    full = TwoPhaseSys(5).checker().spawn_tpu(sync=True, **kw)

    # start async, snapshot early, stop, resume from the snapshot
    running = TwoPhaseSys(5).checker().spawn_tpu(**kw)
    snap = running.checkpoint()
    running.stop().join()
    resumed = TwoPhaseSys(5).checker().spawn_tpu(sync=True, resume=snap, **kw)
    assert resumed.unique_state_count() == full.unique_state_count() == 8832
    assert set(resumed.discoveries()) == set(full.discoveries())
    # snapshots survive a real savez/load round trip AND resume from the
    # loaded NpzFile (0-d scalars, ndev coercion, key set)
    import io

    buf = io.BytesIO()
    np.savez(buf, **snap)
    buf.seek(0)
    loaded = dict(np.load(buf, allow_pickle=False))
    resumed2 = TwoPhaseSys(5).checker().spawn_tpu(
        sync=True, resume=loaded, **kw
    )
    assert resumed2.unique_state_count() == 8832


def test_sharded_resume_rejects_other_model_or_mesh():
    import pytest

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    kw = dict(devices=8, capacity=1 << 13, frontier_capacity=1 << 9)
    c = TwoPhaseSys(3).checker().spawn_tpu(sync=True, **kw)
    snap = c.checkpoint()
    with pytest.raises(ValueError, match="different model"):
        TwoPhaseSys(4).checker().spawn_tpu(sync=True, resume=snap, **kw)
    with pytest.raises(ValueError, match="mesh"):
        TwoPhaseSys(3).checker().spawn_tpu(
            sync=True, devices=4, capacity=1 << 13, frontier_capacity=1 << 9,
            resume=snap,
        )
    # cross-engine confusion is caught, both directions
    with pytest.raises(ValueError, match="engine"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=snap)
    single_snap = TwoPhaseSys(3).checker().spawn_tpu(sync=True).checkpoint()
    with pytest.raises(ValueError, match="engine"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=single_snap, **kw)

"""Wavefront checkpoint/resume (SURVEY §5: "A TPU build at 20× throughput
should add real wavefront checkpointing").

The engine's whole run state is a host-visible carry (table, queue, counters,
discovery fps); ``TpuChecker.checkpoint()`` snapshots it mid-run at a clean
batch boundary and ``spawn_tpu(resume=snap)`` continues it — in the same
process or after a serialize/deserialize round-trip.
"""

import io

import numpy as np
import pytest

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.buckets import SLOTS, bucket_of
from stateright_tpu.ops.hashing import EMPTY


def run_full(n, **kw):
    return TwoPhaseSys(n).checker().spawn_tpu(sync=True, **kw)


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_killed_and_resumed_2pc7_matches_uninterrupted():
    full = run_full(7)
    expected_unique = full.unique_state_count()
    expected_states = full.state_count()
    expected_disc = {
        name: len(path) for name, path in full.discoveries().items()
    }
    assert expected_unique > 100_000  # the run is big enough to interrupt

    # interrupted run: small batches + frequent host syncs, checkpoint taken
    # mid-flight, then the checker is stopped ("killed")
    sys = TwoPhaseSys(7)
    running = sys.checker().spawn_tpu(batch=256, steps_per_call=4)
    snap = running.checkpoint(timeout=120.0)
    running.stop()
    running.join()
    assert int(snap["head"]) < int(snap["tail"]), "checkpoint was not mid-run"
    assert 0 < int(snap["unique"]) < expected_unique

    resumed = TwoPhaseSys(7).checker().spawn_tpu(sync=True, resume=snap)
    assert resumed.unique_state_count() == expected_unique
    assert resumed.state_count() == expected_states
    got_disc = {
        name: len(path) for name, path in resumed.discoveries().items()
    }
    assert got_disc == expected_disc
    resumed.assert_properties()


def test_checkpoint_survives_npz_round_trip():
    sys = TwoPhaseSys(5)
    running = sys.checker().spawn_tpu(batch=64, steps_per_call=2)
    snap = running.checkpoint(timeout=120.0)
    running.stop()
    running.join()

    buf = io.BytesIO()
    np.savez(buf, **snap)
    buf.seek(0)
    loaded = dict(np.load(buf))

    # the file's table is the flat bucket-major one (``ops/buckets.py``,
    # "Where the layout is fixed"): slot ``s`` of bucket ``b`` at ``b * SLOTS
    # + s``, every bucket filled densely from slot 0
    tfp, tpl = loaded["table_fp"], loaded["table_parent"]
    assert tfp.dtype == tpl.dtype == np.uint64 and tfp.ndim == tpl.ndim == 1
    assert tfp.shape == tpl.shape and tfp.shape[0] % SLOTS == 0
    held = np.flatnonzero(tfp != EMPTY)
    assert len(held) == int(loaded["unique"])
    assert np.array_equal(
        bucket_of(tfp[held], tfp.shape[0] // SLOTS), held // SLOTS
    )
    lines = (tfp != EMPTY).reshape(-1, SLOTS)
    assert np.array_equal(
        lines, np.arange(SLOTS)[None, :] < lines.sum(axis=1, keepdims=True)
    )

    resumed = TwoPhaseSys(5).checker().spawn_tpu(sync=True, resume=loaded)
    assert resumed.unique_state_count() == 8832  # examples/2pc.rs:133
    resumed.assert_properties()


def test_resume_rejects_snapshot_from_different_model():
    snap = run_full(3).checkpoint()
    with pytest.raises(ValueError, match="different model"):
        TwoPhaseSys(4).checker().spawn_tpu(sync=True, resume=snap)


def test_checkpoint_after_completion_is_final_state():
    checker = run_full(3)
    snap = checker.checkpoint()
    assert int(snap["unique"]) == 288  # examples/2pc.rs:128
    assert int(snap["head"]) == int(snap["tail"])
    # resuming a finished run is a no-op with identical results
    resumed = TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=snap)
    assert resumed.unique_state_count() == 288
    resumed.assert_properties()


def test_growth_boundary_checkpoint_resume():
    """A snapshot taken at a growth boundary carries ``status != OK``; the
    resume path must apply the growth (rehash/compact) BEFORE stepping and
    finish with pinned counts (``wavefront.py`` resume-growth branch).  The
    engine serves checkpoint requests before growing, so boundary snapshots
    occur naturally; the boundary statuses are forced here so the test is
    deterministic."""
    running = TwoPhaseSys(5).checker().spawn_tpu(batch=64, steps_per_call=2)
    snap = running.checkpoint(timeout=120.0)
    running.stop().join()
    assert 0 < int(snap["unique"]) < 8832, "checkpoint was not mid-run"
    # _STATUS_TABLE_FULL (rehash), _STATUS_QUEUE_FULL (compact),
    # _STATUS_CAND_FULL (budget doubles, no carry transform)
    for status in (2, 1, 3):
        s = dict(snap)
        s["status"] = np.int32(status)
        resumed = TwoPhaseSys(5).checker().spawn_tpu(sync=True, resume=s)
        assert resumed.unique_state_count() == 8832  # examples/2pc.rs:133
        resumed.assert_properties()
        # the rehash handed the flat table across the host (`_grow`) and the
        # final pull reads occupancy and parents from the same flat form
        stats = resumed.occupancy_stats()
        assert stats["occupied"] == 8832 and stats["slots_per_bucket"] == SLOTS
        assert all(len(p) > 1 for p in resumed.discoveries().values())


@pytest.mark.medium
def test_queue_growth_preserves_work():
    # a queue high-water mark far below the state count forces repeated
    # compaction/growth events mid-run; counts must still be exact
    checker = run_full(5, queue_capacity=64, batch=32)
    assert checker.unique_state_count() == 8832
    assert checker._qcap > 64  # a growth event actually happened
    checker.assert_properties()


@pytest.mark.medium
def test_table_growth_preserves_work():
    checker = run_full(5, capacity=1 << 8, batch=32)
    assert checker.unique_state_count() == 8832
    assert checker._cap > (1 << 8)
    checker.assert_properties()


def test_cand_budget_growth_preserves_work():
    """A candidate budget far below the batch's real fanout forces
    _STATUS_CAND_FULL growth events mid-run; the budget doubles (engine
    parameter only — the replayed carry is untouched) and the run still
    finishes with pinned counts.  Regression: the growth branch previously
    never cleared the carry's status word and looped forever."""
    checker = run_full(3, batch=32, cand=16, capacity=1 << 12)
    assert checker.unique_state_count() == 288  # examples/2pc.rs:128
    assert any(status == 3 for status, _ in checker.growth_events)
    assert checker._cand > 16
    checker.assert_properties()


def test_many_init_states_fit_tiny_queue():
    """A model whose init set alone exceeds the queue high-water mark must
    grow cleanly instead of clamp-corrupting the init write (regression:
    init_fn only checked table occupancy)."""
    import numpy as np
    import jax.numpy as jnp

    from stateright_tpu import Expectation, Model, Property
    from stateright_tpu.parallel.tensor_model import (
        TensorBackedModel,
        TensorModel,
    )

    N = 100  # init states; queue_capacity below is far smaller

    class ManyTensor(TensorModel):
        width = 1
        max_actions = 1

        def __init__(self, model):
            self.model = model

        def init_rows(self):
            return np.arange(1, N + 1, dtype=np.uint64).reshape(N, 1)

        def encode_state(self, s):
            return (s,)

        def decode_state(self, row):
            return int(row[0])

        def step_rows(self, rows):
            # each state n steps to n+N once, then n+N is terminal
            w = rows[..., 0]
            succ = (w + jnp.uint64(N))[..., None, None]
            valid = (w <= jnp.uint64(N))[..., None]
            return succ, valid

        def property_masks(self, rows):
            return jnp.ones(rows.shape[:-1] + (1,), bool)

    class Many(TensorBackedModel, Model):
        def tensor_model(self):
            return ManyTensor(self)

        def init_states(self):
            return list(range(1, N + 1))

        def actions(self, s):
            return [0] if s <= N else []

        def next_state(self, s, a):
            return s + N

        def properties(self):
            return [Property(Expectation.ALWAYS, "ok", lambda m, s: True)]

    checker = Many().checker().spawn_tpu(
        sync=True, queue_capacity=16, batch=8, capacity=1 << 10
    )
    assert checker.unique_state_count() == 2 * N
    assert checker.state_count() == 2 * N  # N inits + N successors
    checker.assert_properties()

"""The Pallas visited-set insert kernel (``ops/pallas_insert.py``) must be
bit-identical to the XLA windowed-scatter path — same tables and novelty
verdicts — on random batches and inside the full engine.

On CPU the kernel runs in Pallas interpret mode; on TPU hardware it
compiles to the real DMA kernel (bench A/Bs both paths on chip).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from stateright_tpu.ops.buckets import SLOTS, bucket_insert
from stateright_tpu.ops.hashing import EMPTY


def random_batch(rng, m, nbuckets, dup_rate=0.3):
    fps = rng.integers(1, 1 << 60, size=m, dtype=np.uint64)
    # force duplicates and empties
    dup = rng.random(m) < dup_rate
    fps[dup] = fps[rng.integers(0, m, size=dup.sum())]
    fps[rng.random(m) < 0.1] = np.uint64(EMPTY)
    payloads = rng.integers(0, 1 << 60, size=m, dtype=np.uint64)
    return jnp.asarray(fps), jnp.asarray(payloads)


@pytest.mark.parametrize(
    "m,nbuckets",
    [
        # interpret-mode rounds are slow; the engine-realistic size stays in
        # the fast tier, the tiny-table padding paths run in the medium tier
        pytest.param(64, 16, marks=pytest.mark.medium),
        pytest.param(256, 64, marks=pytest.mark.medium),
        (1024, 256),
    ],
)
def test_pallas_matches_xla_insert(m, nbuckets):
    rng = np.random.default_rng(m * 31 + nbuckets)
    shapes = (nbuckets * SLOTS,)
    tfp_x = jnp.full(shapes, EMPTY, jnp.uint64)
    tpl_x = jnp.zeros(shapes, jnp.uint64)
    tfp_p, tpl_p = tfp_x, tpl_x

    for round_ in range(4):
        fps, payloads = random_batch(rng, m, nbuckets)
        rx = bucket_insert(
            tfp_x, tpl_x, fps, payloads, window=64, use_pallas=False
        )
        rp = bucket_insert(
            tfp_p, tpl_p, fps, payloads, window=64, use_pallas=True
        )
        # (tfp, tpl, sel, n_new, overflow, cand_overflow)
        tfp_x, tpl_x = rx[0], rx[1]
        tfp_p, tpl_p = rp[0], rp[1]
        assert bool(rx[4]) == bool(rp[4]), round_  # overflow agreement
        if bool(rx[4]):
            break
        assert int(rx[3]) == int(rp[3])  # n_new agreement
        # inserted-candidate selection agreement (novelty verdicts)
        np.testing.assert_array_equal(
            np.asarray(rx[2])[: int(rx[3])], np.asarray(rp[2])[: int(rp[3])]
        )
        np.testing.assert_array_equal(np.asarray(tfp_x), np.asarray(tfp_p))
        np.testing.assert_array_equal(np.asarray(tpl_x), np.asarray(tpl_p))


def test_pallas_overflow_writes_nothing():
    from stateright_tpu.ops.buckets import bucket_of

    nbuckets = 4
    tfp = jnp.full((nbuckets * SLOTS,), EMPTY, jnp.uint64)
    tpl = jnp.zeros((nbuckets * SLOTS,), jnp.uint64)
    # >SLOTS distinct fps in one bucket (constructed through the mix64
    # bucket derivation): guaranteed overflow
    colliding, x = [], 1
    while len(colliding) < SLOTS + 1:
        if int(bucket_of(np.uint64(x), nbuckets)) == 0:
            colliding.append(x)
        x += 1
    fps = jnp.asarray(np.asarray(colliding, np.uint64))
    payloads = jnp.arange(SLOTS + 1, dtype=jnp.uint64)
    out = bucket_insert(tfp, tpl, fps, payloads, window=8, use_pallas=True)
    assert bool(out[4]) and int(out[3]) == 0  # overflow, nothing counted
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(tfp))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(tpl))


def test_engine_pinned_count_with_pallas():
    """Full device engine with the Pallas insert: pinned 2pc count parity
    (reference ``examples/2pc.rs:133``: 288 @ 3 RMs).  Off a TPU the
    kernel runs INTERPRETED, and the run says so wherever it says
    ``pallas``: the recorder meta and the report's config flags."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry.report import build_config

    checker = TwoPhaseSys(3).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 12, frontier_capacity=1 << 8, pallas=True
    )
    assert checker.unique_state_count() == 288
    assert set(checker.discoveries()) == {"abort agreement", "commit agreement"}
    meta = checker.flight_recorder.meta_snapshot()
    assert meta["pallas"] is True and meta["pallas_interpret"] is True
    flags = build_config(checker)["flags"]
    assert flags["pallas"] is True and flags["pallas_interpret"] is True

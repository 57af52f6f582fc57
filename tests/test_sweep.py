"""Hyper-batched instance sweeps (stateright_tpu/sweep/, docs/sweep.md).

The acceptance pins, per ISSUE 15:

 - an N>=8-instance sweep reconciles EVERY instance's unique/total
   counts, property verdicts, and discovery traces bit-identically
   against its own sequential oracle run, with exactly ONE cohort
   engine compile (pinned via compile-event count) versus N
   sequentially;
 - sweep off leaves the step jaxpr bit-identical and the engine cache
   unkeyed (the wavefront engine carries zero sweep coupling);
 - kill+resume mid-sweep (the snapshot carries instance tags);
 - fingerprint namespacing: host ``ns_fingerprint`` == device
   ``ns_hash`` bit-for-bit, order-preserving within an instance.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.fingerprint import (
    mix64,
    ns_fingerprint,
    sweep_ns_bits,
    unmix64,
)
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.sweep import SweepInstance, SweepSpec
from stateright_tpu.sweep.cohort import build_cohorts

from fixtures_sweep import BoundedCounterSys, bounded_counter_spec

TPC3 = (288, 1146, 10)  # unique, states, depth (pinned 2pc.rs:138)
PAXOS1 = (265, 482, 13)


def _sweep(spec, *, cartography=False, runs=None, **kw):
    b = spec.instances[0].model.checker()
    telemetry = kw.pop("telemetry", False)
    if cartography or telemetry:
        b = b.telemetry(cartography=cartography)
    if runs:
        b = b.runs(runs)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return b.sweep(spec).spawn_tpu(sync=True, **kw)


def _oracle(model, *, cartography=False, **kw):
    b = model.checker()
    if cartography:
        b = b.telemetry(cartography=True)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return b.spawn_tpu(sync=True, **kw)


def _assert_instance_parity(sweep, key, oracle, cartography=False):
    r = sweep.results[key]
    assert (r.unique, r.states, r.max_depth) == (
        oracle.unique_state_count(),
        oracle.state_count(),
        oracle.max_depth(),
    )
    sd = sweep.instance_discoveries(key)
    od = oracle.discoveries()
    assert sorted(sd) == sorted(od)
    for name in od:
        # discovery traces bit-identical: same states, same actions
        assert sd[name].states() == od[name].states()
        assert sd[name].actions() == od[name].actions()
    if cartography:
        oc, rc = oracle.cartography(), r.cartography
        # exact parity for the generated-state counters; the depth
        # histograms are different ESTIMATORS (sweep = exact bincount,
        # wavefront = sorted-prefix searchsorted) and only reconcile by
        # sum — docs/sweep.md
        assert rc["action_hist"] == oc["action_hist"]
        assert rc["props"] == oc["props"]
        assert rc["fresh_inserts"] == oc["fresh_inserts"]
        assert rc["duplicate_hits"] == oc["duplicate_hits"]
        assert sum(rc["depth_hist"]) == r.unique


# -- fingerprint namespacing ------------------------------------------------


def test_unmix64_inverts_mix64():
    rng = np.random.default_rng(7)
    for x in [0, 1, (1 << 64) - 1] + [
        int(v) for v in rng.integers(0, 1 << 63, 32, dtype=np.uint64)
    ]:
        assert unmix64(mix64(x)) == x
        assert mix64(unmix64(x)) == x


def test_ns_fingerprint_matches_device_ns_hash():
    from stateright_tpu.ops.hashing import ns_hash

    rng = np.random.default_rng(3)
    fps = rng.integers(1, (1 << 63), 64, dtype=np.uint64)
    for bits, tag, seed in ((1, 0, 0), (3, 5, 0), (4, 9, 12345)):
        host = np.asarray(
            [ns_fingerprint(int(f), tag, seed, bits) for f in fps],
            np.uint64,
        )
        from stateright_tpu.fingerprint import (
            SWEEP_NS_SEED,
            fold64,
        )

        xor = (
            np.uint64(0) if not seed
            else np.uint64(mix64(fold64(SWEEP_NS_SEED, seed)))
        )
        dev = np.asarray(ns_hash(
            jnp.asarray(fps),
            jnp.full((64,), np.uint64(tag)),
            jnp.full((64,), xor),
            bits,
        ))
        assert np.array_equal(host, dev)


def test_ns_is_order_preserving_and_disjoint():
    """Within an instance the sort key keeps the raw order (trace
    parity's mechanism); across instances the namespaced fps are
    disjoint even for IDENTICAL raw fps."""
    rng = np.random.default_rng(11)
    fps = sorted(
        int(v) for v in rng.integers(1, 1 << 62, 128, dtype=np.uint64)
    )
    bits = 3
    keyed = [mix64(ns_fingerprint(f, 2, 0, bits)) for f in fps]
    raw_order = sorted(range(128), key=lambda i: mix64(fps[i]))
    ns_order = sorted(range(128), key=lambda i: keyed[i])
    assert raw_order == ns_order
    a = {ns_fingerprint(f, 0, 0, bits) for f in fps}
    b = {ns_fingerprint(f, 1, 0, bits) for f in fps}
    assert not (a & b)


def test_sweep_ns_bits():
    assert sweep_ns_bits(1) == 1
    assert sweep_ns_bits(2) == 1
    assert sweep_ns_bits(3) == 2
    assert sweep_ns_bits(8) == 3
    assert sweep_ns_bits(9) == 4
    assert sweep_ns_bits(1000) == 10


# -- spec + cohorts ----------------------------------------------------------


def test_spec_rejects_duplicates_and_empty():
    with pytest.raises(ValueError):
        SweepSpec([])
    with pytest.raises(ValueError):
        SweepSpec([
            SweepInstance("a", TwoPhaseSys(3)),
            SweepInstance("a", TwoPhaseSys(3)),
        ])


def test_cohort_grouping_and_const_lifting():
    """Bounded counters with differing bounds unify into ONE cohort
    (the bound is lifted twin data); a 2pc member lands in its own."""
    spec = SweepSpec(
        list(bounded_counter_spec([2, 3, 5]).instances)
        + [SweepInstance("2pc", TwoPhaseSys(3))]
    )
    cohorts = build_cohorts(spec)
    assert [c.K for c in cohorts] == [3, 1]
    assert cohorts[0].unified
    # namespace tags are GLOBAL spec positions, not cohort-local
    assert cohorts[0].global_index == [0, 1, 2]
    assert cohorts[1].global_index == [3]


# -- the acceptance sweep ----------------------------------------------------


@pytest.mark.medium
def test_eight_instance_sweep_one_compile_full_parity():
    """ISSUE 15 acceptance: 8 bound-swept instances, ONE cohort engine
    compile (compile-event count) versus 8 sequentially, and every
    instance's counts/verdicts/traces bit-identical to its own
    sequential oracle."""
    bounds = [1, 2, 3, 4, 5, 6, 7, 8]
    spec = bounded_counter_spec(bounds, counters=2)
    c = _sweep(spec, telemetry=True, cartography=True, batch=32)
    assert len(c.cohorts) == 1 and c.cohorts[0].K == 8
    assert c.engine_compiles == 1
    assert len(c.flight_recorder.records("compile")) == 1
    seq_compiles = 0
    for bound in bounds:
        o = (
            BoundedCounterSys(bound).checker()
            .telemetry(cartography=True)
            .spawn_tpu(sync=True, capacity=1 << 12, batch=32)
        )
        seq_compiles += len(o.flight_recorder.records("compile"))
        r = c.results[f"bc-b{bound}"]
        assert r.unique == (bound + 1) ** 2
        assert r.max_depth == 2 * bound
        _assert_instance_parity(
            c, f"bc-b{bound}", o, cartography=True
        )
    assert seq_compiles >= 8  # one per instance sequentially
    # the sweep ring records tell the same story
    recs = c.flight_recorder.records("sweep")
    events = [r["event"] for r in recs]
    assert events.count("cohort_compile") == 1
    assert events.count("instance_done") == 8
    assert events[-1] == "summary"
    assert recs[-1]["engine_compiles"] == 1


def test_seed_sweep_shares_one_program_and_reconciles():
    """Table-seed fuzzing: same dynamics under distinct namespaces —
    one cohort, one compile, every member at the pinned 2pc-3 counts."""
    spec = TwoPhaseSys(3).sweep_family(4)
    c = _sweep(spec, telemetry=True)
    assert len(c.cohorts) == 1 and c.engine_compiles == 1
    for inst in spec.instances:
        r = c.results[inst.key]
        assert (r.unique, r.states, r.max_depth) == TPC3
        assert sorted(r.chains) == [
            "abort agreement", "commit agreement",
        ]


@pytest.mark.slow
def test_paxos1_hand_twin_member_parity():
    spec = SweepSpec([
        SweepInstance("2pc", TwoPhaseSys(3)),
        SweepInstance("paxos1", paxos_model(1, 3)),
    ])
    c = _sweep(spec, cartography=True, capacity=1 << 13, batch=256)
    assert len(c.cohorts) == 2
    _assert_instance_parity(
        c, "2pc", _oracle(TwoPhaseSys(3), cartography=True,
                          capacity=1 << 13, batch=256),
        cartography=True,
    )
    _assert_instance_parity(
        c, "paxos1", _oracle(paxos_model(1, 3), cartography=True,
                             capacity=1 << 13, batch=256),
        cartography=True,
    )
    r = c.results["paxos1"]
    assert (r.unique, r.states, r.max_depth) == PAXOS1


def test_per_instance_target_early_termination():
    """A targeted instance stops early without stalling (or corrupting)
    the full-enumeration member sharing its cohort."""
    spec = SweepSpec([
        SweepInstance("full", TwoPhaseSys(3)),
        SweepInstance("prefix", TwoPhaseSys(3), target=5),
    ])
    c = _sweep(spec, batch=16)
    assert c.results["full"].unique == TPC3[0]
    pre = c.results["prefix"].unique
    assert 5 <= pre < TPC3[0]


def test_growth_preserves_per_instance_counts():
    spec = SweepSpec([
        SweepInstance("a", TwoPhaseSys(4)),
        SweepInstance("b", TwoPhaseSys(4), seed=9),
    ])
    c = _sweep(spec, capacity=1 << 10, batch=32, steps_per_call=4)
    assert c.growth_events, "tiny capacity must force growth"
    for k in ("a", "b"):
        assert c.results[k].unique == 1568


# -- off-contract ------------------------------------------------------------


def test_sweep_off_is_the_plain_engine_and_cache_unkeyed(monkeypatch):
    """No sweep requested => spawn_tpu returns the plain wavefront
    checker with the pre-sweep cache key and step program; the env knob
    on a model without a family prints the loud one-liner and changes
    NOTHING (key + jaxpr pinned equal)."""
    from stateright_tpu.parallel.wavefront import TpuChecker

    def spawn():
        c = TwoPhaseSys(3).checker().spawn_tpu(
            sync=True, capacity=1 << 12, batch=64
        )
        assert type(c) is TpuChecker
        key = c._engine_key(c._cap, c._qcap, c._batch, c._cand)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return key, str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    k_off, j_off = spawn()
    assert not any("sweep" in str(e) for e in k_off)
    monkeypatch.setenv("STATERIGHT_TPU_SWEEP", "1")

    class NoFamily(TwoPhaseSys):
        pass

    m = NoFamily(3)
    m.sweep_family = None  # the knob finds no family hook
    c2 = m.checker().spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    assert type(c2) is TpuChecker
    monkeypatch.delenv("STATERIGHT_TPU_SWEEP")
    k_on, j_on = spawn()
    assert k_on == k_off and j_on == j_off


def test_env_knob_routes_models_with_a_family(monkeypatch):
    from stateright_tpu.sweep.engine import SweepChecker

    monkeypatch.setenv("STATERIGHT_TPU_SWEEP", "2")
    c = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert isinstance(c, SweepChecker)
    assert len(c.spec.instances) == 2
    for r in c.results.values():
        assert (r.unique, r.states) == TPC3[:2]


def test_sweep_rejects_unsupported_modes():
    spec = SweepSpec([SweepInstance("a", TwoPhaseSys(3))])
    for cfg in (
        lambda b: b.por(),
        lambda b: b.spill(),
        lambda b: b.checked(),
        lambda b: b.mxu(),
        lambda b: b.prededup(),
        lambda b: b.symmetry(),
        lambda b: b.autosave("/tmp/nope"),
    ):
        with pytest.raises(NotImplementedError):
            cfg(TwoPhaseSys(3).checker().sweep(spec)).spawn_tpu(
                sync=True
            )
    with pytest.raises(NotImplementedError):
        TwoPhaseSys(3).checker().sweep(spec).spawn_tpu(devices=2)


# -- kill + resume mid-sweep -------------------------------------------------


@pytest.mark.medium
def test_kill_resume_mid_sweep(tmp_path):
    """The snapshot carries instance tags + completed-instance results;
    the resumed sweep finishes every member at oracle counts with the
    lineage header set."""
    import time

    spec = SweepSpec([
        SweepInstance("2pc-3", TwoPhaseSys(3)),
        SweepInstance("2pc-5", TwoPhaseSys(5)),
    ])
    c = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .sweep(spec).spawn_tpu(
            capacity=1 << 12, batch=64, steps_per_call=2
        )
    )
    deadline = time.monotonic() + 60
    snap = None
    while time.monotonic() < deadline:
        try:
            snap = c.checkpoint(timeout=10)
            break
        except (TimeoutError, RuntimeError):
            if c.is_done():
                snap = c.checkpoint()
                break
    assert snap is not None and "q_tag" in snap
    c.stop().join()
    p = tmp_path / "sweep.npz"
    np.savez(p, **{k: np.asarray(v) for k, v in snap.items()})
    loaded = dict(np.load(p, allow_pickle=False))
    spec2 = SweepSpec([
        SweepInstance("2pc-3", TwoPhaseSys(3)),
        SweepInstance("2pc-5", TwoPhaseSys(5)),
    ])
    c2 = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .sweep(spec2).spawn_tpu(
            sync=True, capacity=1 << 12, batch=64, resume=loaded
        )
    )
    assert c2.parent_run_id == c.run_id
    assert c2.results["2pc-3"].unique == 288
    assert c2.results["2pc-5"].unique == 8832
    assert sorted(c2.instance_discoveries("2pc-5")) == [
        "abort agreement", "commit agreement",
    ]
    # the snapshot's banked depth lanes keep the resumed per-instance
    # depth histograms COMPLETE: sum(depth_hist) == unique per instance
    # even across the kill's pre-snapshot growth compactions
    for key, unique in (("2pc-3", 288), ("2pc-5", 8832)):
        dh = c2.results[key].cartography["depth_hist"]
        assert sum(dh) == unique, (key, sum(dh))


def test_resume_refuses_a_foreign_sweep(tmp_path):
    spec = SweepSpec([SweepInstance("a", TwoPhaseSys(3))])
    c = TwoPhaseSys(3).checker().sweep(spec).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    snap = c.checkpoint()
    other = SweepSpec([SweepInstance("b", TwoPhaseSys(4))])
    with pytest.raises(ValueError, match="different sweep"):
        TwoPhaseSys(3).checker().sweep(other).spawn_tpu(
            sync=True, resume=snap
        )
    from stateright_tpu.parallel.wavefront import TpuChecker  # noqa: F401

    with pytest.raises(ValueError, match="sweep"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=snap)


# -- registry + diff ---------------------------------------------------------


def test_registry_per_instance_records_and_identical_diff(tmp_path):
    """One index record per instance tagged sweep_id/instance_key, and
    the sweep-instance-vs-sequential-oracle pair classifies IDENTICAL
    under the contract-aware diff (the one-command parity check)."""
    from stateright_tpu.telemetry.diff import diff_reports
    from stateright_tpu.telemetry.registry import RunRegistry

    runs = str(tmp_path / "runs")
    spec = bounded_counter_spec([2, 3])
    c = _sweep(spec, cartography=True, runs=runs, batch=32)
    c.join()
    reg = RunRegistry(runs)
    idx = reg.index()
    assert len(idx) == 2
    assert {r["instance_key"] for r in idx} == {"bc-b2", "bc-b3"}
    assert all(r["sweep_id"] == c.run_id for r in idx)
    o = (
        BoundedCounterSys(3).checker().telemetry(cartography=True)
        .runs(runs).spawn_tpu(sync=True, capacity=1 << 12, batch=32)
    )
    o.join()
    idx = reg.index()
    swp = next(r for r in idx if r.get("instance_key") == "bc-b3")
    seq = next(r for r in idx if not r.get("sweep_id"))
    d = diff_reports(reg.load(swp["run_id"]), reg.load(seq["run_id"]))
    assert d["verdict"] == "IDENTICAL", d["violations"]
    assert d["config_delta"]["flags.sweep"]["class"] == "identical"
    assert d["config_delta"]["engine"]["a"] == "sweep"
    # tampering an instance record still trips the counts gate
    doc = reg.load(swp["run_id"])
    doc["totals"]["unique"] += 1
    d2 = diff_reports(doc, reg.load(seq["run_id"]))
    assert d2["verdict"] == "DIVERGENT"


def test_runs_verb_groups_sweep_members(tmp_path):
    import io

    from stateright_tpu.models._cli import fleet_runs

    runs = str(tmp_path / "runs")
    spec = bounded_counter_spec([2, 3])
    _sweep(spec, runs=runs, batch=32).join()
    buf = io.StringIO()
    assert fleet_runs([runs], stream=buf) == 0
    out = buf.getvalue()
    assert "2 instance(s)" in out
    assert "verdicts [**]" in out
    assert "[bc-b2]" in out and "[bc-b3]" in out


# -- the mixed-family crawl (lossy/non-lossy paxos + 2pc) --------------------


@pytest.mark.slow
def test_mixed_lossiness_sweep_full_parity():
    """The ISSUE's sweep: 2pc + lossy/non-lossy paxos-1 (hand twin +
    compiled twins, three shape cohorts), every instance reconciling
    counts/verdicts/traces/cartography against its sequential oracle."""
    lossy = paxos_model(1, 3)
    lossy.lossy_network(True)
    spec = SweepSpec([
        SweepInstance("2pc-3", TwoPhaseSys(3)),
        SweepInstance("paxos1", paxos_model(1, 3)),
        SweepInstance("paxos1-lossy", lossy),
    ])
    c = _sweep(spec, cartography=True, capacity=1 << 13, batch=256)
    assert len(c.cohorts) == 3
    oracle_models = {
        "2pc-3": TwoPhaseSys(3),
        "paxos1": paxos_model(1, 3),
        "paxos1-lossy": (lambda m: (m.lossy_network(True), m)[1])(
            paxos_model(1, 3)
        ),
    }
    for key, m in oracle_models.items():
        _assert_instance_parity(
            c, key,
            _oracle(m, cartography=True, capacity=1 << 13, batch=256),
            cartography=True,
        )
    assert c.results["paxos1-lossy"].unique == 2378


@pytest.mark.medium
def test_lossy_cohort_members_unify_across_twin_instances():
    """Two lossy paxos-1 instances compile to ONE cohort program even
    though each carries its own compiled twin object."""
    def lossy():
        m = paxos_model(1, 3)
        m.lossy_network(True)
        return m

    spec = SweepSpec([
        SweepInstance("l0", lossy()),
        SweepInstance("l1", lossy(), seed=3),
    ])
    c = _sweep(spec, telemetry=True, capacity=1 << 15, batch=256)
    assert len(c.cohorts) == 1 and c.engine_compiles == 1
    assert c.results["l0"].unique == c.results["l1"].unique == 2378


# -- CLI verb ----------------------------------------------------------------


def test_sweep_cli_verb(capsys):
    from stateright_tpu.models import two_phase_commit

    two_phase_commit.main([
        "sweep", "2", "--batch=64", "--capacity=4096",
    ])
    out = capsys.readouterr().out
    assert "2 instances over 1 cohort(s), 1 engine compile(s)" in out
    assert "2pc3-seed0: unique=288 states=1146" in out


# -- closure fail-fast estimate (actor_compiler satellite) -------------------


def test_closure_estimator_trips_fast_on_paxos3_per_channel():
    import time

    from stateright_tpu.models.paxos import PaxosState
    from stateright_tpu.parallel.actor_compiler import (
        CompileError,
        compile_actor_model,
    )

    m = paxos_model(3, 3)
    m.per_channel_(True)
    t0 = time.monotonic()
    with pytest.raises(CompileError, match="pre-closure estimate"):
        compile_actor_model(
            m,
            state_bound=lambda i, s: not isinstance(s, PaxosState)
            or s.ballot[0] <= 3,
            env_bound=lambda e: e.msg[0] != "internal"
            or e.msg[1][1][0] <= 3,
        )
    assert time.monotonic() - t0 < 20


def test_closure_estimator_escape_hatch(monkeypatch):
    """STATERIGHT_TPU_CLOSURE_ESTIMATE=off keeps the old exact-wall
    behavior (and legit closures never consult the estimator at all —
    the fleet compiles are pinned elsewhere)."""
    monkeypatch.setenv("STATERIGHT_TPU_CLOSURE_ESTIMATE", "off")
    m = paxos_model(2, 3)
    m.per_channel_(True)
    assert m.tensor_model() is not None


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys

    sys.exit(pytest.main([__file__, "-v"]))

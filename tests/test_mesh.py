"""Mesh-native sharded engine (``parallel/mesh.py`` +
``parallel/partition.py``; docs/mesh.md) — ISSUE 19 acceptance.

The contracts pinned here, in the family's strongest form:

 - mesh-vs-wavefront BIT-IDENTICAL parity — counts, verdicts, discovery
   traces — on 2pc-3 and paxos-1 under the suite's forced 8-device CPU
   mesh, including the per-channel static-routing layout;
 - kill+resume exact totals on the mesh engine, snapshot engine tag,
   and the cross-engine resume rejection;
 - growth preserves both the work AND the sharded placement;
 - the per-shard load / routing-matrix readout is well-formed and rides
   the results;
 - engine selection: ``.mesh()`` / ``--mesh`` / ``STATERIGHT_TPU_MESH``
   arm THIS engine, the old spelling (``devices=``/``n_devices=``/
   ``mesh=`` kwargs) stays the old engine, sweep x mesh is fenced;
 - the partition-rule matcher's guards (scalar, divisibility, no-match,
   flag/layout drift);
 - ZERO hand-written collectives in the mesh path (GSPMD inserts them).
"""

from __future__ import annotations

import ast

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from stateright_tpu.checker.base import CheckerBuilder
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.mesh import MeshTpuChecker
from stateright_tpu.parallel.partition import (
    ENV_MESH,
    MESH_AXES,
    WAVEFRONT_CARRY_RULES,
    build_mesh,
    match_partition_rules,
    resolve_mesh_flag,
    wavefront_carry_names,
)
from stateright_tpu.parallel.wavefront import TpuChecker

TPC3_UNIQUE, TPC3_TOTAL = 288, 1146
PAXOS1_TOTAL, PAXOS1_UNIQUE = 482, 265


def _mesh_spawn(m, **kw):
    kw.setdefault("sync", True)
    return m.checker().mesh().spawn_tpu(**kw)


def _solo_spawn(m, **kw):
    kw.setdefault("sync", True)
    return m.checker().spawn_tpu(**kw)


def _assert_trace_parity(a, b):
    da, db = a.discoveries(), b.discoveries()
    assert set(da) == set(db)
    for name in da:
        assert [str(s) for s in da[name].states()] == [
            str(s) for s in db[name].states()
        ], name


# -- bit-identical parity (the acceptance pins) -------------------------------


def test_mesh_parity_2pc3_counts_verdicts_traces():
    """2pc-3 on the suite's 8-device mesh: every count, the visited
    table contents, every verdict, and every discovery trace must match
    the single-device wavefront bit-for-bit (same programs, partitioned
    placement — parity is by construction, pinned here)."""
    solo = _solo_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=256)
    mesh = _mesh_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=256)
    assert isinstance(mesh, MeshTpuChecker)
    assert mesh.n_devices == 8
    assert (
        mesh.unique_state_count() == solo.unique_state_count() == TPC3_UNIQUE
    )
    assert mesh.state_count() == solo.state_count() == TPC3_TOTAL
    assert mesh.max_depth() == solo.max_depth()
    ts, tm = solo._table_np(), mesh._table_np()
    assert np.array_equal(ts[0], tm[0])
    assert np.array_equal(ts[1], tm[1])
    mesh.assert_properties()
    _assert_trace_parity(solo, mesh)


# cross-engine full-space parity on a consensus model is an
# integration sweep — the daily tier owns it (870s fast-tier budget)
@pytest.mark.medium
def test_mesh_parity_paxos1():
    solo = _solo_spawn(paxos_model(1, 3), capacity=1 << 15, batch=256)
    mesh = _mesh_spawn(paxos_model(1, 3), capacity=1 << 15, batch=256)
    assert (
        mesh.unique_state_count()
        == solo.unique_state_count()
        == PAXOS1_UNIQUE
    )
    assert mesh.state_count() == solo.state_count() == PAXOS1_TOTAL
    mesh.assert_properties()
    _assert_trace_parity(solo, mesh)


def test_mesh_parity_per_channel_static_routing():
    """The first queued unlock: with the per-channel layout armed the
    (src,dst) channel map makes candidate destinations static on the
    mesh — counts and traces must still match the wavefront on the same
    encoding."""
    def pc():
        m = paxos_model(1, 3)
        m.per_channel_()
        return m

    solo = _solo_spawn(pc(), capacity=1 << 15, batch=256)
    mesh = _mesh_spawn(pc(), capacity=1 << 15, batch=256)
    assert (
        mesh.unique_state_count()
        == solo.unique_state_count()
        == PAXOS1_UNIQUE
    )
    assert mesh.state_count() == solo.state_count() == PAXOS1_TOTAL
    _assert_trace_parity(solo, mesh)


# -- kill + resume ------------------------------------------------------------


def test_mesh_kill_resume_exact_totals_and_engine_tag():
    m = TwoPhaseSys(4)
    ref = _solo_spawn(m, capacity=1 << 12, batch=64)
    c = m.checker().mesh().spawn_tpu(
        sync=False, capacity=1 << 12, batch=64, steps_per_call=2
    )
    snap = c.checkpoint()
    c.stop()
    c.join()
    assert snap["engine"] == "mesh"
    r = m.checker().mesh().spawn_tpu(sync=True, resume=snap)
    assert r.unique_state_count() == ref.unique_state_count()
    assert r.state_count() == ref.state_count()
    _assert_trace_parity(ref, r)
    # a mesh snapshot must not silently resume on the plain engine
    with pytest.raises(ValueError, match="engine"):
        m.checker().spawn_tpu(sync=True, resume=snap)


def test_mesh_growth_preserves_work_and_sharding():
    """Capacity growth round-trips the carry through host numpy; the
    re-jitted engine must land the grown table SHARDED again (the
    in_shardings re-shard), with totals matching a pre-sized solo run."""
    m = TwoPhaseSys(4)
    mesh = _mesh_spawn(m, capacity=1 << 9, batch=128)
    assert len(mesh.growth_events) >= 1
    ref = _solo_spawn(m, capacity=1 << 12, batch=128)
    assert mesh.unique_state_count() == ref.unique_state_count()
    assert mesh.state_count() == ref.state_count()
    table = mesh._final_carry[0]
    assert table.sharding.spec == P(MESH_AXES)
    assert not table.sharding.is_fully_replicated
    assert len(table.addressable_shards) == 8


# -- the A/B readout ----------------------------------------------------------


def test_mesh_stats_well_formed_and_in_results():
    mesh = _mesh_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=256)
    stats = mesh.mesh_stats()
    assert stats is not None
    assert stats["devices"] == 8
    assert stats["axes"] == {"host": 1, "chip": 8}
    assert len(stats["shard_load"]) == 8
    assert sum(stats["shard_load"]) == TPC3_UNIQUE
    imb = stats["imbalance"]
    assert imb["max"] >= imb["mean"] > 0 and imb["ratio"] >= 1.0
    route = np.asarray(stats["route_matrix"])
    assert route.shape == (8, 8)
    # every non-init unique state routes parent-owner -> child-owner
    # (2pc has ONE init state, the only row with parent fingerprint 0)
    assert route.sum() == stats["routed_states"] == TPC3_UNIQUE - 1
    assert mesh._results["mesh"] == stats


def test_mesh_stats_ride_cartography_block():
    mesh = (
        TwoPhaseSys(3).checker().mesh().cartography().spawn_tpu(
            sync=True, capacity=1 << 12, batch=256
        )
    )
    cart = mesh._results["cartography"]
    assert cart["shard_load"] == mesh.mesh_stats()["shard_load"]
    assert cart["route_matrix"] == mesh.mesh_stats()["route_matrix"]
    assert "ratio" in cart["shard_imbalance"]


# -- engine selection ---------------------------------------------------------


def test_builder_mesh_selects_mesh_engine(monkeypatch):
    monkeypatch.delenv(ENV_MESH, raising=False)
    c = _mesh_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=64)
    assert isinstance(c, MeshTpuChecker)
    # bounded mesh: .mesh(devices=2)
    c2 = TwoPhaseSys(3).checker().mesh(devices=2).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert c2.n_devices == 2
    assert c2.unique_state_count() == TPC3_UNIQUE


def test_env_knob_and_malformed_warning(monkeypatch, capsys):
    monkeypatch.setenv(ENV_MESH, "1")
    assert resolve_mesh_flag(None, None) == (True, None)
    monkeypatch.setenv(ENV_MESH, "4")
    assert resolve_mesh_flag(None, None) == (True, 4)
    monkeypatch.setenv(ENV_MESH, "0")
    assert resolve_mesh_flag(None, None) == (False, None)
    # explicit builder setting beats the env knob in BOTH directions
    monkeypatch.setenv(ENV_MESH, "1")
    assert resolve_mesh_flag(False, None) == (False, None)
    monkeypatch.setenv(ENV_MESH, "0")
    assert resolve_mesh_flag(True, 2) == (True, 2)
    # a typo'd knob warns loudly and never silently disarms as "off"
    monkeypatch.setenv(ENV_MESH, "yes")
    assert resolve_mesh_flag(None, None) == (False, None)
    assert "malformed" in capsys.readouterr().err


def test_env_knob_spawns_mesh_engine(monkeypatch):
    monkeypatch.setenv(ENV_MESH, "1")
    c = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert isinstance(c, MeshTpuChecker)
    assert c.unique_state_count() == TPC3_UNIQUE


def test_old_spelling_stays_old_engine(monkeypatch):
    """``devices=``/``n_devices=`` keep routing to the OLD shard_map
    engine even with the mesh flag armed — the A/B harness depends on
    the two spellings staying distinct."""
    import stateright_tpu.parallel.sharded as sharded_mod

    calls = []

    class Sentinel:
        def __init__(self, options, **kw):
            calls.append(kw)
            raise RuntimeError("sentinel-constructed")

    monkeypatch.setattr(sharded_mod, "ShardedTpuChecker", Sentinel)
    monkeypatch.setenv(ENV_MESH, "1")
    with pytest.raises(RuntimeError, match="sentinel"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, devices=2)
    assert calls and calls[0].get("n_devices") == 2


def test_sweep_x_mesh_is_fenced():
    from stateright_tpu.sweep.spec import SweepSpec

    from stateright_tpu.models.two_phase_commit import sweep_family

    spec = sweep_family(2)
    assert isinstance(spec, SweepSpec)
    with pytest.raises(NotImplementedError, match="sweep x mesh"):
        TwoPhaseSys(3).checker().sweep(spec).mesh().spawn_tpu(sync=True)


def test_mesh_rejects_pallas_and_oversized_mesh():
    with pytest.raises(NotImplementedError, match="[Pp]allas"):
        TwoPhaseSys(3).checker().mesh().spawn_tpu(
            sync=True, pallas=True, capacity=1 << 12, batch=64
        )
    with pytest.raises(ValueError, match="visible"):
        build_mesh(n_devices=99)


def test_mesh_engine_cache_key_never_collides():
    """The compiled-run cache lives on the SHARED tensor twin: the mesh
    key must carry the engine tag + device ids so a mesh entry never
    answers a single-device lookup (or a different sub-mesh's)."""
    solo = _solo_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=64)
    mesh = _mesh_spawn(TwoPhaseSys(3), capacity=1 << 12, batch=64)
    k_solo = solo._engine_key(
        solo._cap, solo._qcap, solo._batch, solo._cand
    )
    k_mesh = mesh._engine_key(
        mesh._cap, mesh._qcap, mesh._batch, mesh._cand
    )
    assert k_mesh[:-1] == k_solo
    assert k_mesh[-1] == ("mesh",) + tuple(range(8))
    sub = TwoPhaseSys(3).checker().mesh(devices=2).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    k_sub = sub._engine_key(sub._cap, sub._qcap, sub._batch, sub._cand)
    assert k_sub[-1] == ("mesh", 0, 1)
    assert len({k_solo, k_mesh, k_sub}) == 3


# -- partition rules ----------------------------------------------------------


def test_match_partition_rules_guards():
    mesh = build_mesh()  # 1 x 8 over the suite's virtual devices
    names = ("table_fp", "q_rows", "head", "odd_dim")
    avals = (
        jax.ShapeDtypeStruct((1 << 12,), np.uint64),  # divisible: sharded
        jax.ShapeDtypeStruct((640, 3), np.uint64),    # divisible: sharded
        jax.ShapeDtypeStruct((), np.int32),           # scalar: replicated
        jax.ShapeDtypeStruct((13,), np.int32),        # 13 % 8: replicated
    )
    rules = WAVEFRONT_CARRY_RULES + ((r"odd_dim", P(MESH_AXES)),)
    s = match_partition_rules(rules, names, avals, mesh)
    assert s[0].spec == P(MESH_AXES)
    assert s[1].spec == P(MESH_AXES)
    assert s[2].spec == P()
    # divisibility guard replicated the dim (P(None) normalizes to P())
    assert all(ax is None for ax in s[3].spec)
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(
            ((r"^table_", P(MESH_AXES)),), ("stray",),
            (jax.ShapeDtypeStruct((8,), np.int32),), mesh,
        )


def test_wavefront_carry_names_flag_guards():
    base = wavefront_carry_names(13)
    assert base[0] == "table_fp" and base[12] == "status"
    with_err = wavefront_carry_names(16, checked=True)
    assert with_err[13] == "err" and with_err[14] == "cart_0"
    with pytest.raises(ValueError, match="carry has"):
        wavefront_carry_names(13, checked=True, por=True)


# -- no vma collectives in the mesh path --------------------------------------


def test_mesh_engine_needs_no_vma_collectives():
    """The mesh engine's defining property: the compiler, not the code,
    inserts the collectives — the module contains no ``pvary``/``pcast``
    attribute access and no ``shard_map`` use (AST-checked, so
    docstrings don't count)."""
    import stateright_tpu.parallel.mesh as mesh_mod

    tree = ast.parse(open(mesh_mod.__file__).read())
    banned = {"pvary", "pcast", "shard_map"}
    hits = [
        node.attr
        for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and node.attr in banned
    ] + [
        node.id
        for node in ast.walk(tree)
        if isinstance(node, ast.Name) and node.id in banned
    ] + [
        alias.name
        for node in ast.walk(tree)
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
        if alias.name in banned
    ]
    assert not hits, hits


# -- regress --mesh gate (injectable artifacts) -------------------------------


def _good_mesh_leg():
    return {
        "tpu_mesh_states_per_sec": 1000.0,
        "tpu_mesh_solo_states_per_sec": 900.0,
        "tpu_mesh": {
            "model": "2pc-5", "devices": 4,
            "unique": 100, "states": 180,
            "shard_load": [25, 25, 30, 20],
            "imbalance": {"max": 30, "mean": 25.0, "ratio": 1.2},
            "routed_states": 99,
            "sec": 1.0, "solo_sec": 1.1,
            "parity": "IDENTICAL",
        },
    }


def _leg(**over):
    run = _good_mesh_leg()
    run["tpu_mesh"] = dict(run["tpu_mesh"], **over)
    return run


def test_regress_mesh_gate_absence_never_trips():
    import regress

    v = regress.mesh_verdict({}, {})
    assert v["ok"] and not v["present"]
    # a stale/pre-mesh BASELINE never trips a run either way
    v = regress.mesh_verdict(_good_mesh_leg(), {})
    assert v["ok"] and v["present"] and not v["baseline_present"]


def test_regress_mesh_gate_validates_present_legs():
    import regress

    good = _good_mesh_leg()
    v = regress.mesh_verdict(good, {})
    assert v["ok"], v
    assert v["shard_load"] == [25, 25, 30, 20]
    assert v["imbalance_ratio"] == 1.2

    crashed = dict(good, tpu_mesh_error="RuntimeError: boom")
    assert not regress.mesh_verdict(crashed, {})["ok"]

    v = regress.mesh_verdict(_leg(parity="DIVERGENT"), {})
    assert not v["ok"] and any("IDENTICAL" in p for p in v["problems"])

    # a load vector that cannot account for every visited row
    v = regress.mesh_verdict(_leg(shard_load=[25, 25, 30, 19]), {})
    assert not v["ok"] and any(
        "one shard owner" in p for p in v["problems"]
    )
    # ... or whose width disagrees with the mesh
    assert not regress.mesh_verdict(_leg(shard_load=[50, 50]), {})["ok"]

    # routed_states must exclude the init states
    v = regress.mesh_verdict(_leg(routed_states=100), {})
    assert not v["ok"] and any(
        "route nowhere" in p for p in v["problems"]
    )

    v = regress.mesh_verdict(_leg(states=50), {})
    assert not v["ok"] and any("bound uniques" in p for p in v["problems"])

    # injected artifacts are arbitrary JSON: a stringified crash in the
    # block slot must produce a verdict, not a traceback
    trash = dict(good, tpu_mesh="XlaRuntimeError: boom")
    assert not regress.mesh_verdict(trash, {})["ok"]
    assert not regress.mesh_verdict(_leg(devices="8"), {})["ok"]


def test_regress_main_mesh_flag(tmp_path, capsys):
    """End-to-end through regress.main: a fresh run with a good leg
    passes; one with a crashed leg exits 1; a run WITHOUT the leg passes
    (flag-gated, the spill/mxu/sweep/fleet rule)."""
    import json

    import regress

    bp = tmp_path / "base.json"
    bp.write_text(json.dumps({}))
    args = ["--baseline=" + str(bp), "--mesh"]

    def run_file(extra):
        doc = {"fresh": True, **extra}
        p = tmp_path / f"run{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(doc))
        return str(p)

    assert regress.main([run_file(_good_mesh_leg())] + args) == 0
    assert regress.main([run_file({})] + args) == 0
    assert regress.main([run_file({"tpu_mesh_error": "boom"})] + args) == 1
    # stale artifacts never trip the mesh gate (exit 2 is staleness,
    # not a gate failure; --allow-stale with a broken leg still passes)
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"fresh": False, "tpu_mesh_error": "boom"}))
    assert regress.main([str(stale)] + args) == 2
    assert regress.main([str(stale), "--allow-stale"] + args) == 0
    capsys.readouterr()

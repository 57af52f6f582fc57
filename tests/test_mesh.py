"""The mesh engine (``parallel/mesh.py`` + ``parallel/partition.py``;
docs/mesh.md): the one multi-device engine.

The contracts pinned here, in the family's strongest form:

 - mesh-vs-wavefront BIT-IDENTICAL parity — counts, verdicts, discovery
   traces — on 2pc-3 and paxos-1 under the suite's forced 8-device CPU
   mesh, including the per-channel static-routing layout;
 - kill+resume exact totals on the mesh engine, snapshot engine tag,
   and the cross-engine resume rejection;
 - growth preserves both the work AND the sharded placement;
 - the per-shard load / routing-matrix readout is well-formed and rides
   the results;
 - engine selection: every spelling of "more than one device"
   (``devices=`` / ``n_devices=`` / ``mesh=`` / ``.mesh()`` / ``--mesh``
   / ``STATERIGHT_TPU_MESH``) selects THIS engine, spellings that name
   different widths are an error, sweep x mesh is fenced;
 - the pinned 2pc spaces, shortest paths, growth, target counts, live
   counters and resume refusals on the suite's 8-device CPU mesh (the
   cases the deleted ``shard_map`` engine was held to);
 - the partition-rule matcher's guards (scalar, divisibility, no-match,
   flag/layout drift);
 - ZERO hand-written collectives anywhere in the package (GSPMD
   inserts them), and no second engine to write them in.
"""

from __future__ import annotations

import ast
import io
import pathlib
import time

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
)
from stateright_tpu.parallel.wavefront import TpuChecker

TPC3_UNIQUE, TPC3_TOTAL = 288, 1146
TPC5_UNIQUE = 8832  # examples/2pc.rs:133
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
    # growth never loses progress: unique is monotone across boundaries
    uniq = [u for _, u in mesh.growth_events]
    assert uniq == sorted(uniq) and all(u > 0 for u in uniq)
    ref = _solo_spawn(m, capacity=1 << 12, batch=128)
    assert mesh.unique_state_count() == ref.unique_state_count()
    assert mesh.state_count() == ref.state_count()
    table = mesh._final_carry.table_fp
    assert table.sharding.spec == P(MESH_AXES)
    assert not table.sharding.is_fully_replicated
    assert len(table.addressable_shards) == 8


@pytest.mark.medium
def test_mesh_growth_boundary_and_npz_resume():
    """A snapshot carrying a growth-boundary flag (status != OK) must grow
    on resume, on the mesh too (the grown carry re-enters sharded), and a
    snapshot survives a real savez/load round trip.  The boundary
    statuses are forced so the test is deterministic."""
    kw = dict(devices=2, capacity=1 << 13, batch=128, steps_per_call=1)
    running = TwoPhaseSys(5).checker().spawn_tpu(**kw)
    snap = running.checkpoint(timeout=120.0)
    running.stop().join()
    assert 0 < int(snap["unique"]) < TPC5_UNIQUE, "checkpoint was not mid-run"
    buf = io.BytesIO()
    np.savez(buf, **snap)
    buf.seek(0)
    loaded = dict(np.load(buf, allow_pickle=False))
    for status in (0, 2, 1):  # as taken, _STATUS_TABLE_FULL, _QUEUE_FULL
        s = dict(loaded)
        s["status"] = np.int32(status)
        resumed = TwoPhaseSys(5).checker().spawn_tpu(
            sync=True, resume=s, **kw
        )
        assert resumed.unique_state_count() == TPC5_UNIQUE
        resumed.assert_properties()


# -- the cases the deleted shard_map engine was held to -----------------------


def test_build_mesh_takes_all_devices():
    mesh = build_mesh()
    assert mesh.size == len(jax.devices()) == 8
    assert dict(mesh.shape) == {"host": 1, "chip": 8}


@pytest.mark.parametrize("n,expected", [(3, 288), (5, TPC5_UNIQUE)])
def test_mesh_2pc_pinned_counts(n, expected):
    sys = TwoPhaseSys(n)
    checker = sys.checker().spawn_tpu(devices=8, sync=True)
    assert isinstance(checker, MeshTpuChecker)
    assert checker.unique_state_count() == expected
    cpu = sys.checker().spawn_bfs().join()
    assert cpu.unique_state_count() == expected
    assert checker.state_count() == cpu.state_count()
    assert set(checker.discoveries()) == set(cpu.discoveries()) == {
        "abort agreement",
        "commit agreement",
    }
    checker.assert_properties()


def test_mesh_discovery_paths_are_valid_and_shortest():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(devices=8, sync=True)
    cpu = sys.checker().spawn_bfs().join()  # single-thread BFS: shortest paths
    for name in ("abort agreement", "commit agreement"):
        path = checker.discovery(name)
        cond = sys.property_by_name(name).condition
        assert cond(sys, path.final_state())
        # level-synchronous wavefront => shortest witness, like 1-thread BFS
        assert len(path) == len(cpu.discovery(name))


def test_mesh_capacity_overflow_grows():
    sys = TwoPhaseSys(3)
    checker = sys.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 8, frontier_capacity=1 << 5
    )
    assert checker.growth_events, "capacities were too generous to grow"
    assert checker.unique_state_count() == 288
    checker.assert_properties()


def test_mesh_target_state_count():
    sys = TwoPhaseSys(5)
    checker = sys.checker().target_states(1000).spawn_tpu(
        devices=8, sync=True, frontier_capacity=1 << 7
    )
    assert 1000 <= checker.unique_state_count() < TPC5_UNIQUE


def test_mesh_matches_single_device_table_contents():
    """Every fingerprint the single-device engine visits is in the mesh
    engine's table with the SAME parent (one program: no tie-break can
    differ), and every parent is itself visited or the init marker."""
    sys = TwoPhaseSys(3)
    single = sys.checker().spawn_tpu(sync=True)
    mesh = sys.checker().spawn_tpu(devices=8, sync=True)
    assert single._parents() == mesh._parents()
    visited = set(mesh._parents())
    for parent in mesh._parents().values():
        assert parent == 0 or parent in visited


def test_mesh_on_two_devices():
    checker = TwoPhaseSys(3).checker().spawn_tpu(devices=2, sync=True)
    assert checker.n_devices == 2
    assert checker.unique_state_count() == 288


def test_mesh_live_progress_counters():
    """The chunked host loop surfaces live counters mid-run."""
    checker = TwoPhaseSys(5).checker().spawn_tpu(
        devices=8, capacity=1 << 17, frontier_capacity=1 << 7,
        steps_per_call=1,
    )
    samples = []
    while not checker.is_done():
        samples.append(checker.unique_state_count())
        time.sleep(0.05)
    checker.join()
    assert checker.unique_state_count() == TPC5_UNIQUE
    # monotone live counters (no overflow restart at these capacities)
    assert samples == sorted(samples)


def test_mesh_resume_rejects_other_model_or_engine():
    kw = dict(devices=8, capacity=1 << 13, frontier_capacity=1 << 9)
    c = TwoPhaseSys(3).checker().spawn_tpu(sync=True, **kw)
    snap = c.checkpoint()
    with pytest.raises(ValueError, match="different model"):
        TwoPhaseSys(4).checker().spawn_tpu(sync=True, resume=snap, **kw)
    # cross-engine confusion is caught, both directions
    with pytest.raises(ValueError, match="'mesh' engine; this is the 'single'"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=snap)
    single_snap = TwoPhaseSys(3).checker().spawn_tpu(sync=True).checkpoint()
    with pytest.raises(ValueError, match="'single' engine; this is the 'mesh'"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=single_snap, **kw)
    # a snapshot the deleted shard_map engine took is refused by the same
    # message (its carry had another layout)
    old = dict(snap, engine=np.asarray("sharded"))
    with pytest.raises(ValueError, match="'sharded' engine; this is the 'mesh'"):
        TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=old, **kw)
    # the carry is the GLOBAL one, so another mesh width is no other
    # engine: the snapshot finishes on two devices at the same totals
    r = TwoPhaseSys(3).checker().spawn_tpu(sync=True, devices=2, resume=snap)
    assert r.n_devices == 2
    assert r.unique_state_count() == c.unique_state_count() == 288
    assert r.state_count() == c.state_count()


def test_async_run_thread_error_surfaces_at_join(monkeypatch):
    """A failure on the engine's run thread (here: the mesh engine's
    build) must not vanish with the daemon thread: ``join()`` raises."""
    def boom(self, *a, **k):
        raise RuntimeError("build exploded")

    monkeypatch.setattr(MeshTpuChecker, "_build", boom)
    checker = TwoPhaseSys(3).checker().spawn_tpu(
        sync=False, devices=8, capacity=1 << 13, frontier_capacity=1 << 9
    )
    with pytest.raises(RuntimeError, match="build exploded"):
        checker.join()


# -- per-shard load and routing -----------------------------------------------


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


_SPELLINGS = {
    "devices": lambda b: b.spawn_tpu(sync=True, devices=2),
    "n_devices": lambda b: b.spawn_tpu(sync=True, n_devices=2),
    "mesh": lambda b: b.spawn_tpu(sync=True, mesh=build_mesh(2)),
    "builder": lambda b: b.mesh(devices=2).spawn_tpu(sync=True),
}


@pytest.fixture(scope="module")
def solo_runs():
    """One-device reference runs, one per model (shared by the four
    spellings): counts, discoveries, paths."""
    from stateright_tpu.models.raft import raft_model

    models = {
        "2pc3": lambda: TwoPhaseSys(3).checker(),
        "2pc5": lambda: TwoPhaseSys(5).checker(),
        "raft3-sym": lambda: raft_model(3).checker().symmetry(),
    }
    return {
        k: (mk, mk().spawn_tpu(sync=True)) for k, mk in models.items()
    }


@pytest.mark.parametrize("spelling", sorted(_SPELLINGS))
def test_every_spelling_selects_the_mesh_engine(
    spelling, solo_runs, monkeypatch
):
    """``devices=2``, ``n_devices=2``, ``mesh=build_mesh(2)`` and
    ``.mesh(devices=2)`` are one request: a two-device MeshTpuChecker
    whose counts, discoveries and paths equal TpuChecker's — on raft-3
    under symmetry too, where the visit order decides the count."""
    monkeypatch.delenv(ENV_MESH, raising=False)
    for name, (mk, solo) in solo_runs.items():
        assert type(solo) is TpuChecker
        c = _SPELLINGS[spelling](mk())
        assert type(c) is MeshTpuChecker, (spelling, name)
        assert c.n_devices == 2
        assert c.unique_state_count() == solo.unique_state_count(), name
        assert c.state_count() == solo.state_count(), name
        assert c.max_depth() == solo.max_depth(), name
        _assert_trace_parity(solo, c)


def test_conflicting_widths_are_an_error(monkeypatch):
    monkeypatch.delenv(ENV_MESH, raising=False)
    b = TwoPhaseSys(3).checker
    with pytest.raises(ValueError, match="different numbers of devices"):
        b().mesh(devices=4).spawn_tpu(sync=True, devices=2)
    with pytest.raises(ValueError, match="different numbers of devices"):
        b().spawn_tpu(sync=True, devices=2, n_devices=4)
    with pytest.raises(ValueError, match="different numbers of devices"):
        b().spawn_tpu(sync=True, devices=4, mesh=build_mesh(2))
    monkeypatch.setenv(ENV_MESH, "4")
    with pytest.raises(ValueError, match="different numbers of devices"):
        b().spawn_tpu(sync=True, devices=2)
    # the same width said twice is one request; one device is no request
    c = b().mesh(devices=2).spawn_tpu(
        sync=True, devices=2, mesh=build_mesh(2)
    )
    assert type(c) is MeshTpuChecker and c.n_devices == 2
    monkeypatch.delenv(ENV_MESH)
    assert type(b().spawn_tpu(sync=True, devices=1)) is TpuChecker
    assert b()._mesh_request({}) is None


def test_sweep_x_mesh_is_fenced():
    from stateright_tpu.sweep.spec import SweepSpec

    from stateright_tpu.models.two_phase_commit import sweep_family

    spec = sweep_family(2)
    assert isinstance(spec, SweepSpec)
    with pytest.raises(NotImplementedError, match="sweep x mesh"):
        TwoPhaseSys(3).checker().sweep(spec).mesh().spawn_tpu(sync=True)


def test_mesh_rejects_oversized_mesh():
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


# -- one engine, no hand-written collectives ----------------------------------


def test_no_second_engine():
    """The compiler, not the code, inserts the collectives — in every
    module of the package: no ``shard_map``, ``all_to_all``, ``pcast`` or
    ``pvary`` name, attribute or import, and no import of the deleted
    ``parallel.sharded`` (AST-checked, so docstrings don't count)."""
    import stateright_tpu

    banned = {"pvary", "pcast", "shard_map", "all_to_all"}
    root = pathlib.Path(stateright_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    assert len(files) > 50 and root / "parallel" / "mesh.py" in files
    assert not (root / "parallel" / "sharded.py").exists()
    hits = []
    for path in files:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Attribute):
                names = [node.attr]
            elif isinstance(node, ast.Name):
                names = [node.id]
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                names = (getattr(node, "module", None) or "").split(".")
                names += [p for a in node.names for p in a.name.split(".")]
                names = ["parallel.sharded" if n == "sharded" else n
                         for n in names]
            else:
                continue
            hits += [
                f"{path.relative_to(root)}:{node.lineno}: {n}"
                for n in names if n in banned | {"parallel.sharded"}
            ]
    assert not hits, hits


def test_dryrun_multichip_runs_the_mesh_engine(monkeypatch, capsys):
    """The driver's multi-chip dry run (``__graft_entry__.py``): pinned
    2pc counts with growth, kill + resume, the compiled twin, symmetry
    equal to the one-device count — on the mesh engine."""
    import os

    import __graft_entry__ as graft

    # the dry run pins the platform in os.environ; put it back after
    for name in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    graft.dryrun_multichip(2)
    out = capsys.readouterr().out
    assert "dryrun_multichip OK: 2-device mesh engine" in out
    assert "2pc5 unique=8832" in out and "kill+resume exact" in out
    assert "raft3-sym unique=2926 (= one device" in out


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

"""Run ledger & differential observability (telemetry/registry.py +
telemetry/diff.py; docs/telemetry.md "Comparing runs").

Pins the round's contracts:

 - IDENTITY: the run report carries a deterministic ``config`` block with
   a canonical ``config_key``, and a volatile ``run_id`` header — with
   :data:`report.VOLATILE_KEYS` as the SCHEMA the diff engine scrubs by
   (never hand-listed downstream);
 - REGISTRY: ``CheckerBuilder.runs(DIR)`` / ``STATERIGHT_TPU_RUN_DIR``
   archive each completed run (report document + versioned index
   record, golden-schema-pinned + round-trip);
 - ZERO JAXPR IMPACT (the family's strongest contract): registry on or
   off leaves the step jaxpr bit-identical and the engine cache unkeyed,
   both engines;
 - the CONTRACT MATRIX: observability flag deltas classify IDENTICAL,
   ``--por`` ISOMORPHIC (with the explored-count delta reported and
   reduction-direction enforced), pure perf knobs PERF-ONLY, corrupted
   counts DIVERGENT with named violations, different instances
   incomparable;
 - LINEAGE: snapshot manifests carry ``run_id``, resumed runs record
   ``parent_run_id``, the registry links kill+resume chains, and the
   resumed-vs-full compare is the PR-8/PR-10 exact-totals pin as one
   command;
 - the ``compare``/``runs`` CLI verbs (per-example + fleet) and the
   Explorer's ``/.runs`` endpoints with the UNIFIED stable error shape
   (``{"error", "hint"}`` — exactly the ``/.metrics`` telemetry-off
   body's shape).
"""

import copy
import json
import numbers
import urllib.error
import urllib.request

import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry.diff import (
    DIFF_V,
    DIVERGENT,
    IDENTICAL,
    ISOMORPHIC,
    PERF_ONLY,
    diff_reports,
    render_diff,
)
from stateright_tpu.telemetry.registry import (
    ENV_RUN_DIR,
    REGISTRY_V,
    RunRegistry,
)
from stateright_tpu.telemetry.report import VOLATILE_KEYS, config_key

TPC3_UNIQUE, TPC3_STATES = 288, 1146


def _spawn(runs_dir=None, telemetry=True, **kw):
    b = TwoPhaseSys(3).checker()
    if runs_dir is not None:
        b = b.runs(str(runs_dir))
    if telemetry:
        b = b.telemetry(cartography=True, memory=True)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return b.spawn_tpu(sync=True, **kw).join()


@pytest.fixture(scope="module")
def ledger(tmp_path_factory):
    """One populated registry shared by the read-side tests: two
    archived same-config runs + their index records."""
    root = tmp_path_factory.mktemp("ledger")
    c1 = _spawn(runs_dir=root)
    c2 = _spawn(runs_dir=root)
    reg = RunRegistry(str(root))
    return reg, c1, c2


# -- identity: config block + run_id header ----------------------------------


def test_report_carries_config_and_run_identity(tmp_path):
    path = tmp_path / "r.json"
    c = TwoPhaseSys(3).checker().report(str(path)).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    doc = json.loads(path.read_text())
    # volatile header: generated_at + run_id, leading the document, all
    # named by the VOLATILE_KEYS schema
    assert doc["run_id"] == c.run_id and len(c.run_id) == 16
    head = [k for k in doc if k in VOLATILE_KEYS]
    assert list(doc)[: len(head)] == head and "run_id" in head
    cfg = doc["config"]
    assert cfg["model"] == "TwoPhaseSys" and cfg["engine"] == "wavefront"
    assert isinstance(cfg["instance"]["sig"], str)
    assert cfg["key"] == config_key(cfg)
    for flag in ("telemetry", "cartography", "memory", "checked",
                 "prededup", "spill", "por", "symmetry", "prewarm",
                 "compile_cache", "roofline", "sweep"):
        assert flag in cfg["flags"], flag
    # different instance arguments -> different config_key
    from stateright_tpu.telemetry.report import build_config

    other = build_config(
        TwoPhaseSys(4).checker().spawn_tpu(
            sync=True, capacity=1 << 13, batch=64
        )
    )
    assert other["key"] != cfg["key"]
    assert other["instance"]["sig"] != cfg["instance"]["sig"]


# -- registry: archive + golden index schema + round-trip --------------------

_REAL = numbers.Real
_INDEX_REQUIRED = {
    "v": int, "run_id": str, "config_key": str, "model": str,
    "engine": str, "generated_at": str, "path": str, "headline": dict,
}
_INDEX_OPTIONAL = {"parent_run_id": str, "leg": str}
_HEADLINE_REQUIRED = {
    "states": int, "unique": int, "max_depth": int, "done": bool,
    "discoveries": list,
}
_HEADLINE_OPTIONAL = {"states_per_sec": _REAL, "wall_secs": _REAL,
                      "stages": dict}


def _check_index_record(rec: dict) -> list:
    problems = []
    for k, t in _INDEX_REQUIRED.items():
        if not isinstance(rec.get(k), t):
            problems.append(f"index.{k} missing/mistyped: {rec.get(k)!r}")
    for k, v in rec.items():
        if k in _INDEX_REQUIRED:
            continue
        if k not in _INDEX_OPTIONAL:
            problems.append(f"index: UNKNOWN field {k!r} (drift — extend "
                            "the golden deliberately, with its consumer)")
        elif not isinstance(v, _INDEX_OPTIONAL[k]):
            problems.append(f"index.{k} mistyped: {v!r}")
    h = rec.get("headline") or {}
    for k, t in _HEADLINE_REQUIRED.items():
        if not isinstance(h.get(k), t):
            problems.append(f"headline.{k} missing/mistyped: {h.get(k)!r}")
    for k, v in h.items():
        if k in _HEADLINE_REQUIRED:
            continue
        if k not in _HEADLINE_OPTIONAL:
            problems.append(f"headline: UNKNOWN field {k!r}")
        elif not isinstance(v, _HEADLINE_OPTIONAL[k]):
            problems.append(f"headline.{k} mistyped: {v!r}")
    return problems


def test_registry_index_record_matches_golden_schema(ledger):
    reg, c1, c2 = ledger
    recs = reg.index()
    assert len(recs) == 2
    problems = []
    for rec in recs:
        assert rec["v"] == REGISTRY_V == 1
        problems += _check_index_record(rec)
    assert not problems, "\n".join(problems)
    # same configuration -> same config_key; append order preserved
    assert recs[0]["config_key"] == recs[1]["config_key"]
    assert [r["run_id"] for r in recs] == [c1.run_id, c2.run_id]
    h = recs[0]["headline"]
    assert h["unique"] == TPC3_UNIQUE and h["states"] == TPC3_STATES
    assert h["done"] is True


def test_registry_archive_round_trips(ledger):
    reg, c1, _ = ledger
    doc = reg.load(c1.run_id)
    assert doc["run_id"] == c1.run_id
    assert doc["totals"]["unique"] == TPC3_UNIQUE
    assert doc["config"]["key"] == reg.index()[0]["config_key"]
    # the headline accessor reads the index, not the archive
    assert reg.headline(c1.run_id)["unique"] == TPC3_UNIQUE
    # trends group by config_key
    trends = reg.trends()
    (series,) = trends.values()
    assert [s["unique"] for s in series] == [TPC3_UNIQUE, TPC3_UNIQUE]


def test_registry_env_knob_archives_plain_runs(tmp_path, monkeypatch):
    monkeypatch.setenv(ENV_RUN_DIR, str(tmp_path))
    _spawn(telemetry=False)
    recs = RunRegistry(str(tmp_path)).index()
    assert len(recs) == 1 and recs[0]["headline"]["unique"] == TPC3_UNIQUE


def test_registry_skips_malformed_index_lines(ledger, tmp_path):
    reg, *_ = ledger
    tainted = tmp_path / "index.jsonl"
    tainted.write_text(
        open(reg.index_path).read() + "{torn line\n"
    )
    reg2 = RunRegistry(str(tmp_path))
    reg2.index_path = str(tainted)
    assert len(reg2.index()) == 2  # the torn tail hides nothing


# -- zero jaxpr impact + engine cache unkeyed (both engines) -----------------


def _wavefront_build_jaxpr(runs_dir) -> str:
    m = TwoPhaseSys(3)
    b = m.checker()
    if runs_dir:
        b = b.runs(str(runs_dir))
    c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    init_fn, run_fn = c._build(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    # fresh lambda per call: make_jaxpr memoizes on fn identity
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


def test_registry_leaves_run_jaxpr_bit_identical(tmp_path):
    """Strongest form of the contract: the registry is post-run host
    I/O — the device program is bit-identical with it on or off."""
    assert _wavefront_build_jaxpr(None) == _wavefront_build_jaxpr(tmp_path)


def test_registry_does_not_key_the_engine_cache(tmp_path):
    """Registry on/off must share one compiled engine: a plain spawn
    after a registry-armed spawn on the same model is a cache HIT."""
    m = TwoPhaseSys(3)
    kw = dict(sync=True, capacity=1 << 12, batch=64)
    c1 = m.checker().runs(str(tmp_path)).spawn_tpu(**kw)
    n_keys = len(c1.tensor._run_cache)
    c2 = m.checker().spawn_tpu(**kw)
    assert len(c2.tensor._run_cache) == n_keys
    assert c2.unique_state_count() == c1.unique_state_count()
    assert RunRegistry(str(tmp_path)).index(), "armed spawn must archive"


def test_registry_mesh_archives_and_cache_unkeyed(tmp_path):
    m = TwoPhaseSys(3)
    kw = dict(sync=True, n_devices=2, capacity=1 << 12,
              frontier_capacity=1 << 9)
    c1 = m.checker().runs(str(tmp_path)).spawn_tpu(**kw)
    n_keys = len(c1.tensor._run_cache)
    c2 = m.checker().spawn_tpu(**kw)
    assert len(c2.tensor._run_cache) == n_keys
    recs = RunRegistry(str(tmp_path)).index()
    assert recs and recs[0]["engine"] == "mesh"
    assert recs[0]["headline"]["unique"] == TPC3_UNIQUE


# -- the diff engine: contract matrix ----------------------------------------


def test_diff_same_config_pair_is_identical(ledger):
    reg, c1, c2 = ledger
    d = diff_reports(
        reg.load(c1.run_id), reg.load(c2.run_id),
        a_headline=reg.headline(c1.run_id),
        b_headline=reg.headline(c2.run_id),
    )
    assert d["v"] == DIFF_V == 1
    assert d["verdict"] == IDENTICAL and d["contract"] == "same"
    assert d["violations"] == [] and d["config_delta"] == {}
    assert d["blocks"]["totals"]["unique"]["match"] is True
    assert d["blocks"]["cartography"]["match"] is True
    # the wall-clock headline rides as a non-gating perf block
    assert "states_per_sec" in d["blocks"]["perf"]
    assert "IDENTICAL" in render_diff(d)
    # the diff document is JSON-safe and round-trips
    assert json.loads(json.dumps(d)) == d


def test_diff_volatile_fields_ignored_by_schema(ledger, monkeypatch):
    """The scrub consults report.VOLATILE_KEYS at diff time: a NEW
    volatile field registered there is ignored with no diff change."""
    from stateright_tpu.telemetry import report as report_mod

    reg, c1, _ = ledger
    a = reg.load(c1.run_id)
    b = copy.deepcopy(a)
    b["generated_at"] = "2099-01-01T00:00:00+00:00"
    b["run_id"] = "ffffffffffffffff"
    assert diff_reports(a, b)["verdict"] == IDENTICAL
    b["freshly_volatile"] = "zzz"
    monkeypatch.setattr(
        report_mod, "VOLATILE_KEYS",
        report_mod.VOLATILE_KEYS + ("freshly_volatile",),
    )
    d = diff_reports(a, b)
    assert d["verdict"] == IDENTICAL and d["violations"] == []


def test_diff_contract_matrix(ledger):
    reg, c1, _ = ledger
    a = reg.load(c1.run_id)

    # observability delta -> IDENTICAL (blocks may appear/disappear)
    b = copy.deepcopy(a)
    for f in ("telemetry", "cartography", "memory"):
        b["config"]["flags"][f] = False
    b.pop("cartography")
    b.pop("memory")
    d = diff_reports(a, b)
    assert (d["verdict"], d["contract"]) == (IDENTICAL, "observability")

    # pure perf knob -> PERF-ONLY (counts still gated)
    b = copy.deepcopy(a)
    b["config"]["flags"]["prewarm"] = True
    d = diff_reports(a, b)
    assert (d["verdict"], d["contract"]) == (PERF_ONLY, "perf")

    # --por with shrunken counts -> ISOMORPHIC, delta reported
    b = copy.deepcopy(a)
    b["config"]["flags"]["por"] = True
    b["totals"]["states"] -= 45
    b["totals"]["unique"] -= 15
    d = diff_reports(a, b)
    assert (d["verdict"], d["contract"]) == (ISOMORPHIC, "isomorphic")
    assert d["blocks"]["totals"]["unique"]["delta"] == -15
    assert all(p["match"] for p in d["blocks"]["properties"])

    # --por that GREW the space -> DIVERGENT reduction_grew
    b = copy.deepcopy(a)
    b["config"]["flags"]["por"] = True
    b["totals"]["unique"] += 10
    b["totals"]["states"] += 10
    d = diff_reports(a, b)
    assert d["verdict"] == DIVERGENT
    assert any(v["rule"] == "reduction_grew" for v in d["violations"])

    # corrupted counts under a count-identical contract -> DIVERGENT
    # with the violation naming the field
    b = copy.deepcopy(a)
    b["totals"]["unique"] += 1
    d = diff_reports(a, b)
    assert d["verdict"] == DIVERGENT
    (v,) = [x for x in d["violations"] if x["field"] == "totals.unique"]
    assert v["rule"] == "counts_must_match"
    assert (v["a"], v["b"]) == (TPC3_UNIQUE, TPC3_UNIQUE + 1)

    # flipped property verdict -> DIVERGENT verdict_parity (every
    # comparable contract gates on it)
    b = copy.deepcopy(a)
    b["config"]["flags"]["por"] = True
    for p in b["properties"]:
        if p["name"] == "commit agreement":
            p["discovery"] = False
    d = diff_reports(a, b)
    assert d["verdict"] == DIVERGENT
    assert any(v["rule"] == "verdict_parity" for v in d["violations"])

    # different model -> incomparable, DIVERGENT with ONE named violation
    b = copy.deepcopy(a)
    b["model"] = "Other"
    b["config"]["model"] = "Other"
    d = diff_reports(a, b)
    assert (d["verdict"], d["contract"]) == (DIVERGENT, "incomparable")
    assert [v["rule"] for v in d["violations"]] == ["incomparable"]

    # pre-registry pair (no config blocks): unknown contract — equal
    # counts classify IDENTICAL, differing counts ISOMORPHIC (nothing
    # stronger can be promised), verdict parity still gates
    a0, b0 = copy.deepcopy(a), copy.deepcopy(a)
    a0.pop("config")
    b0.pop("config")
    assert diff_reports(a0, b0)["verdict"] == IDENTICAL
    b0["totals"]["unique"] -= 1
    d = diff_reports(a0, b0)
    assert (d["verdict"], d["contract"]) == (ISOMORPHIC, "unknown")


def test_diff_cartography_gates_count_contracts(ledger):
    """A tampered depth histogram with untouched totals still diverges
    under a count-identical contract — the search shape is count-derived
    too."""
    reg, c1, _ = ledger
    a = reg.load(c1.run_id)
    b = copy.deepcopy(a)
    h = list(b["cartography"]["depth_hist"])
    h[0] += 1
    h[1] -= 1
    b["cartography"]["depth_hist"] = h
    d = diff_reports(a, b)
    assert d["verdict"] == DIVERGENT
    assert any(v["field"] == "cartography" for v in d["violations"])


def test_host_prefix_target_enters_the_instance_identity(tmp_path):
    """A host run's target_states is instance identity too (device
    engines store it as _target; the thread-pool checkers only keep the
    builder options): a prefix host run vs a full host run must be
    INCOMPARABLE, not falsely same-config DIVERGENT."""
    from stateright_tpu.telemetry.report import build_config

    full = TwoPhaseSys(3).checker().spawn_bfs().join()
    prefix = TwoPhaseSys(3).checker().target_states(64).spawn_bfs().join()
    cfg_full, cfg_prefix = build_config(full), build_config(prefix)
    assert cfg_full["instance"]["target"] is None
    assert cfg_prefix["instance"]["target"] == 64
    a = {"v": 1, "model": "TwoPhaseSys", "engine": "BfsChecker",
         "config": cfg_full,
         "totals": {"states": 1146, "unique": 288, "max_depth": 0,
                    "done": True},
         "properties": []}
    b = copy.deepcopy(a)
    b["config"] = cfg_prefix
    b["totals"].update(states=158, unique=67)
    d = diff_reports(a, b)
    assert (d["verdict"], d["contract"]) == (DIVERGENT, "incomparable")
    assert [v["rule"] for v in d["violations"]] == ["incomparable"]


def test_diff_cross_engine_pair_gates_unique_only(ledger, tmp_path):
    """Host BFS vs device wavefront on the same instance: the engine
    delta is identical-class, gated on unique counts + verdicts — the
    host engine's different generated-states accounting and missing
    max_depth must not false-positive, while the instance signature
    (twin-resolved on both sides) keeps the pair comparable."""
    reg, c1, _ = ledger
    host = TwoPhaseSys(3).checker().runs(str(tmp_path)).spawn_bfs()
    host.join()
    hreg = RunRegistry(str(tmp_path))
    a, b = reg.load(c1.run_id), hreg.load(host.run_id)
    assert (
        a["config"]["instance"]["sig"] == b["config"]["instance"]["sig"]
    )
    d = diff_reports(a, b)
    assert d["contract"] == "identical"
    assert d["verdict"] == IDENTICAL, d["violations"]
    # ...but a cross-engine UNIQUE drift still diverges
    b2 = copy.deepcopy(b)
    b2["totals"]["unique"] += 1
    d2 = diff_reports(a, b2)
    assert d2["verdict"] == DIVERGENT
    assert any(v["field"] == "totals.unique" for v in d2["violations"])


# -- lineage: snapshot run_id -> parent_run_id -> registry chain -------------


def test_kill_resume_lineage_links_and_compares(tmp_path):
    root = tmp_path / "reg"
    parent = (
        TwoPhaseSys(3).checker().runs(str(root)).target_states(64)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=32)
    )
    parent.join()
    snap = parent.checkpoint()
    assert str(snap["run_id"]) == parent.run_id  # manifest carries it
    resumed = TwoPhaseSys(3).checker().runs(str(root)).spawn_tpu(
        sync=True, resume=snap, capacity=1 << 12, batch=32
    )
    resumed.join()
    assert resumed.parent_run_id == parent.run_id
    reg = RunRegistry(str(root))
    chain = reg.chain(resumed.run_id)
    assert [r["run_id"] for r in chain] == [parent.run_id, resumed.run_id]
    # the resumed run completed the space exactly (PR-8/PR-10 pin)
    assert resumed.unique_state_count() == TPC3_UNIQUE
    assert resumed.state_count() == TPC3_STATES
    # parent -> resumed: lineage contract, monotone, IDENTICAL
    d = diff_reports(reg.load(parent.run_id), reg.load(resumed.run_id))
    assert d["verdict"] == IDENTICAL and d["contract"] == "lineage"
    assert d["lineage"]["parent"] == parent.run_id
    # resumed vs a fresh FULL run: the exact-totals one-command check
    full = _spawn(runs_dir=root, telemetry=False)
    d2 = diff_reports(reg.load(full.run_id), reg.load(resumed.run_id))
    assert d2["verdict"] == IDENTICAL and d2["violations"] == []
    # a resumed run that LOST work diverges loudly
    tampered = copy.deepcopy(reg.load(resumed.run_id))
    tampered["totals"]["unique"] = 10
    d3 = diff_reports(reg.load(parent.run_id), tampered)
    assert d3["verdict"] == DIVERGENT
    assert any(v["rule"] == "resume_lost_work" for v in d3["violations"])


def test_npz_round_tripped_snapshot_keeps_lineage(tmp_path):
    """run_id survives np.savez/np.load like the rest of the manifest
    (kill+resume across processes is the point of the chain)."""
    import numpy as np

    parent = (
        TwoPhaseSys(3).checker().target_states(64)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=32)
    )
    parent.join()
    snap = parent.checkpoint()
    path = tmp_path / "snap.npz"
    np.savez(path, **snap)
    loaded = dict(np.load(path, allow_pickle=False))
    resumed = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, resume=loaded, capacity=1 << 12, batch=32
    )
    resumed.join()
    assert resumed.parent_run_id == parent.run_id
    assert resumed.unique_state_count() == TPC3_UNIQUE


# -- CLI verbs: compare (per-example + fleet) and runs -----------------------


def test_compare_cli_verb_identical_and_tampered(ledger, tmp_path, capsys):
    from stateright_tpu.models.two_phase_commit import main

    reg, c1, c2 = ledger
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(reg.load(c1.run_id)))
    b.write_text(json.dumps(reg.load(c2.run_id)))
    main(["compare", str(a), str(b), "--expect=IDENTICAL"])
    out = capsys.readouterr().out
    assert "verdict: IDENTICAL" in out
    # machine-readable JSON line rides along
    last = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
    assert json.loads(last)["verdict"] == IDENTICAL
    # tampered report -> DIVERGENT, non-empty violations, nonzero exit
    doc = json.loads(b.read_text())
    doc["totals"]["unique"] += 3
    b.write_text(json.dumps(doc))
    with pytest.raises(SystemExit) as e:
        main(["compare", str(a), str(b)])
    assert e.value.code == 1
    out = capsys.readouterr().out
    assert "DIVERGENT" in out and "counts_must_match" in out


def test_compare_cli_resolves_registry_run_ids(ledger, capsys):
    from stateright_tpu.models._cli import compare_reports_cmd

    reg, c1, c2 = ledger
    rc = compare_reports_cmd([
        c1.run_id, c2.run_id, f"--registry={reg.root}",
        "--expect=IDENTICAL",
    ])
    assert rc == 0
    assert "throughput" in capsys.readouterr().out  # headline attached


def test_compare_cli_expect_mismatch_fails(ledger, capsys, tmp_path):
    from stateright_tpu.models._cli import compare_reports_cmd

    reg, c1, c2 = ledger
    rc = compare_reports_cmd([
        c1.run_id, c2.run_id, f"--registry={reg.root}",
        "--expect=ISOMORPHIC",
    ])
    assert rc == 1
    assert "!= expected" in capsys.readouterr().out
    # an explicit --expect=DIVERGENT asserting a known-bad pair exits 0
    # (the expectation is the whole judgement)
    bad = copy.deepcopy(reg.load(c2.run_id))
    bad["totals"]["unique"] += 1
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    rc = compare_reports_cmd([
        c1.run_id, str(p), f"--registry={reg.root}",
        "--expect=DIVERGENT",
    ])
    assert rc == 0


def test_runs_fleet_verb_lists_registry(ledger, capsys):
    from stateright_tpu.models._cli import fleet_runs

    reg, c1, c2 = ledger
    assert fleet_runs([reg.root]) == 0
    out = capsys.readouterr().out
    assert c1.run_id in out and c2.run_id in out
    assert "2 archived over 1 config(s)" in out
    assert "trend" in out
    # no registry anywhere -> loud rc 2, not a crash
    assert fleet_runs([]) == 2


# -- Explorer: /.runs endpoints + unified error bodies -----------------------


def _get(addr, path):
    try:
        with urllib.request.urlopen(f"http://{addr}{path}") as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def runs_server(ledger):
    from stateright_tpu.explorer import serve

    reg, *_ = ledger
    server = serve(
        TwoPhaseSys(3).checker(), "localhost:0", block=False,
        runs_dir=reg.root,
    )
    server.checker.join()
    yield server, reg
    server.shutdown()


def test_explorer_runs_index_and_archive(runs_server):
    server, reg = runs_server
    code, view = _get(server.addr, "/.runs")
    assert code == 200 and view["v"] == REGISTRY_V
    assert len(view["runs"]) == len(reg.index())
    assert view["trends"]
    rid = view["runs"][0]["run_id"]
    code, doc = _get(server.addr, f"/.runs/{rid}")
    assert code == 200 and doc["run_id"] == rid
    assert doc["totals"]["unique"] == TPC3_UNIQUE


def test_explorer_runs_diff_endpoint(runs_server):
    server, reg = runs_server
    ids = [r["run_id"] for r in reg.index()]
    code, d = _get(server.addr, f"/.runs/diff/{ids[0]}/{ids[1]}")
    assert code == 200 and d["verdict"] == IDENTICAL
    assert "perf" in d["blocks"]  # index headlines attached


def test_explorer_error_bodies_are_unified(runs_server):
    """Satellite contract: every /.runs error body has EXACTLY the
    /.metrics telemetry-off shape — {"error": token, "hint": prose} —
    no ad-hoc strings."""
    server, _ = runs_server
    code, body = _get(server.addr, "/.runs/nope")
    assert code == 404 and set(body) == {"error", "hint"}
    assert body["error"] == "unknown_run"
    code, body = _get(server.addr, "/.runs/diff/onlyone")
    assert code == 404 and set(body) == {"error", "hint"}
    assert body["error"] == "bad_diff_request"
    code, body = _get(server.addr, "/.metrics")
    assert code == 404 and set(body) == {"error", "hint"}
    assert body["error"] == "telemetry_disabled"


def test_explorer_without_registry_answers_registry_disabled():
    from stateright_tpu.explorer import serve

    server = serve(
        TwoPhaseSys(3).checker(), "localhost:0", block=False
    )
    try:
        server.checker.join()
        code, body = _get(server.addr, "/.runs")
        assert code == 404 and set(body) == {"error", "hint"}
        assert body["error"] == "registry_disabled"
    finally:
        server.shutdown()

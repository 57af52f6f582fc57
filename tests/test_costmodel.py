"""Roofline cost ledger (analysis/costmodel.py + telemetry/roofline.py).

Pins the round's contracts (docs/roofline.md):

 - ZERO ENGINE IMPACT (the family's strongest form): roofline on or off
   leaves the engine's step jaxpr bit-identical and the engine cache
   unkeyed — the ledger re-traces kernels on the side, it never touches
   the run program;
 - RECONCILIATION: the analytic per-stage FLOPs/bytes totals land
   inside the pinned tolerance bands of XLA's own
   ``compiled.cost_analysis()`` on the 2pc and paxos twins, and the
   purely elementwise ``hash`` stage charges FLOPs EXACTLY equal to
   XLA's count ("exact where XLA reports exact");
 - the run report's ``roofline`` block is DETERMINISTIC (static costs
   only — XLA numbers, device specs, and wall clock never enter the
   JSON body);
 - op classification, per-action attribution via the action-axis
   decomposition, the JX4xx MXU-candidate ranking, the device-spec
   table + ``STATERIGHT_TPU_DEVICE_SPEC`` override, and the CPU
   degradation (no spec ⇒ arithmetic-intensity-only, never a crash).
"""

import json

import pytest

import jax

from stateright_tpu.analysis.costmodel import (
    COSTMODEL_V,
    FLOPS_BAND,
    classify_primitive,
    walk_jaxpr,
    wavefront_costs,
    xla_cost,
)
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.tensor_model import twin_or_none
from stateright_tpu.telemetry.roofline import (
    ENV_DEVICE_SPEC,
    ROOFLINE_V,
    achieved_block,
    classify_stages,
    device_spec,
)

_KW = dict(capacity=1 << 12, batch=64)
_STAGES = ("property", "expand", "hash", "dedup-insert", "queue")


def _twin(model):
    cached = getattr(model, "_tensor_cached", None)
    return cached() if cached is not None else model.tensor_model()


# -- zero engine impact ------------------------------------------------------


def _wavefront_build_jaxpr(roofline: bool) -> str:
    m = TwoPhaseSys(3)
    b = m.checker()
    if roofline:
        b = b.telemetry(roofline=True)
    c = b.spawn_tpu(sync=True, **_KW)
    init_fn, run_fn = c._build(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    # fresh lambda per call: make_jaxpr memoizes on fn identity
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


def test_roofline_leaves_run_jaxpr_bit_identical():
    """The ledger never touches the device program — ON is bit-identical
    to OFF (re-traced side kernels only)."""
    assert _wavefront_build_jaxpr(False) == _wavefront_build_jaxpr(True)


def test_roofline_does_not_key_the_engine_cache():
    """Roofline on/off must share one compiled engine: a roofline-off
    spawn after a roofline-on spawn on the same model is a cache HIT."""
    m = TwoPhaseSys(3)
    c1 = m.checker().telemetry(roofline=True).spawn_tpu(sync=True, **_KW)
    n_keys = len(c1.tensor._run_cache)
    c2 = m.checker().telemetry().spawn_tpu(sync=True, **_KW)
    assert len(c2.tensor._run_cache) == n_keys
    assert c2.unique_state_count() == c1.unique_state_count()


def test_mesh_roofline_block_and_cache_identity():
    """A two-device run carries the wavefront program's ledger (it runs
    that program; the collectives the compiler adds are not priced) and
    names the mesh engine, under the same cache-identity contract."""
    m = TwoPhaseSys(3)
    c1 = (
        m.checker().telemetry(roofline=True)
        .spawn_tpu(sync=True, devices=2, capacity=1 << 12)
    )
    roof = c1.roofline()
    assert roof is not None and roof["engine"] == "mesh"
    assert set(roof["stages"]) == set(
        m.checker().telemetry(roofline=True)
        .spawn_tpu(sync=True, capacity=1 << 12).roofline()["stages"]
    )
    n_keys = len(c1.tensor._run_cache)
    c2 = m.checker().telemetry().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12
    )
    assert len(c2.tensor._run_cache) == n_keys
    assert c2.roofline() is None
    assert c2.unique_state_count() == c1.unique_state_count()


# -- reconciliation (the acceptance-criteria pin) ----------------------------


@pytest.mark.parametrize("model_fn", [
    lambda: TwoPhaseSys(3),
    lambda: paxos_model(1),
], ids=["2pc", "paxos"])
def test_analytic_totals_reconcile_against_xla(model_fn):
    """The pinned contract: every stage's analytic FLOPs/bytes land
    inside the tolerance bands of XLA's own cost_analysis() on the 2pc
    AND paxos twins.  The bands are ``reconcile_stage``'s, its exemption
    of the ``queue`` stage's lower byte bound included: XLA charges a
    dynamic-update-slice at full-buffer scale, the walk one ``qchunk``-row
    chunk of the append - its loop's body, once - and, where a row is wider
    than a word, the payload's one ``cand``-row gather before the loop
    (2pc-3: 26,511 B, ratio 0.145; paxos-1: 786,495 B, 0.587)."""
    m = model_fn()
    twin = _twin(m)
    rep = wavefront_costs(twin, 1 << 12, 1 << 11, 64)
    assert rep is not None
    recon = rep.recon_block()
    assert recon["ok"], recon
    for name in _STAGES:
        assert name in rep.stages, sorted(rep.stages)
        v = recon["stages"][name]
        if v.get("xla_flops"):
            r = v["flops_ratio"]
            assert 1.0 / FLOPS_BAND <= r <= FLOPS_BAND, (name, v)
        assert v["ok"], (name, v)  # the byte bands, as reconcile_stage holds them


def test_hash_stage_flops_exact_where_xla_is_exact():
    """"Exact where XLA reports exact": the hash stage is purely
    elementwise — both models count one scalar op per output element,
    so the analytic FLOPs equal XLA's bit-for-bit on both twins."""
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.ops.hashing import row_hash

    for m in (TwoPhaseSys(3), paxos_model(1)):
        twin = _twin(m)
        np.asarray(twin.init_rows())
        rep = wavefront_costs(twin, 1 << 12, 1 << 11, 64)
        aval = jax.ShapeDtypeStruct(
            (64, twin.max_actions, twin.width), jnp.uint64
        )
        xla = xla_cost(row_hash, (aval,))
        if not xla or not xla.get("flops"):
            pytest.skip("backend exposes no cost_analysis flops")
        assert rep.stages["hash"].flops == xla["flops"]


# -- classification + attribution units --------------------------------------


def test_classify_primitive_covers_the_catalogue():
    assert classify_primitive("gather") == "gather"
    assert classify_primitive("dynamic_slice") == "gather"
    assert classify_primitive("scatter") == "scatter"
    assert classify_primitive("dynamic_update_slice") == "scatter"
    assert classify_primitive("sort") == "sort"
    assert classify_primitive("dot_general") == "dot"
    assert classify_primitive("reduce_sum") == "reduce"
    assert classify_primitive("argmax") == "reduce"
    assert classify_primitive("while") == "control"
    assert classify_primitive("closed_call") == "control"
    assert classify_primitive("add") == "elementwise"
    assert classify_primitive("reshape") == "elementwise"


def test_per_action_attribution_follows_the_decomposition():
    """2pc's hand twin decomposes per action: the attribution carries
    one entry per action slot plus the trailing shared bucket, with
    non-negative costs; the slot-multiset paxos twin does NOT decompose
    (JX302) and honestly reports None."""
    m = TwoPhaseSys(3)
    twin = _twin(m)
    rep = wavefront_costs(twin, 1 << 12, 1 << 11, 64)
    acts = rep.actions
    assert acts is not None
    assert len(acts) == twin.max_actions + 1
    assert acts[-1]["action"] == "shared"
    assert all(a["flops"] >= 0 and a["bytes"] >= 0 for a in acts)
    assert any(a["bytes"] > 0 for a in acts[:-1])

    p = paxos_model(1)
    prep = wavefront_costs(_twin(p), 1 << 12, 1 << 11, 64)
    assert prep.actions is None


def test_mxu_candidates_rank_by_bytes_and_emit_jx4xx():
    """The ranking is byte-descending, every candidate is a
    gather/scatter/sort site, and the findings carry the JX400/JX401
    per-candidate rules plus the JX402 summary."""
    m = TwoPhaseSys(3)
    rep = wavefront_costs(_twin(m), 1 << 12, 1 << 11, 64)
    cands = rep.candidates
    assert cands, "2pc's insert pipeline must surface MXU candidates"
    byte_list = [c["bytes"] for c in cands]
    assert byte_list == sorted(byte_list, reverse=True)
    assert all(c["op_class"] in ("gather", "scatter", "sort")
               for c in cands)
    assert [c["rank"] for c in cands] == list(range(1, len(cands) + 1))
    rules = {f.rule_id for f in rep.findings}
    assert "JX400" in rules and "JX402" in rules
    # the dedup-insert membership gather is the known top hot spot
    assert cands[0]["stage"] == "dedup-insert"


def test_the_insert_is_charged_no_pass_over_the_table():
    """PR 38: nothing in the insert's step reads or writes O(capacity) -
    the membership gather fetches rows through the flat table's
    ``[cap / 128, 128]`` view, which the walk prices as the bitcast it is
    on the TPU (``costmodel._is_row_view``), and the scatters are charged
    their update windows.  So the stage's bytes do not depend on the table:
    1,379,346 B a step at 2^12 and at 2^20 slots alike (the parent, whose
    ``[nbuckets, 16]`` view was charged read and written, 2 x 8 B a slot:
    865,042 B at 2^12 and 17,576,722 B at 2^20)."""
    twin = _twin(TwoPhaseSys(3))
    charged = {
        cap: wavefront_costs(twin, cap, 1 << 11, 64, reconcile=False)
        .stages["dedup-insert"].bytes_total
        for cap in (1 << 12, 1 << 20)
    }
    assert charged[1 << 12] == charged[1 << 20] == 1_379_346


@pytest.mark.parametrize("dtype,shape,free", [
    ("uint32", (16, 128), True),    # the row view of a 32-bit plane
    ("uint64", (16, 128), True),    # ... of the table (two such planes)
    ("uint8", (16, 128), False),    # a packed dtype tiles otherwise
    ("bfloat16", (16, 128), False),
    ("uint32", (128, 16), False),   # the [nbuckets, SLOTS] lines it replaced
    ("uint32", (8, 256), False),
])
def test_only_the_row_view_of_whole_words_is_a_free_reshape(dtype, shape, free):
    """``costmodel._is_row_view``: 0 bytes for ``[n] <-> [n / ROW_LANES,
    ROW_LANES]`` of 4- or 8-byte elements, both directions; every other
    reshape is charged its operand read and written."""
    import jax.numpy as jnp

    flat = jax.ShapeDtypeStruct((2048,), jnp.dtype(dtype))
    for fn, aval in (
        (lambda x: x.reshape(shape), flat),
        (lambda x: x.reshape(-1), jax.ShapeDtypeStruct(shape, flat.dtype)),
    ):
        cost = walk_jaxpr(jax.make_jaxpr(fn)(aval))
        assert cost.bytes_total == (0 if free else 2 * 2048 * flat.dtype.itemsize)


# -- device spec + roofline classification ----------------------------------


def test_device_spec_env_override_and_cpu_degradation(monkeypatch, capsys):
    monkeypatch.delenv(ENV_DEVICE_SPEC, raising=False)
    spec = device_spec()
    if jax.devices()[0].platform == "cpu":
        assert spec is None  # arithmetic-intensity-only degradation
    monkeypatch.setenv(ENV_DEVICE_SPEC, "1.97e14:8.19e11:tpu-v5e")
    spec = device_spec()
    # the pre-split fields hold exactly (back-compat contract) ...
    assert {
        k: spec[k]
        for k in ("name", "peak_flops", "hbm_bytes_per_sec", "ridge",
                  "src")
    } == {
        "name": "tpu-v5e", "peak_flops": 1.97e14,
        "hbm_bytes_per_sec": 8.19e11,
        "ridge": 1.97e14 / 8.19e11, "src": "env",
    }
    # ... and the two-peak split rides along (MXU aliases the old pair;
    # VPU defaults to PEAK/64 for the 3-field form — docs/roofline.md)
    assert spec["mxu_peak"] == spec["peak_flops"]
    assert spec["mxu_ridge"] == spec["ridge"]
    assert spec["vpu_peak"] == 1.97e14 / 64.0
    assert spec["vpu_ridge"] == spec["vpu_peak"] / 8.19e11
    monkeypatch.setenv(ENV_DEVICE_SPEC, "garbage")
    assert device_spec() is None or device_spec()["src"] != "env"
    assert "malformed" in capsys.readouterr().err


def test_device_spec_matches_the_real_v5e_kind_and_never_guesses(
    monkeypatch, capsys
):
    """A v5e reports ``device_kind == "TPU v5 lite"`` (chip_smoke.py, PR
    22) and gets the v5e row; an unlisted TPU kind gets NO spec — loudly
    — never a neighbouring generation's peaks."""
    from types import SimpleNamespace

    monkeypatch.delenv(ENV_DEVICE_SPEC, raising=False)
    v5e = device_spec(SimpleNamespace(platform="tpu",
                                      device_kind="TPU v5 lite"))
    assert v5e["name"] == "tpu-v5e" and v5e["src"] == "device"
    assert v5e["hbm_bytes_per_sec"] == 819e9
    assert capsys.readouterr().err == ""
    for kind in ("TPU v5", "TPU v7x"):
        assert device_spec(
            SimpleNamespace(platform="tpu", device_kind=kind)
        ) is None
        assert "no peak table entry" in capsys.readouterr().err


def test_classify_stages_verdicts():
    static = {"stages": {
        "a": {"intensity": 0.05},
        "b": {"intensity": 500.0},
        "c": {},
    }}
    spec = {"peak_flops": 1e14, "hbm_bytes_per_sec": 1e12, "ridge": 100.0}
    v = classify_stages(static, spec)
    assert v["a"]["verdict"] == "memory-bound"
    assert v["b"]["verdict"] == "compute-bound"
    assert v["c"]["verdict"] == "unknown"
    # no spec: every verdict degrades to unknown, intensities survive
    v = classify_stages(static, None)
    assert {e["verdict"] for e in v.values()} == {"unknown"}
    assert v["a"]["intensity"] == 0.05


def test_achieved_block_math():
    static = {"totals": {"bytes": 1000, "flops": 100}, "batch": 10}
    spec = {"peak_flops": 1e6, "hbm_bytes_per_sec": 1e6, "ridge": 1.0}
    ach = achieved_block(
        static, spec, {"device_secs": 2.0}, unique=25, batch=10,
    )
    assert ach["est_device_steps"] == 3  # ceil(25 / 10)
    assert ach["bytes_per_sec"] == 1500.0
    assert ach["frac_of_hbm_ceiling"] == pytest.approx(0.0015)
    # a mesh run's step is the same program: its batch rows are spread
    # over the devices, the step estimate does not change with the mesh
    ach = achieved_block(
        static, spec, {"device_secs": 2.0}, unique=100, batch=10,
    )
    assert ach["est_device_steps"] == 10
    assert ach["bytes_per_sec"] == 5000.0
    # no attribution yet / no bytes: no achieved block, never a crash
    assert achieved_block(static, spec, None, 25, 10) is None
    assert achieved_block({"totals": {}}, spec,
                          {"device_secs": 2.0}, 25, 10) is None


def test_fold_into_report_merges_jx4xx_and_metrics():
    """The for-callers AuditReport hook (the independence.fold_into_report
    pattern): findings land deduped in the report, the metrics block
    carries the ledger summary."""
    from stateright_tpu.analysis import AuditReport
    from stateright_tpu.analysis.costmodel import fold_into_report

    m = TwoPhaseSys(3)
    rep = wavefront_costs(_twin(m), 1 << 12, 1 << 11, 64)
    report = AuditReport()
    fold_into_report(rep, report)
    rules = {f.rule_id for f in report.findings}
    assert "JX400" in rules and "JX402" in rules
    mc = report.metrics["costmodel"]
    assert mc["reconciled"] is True
    assert mc["flops"] == rep.total_flops
    assert mc["mxu_candidates"] == len(rep.candidates)


# -- checker surfaces --------------------------------------------------------


def _spawn(roofline=True, **kw):
    b = TwoPhaseSys(3).checker()
    b = b.telemetry(cartography=True, memory=True, roofline=roofline) \
        if roofline else b.telemetry()
    kw = {**_KW, **kw}
    return b.spawn_tpu(sync=True, **kw)


def test_roofline_accessor_off_and_on():
    assert _spawn(roofline=False).roofline() is None
    c = _spawn()
    live = c.roofline()
    assert live["v"] == COSTMODEL_V
    assert set(live["stages"]) == set(_STAGES)
    assert live["reconciliation"]["ok"]
    assert "verdicts" in live
    # achieved exists once stage attribution does (sync run is done)
    assert live.get("achieved") is None or (
        live["achieved"]["est_device_steps"] >= 1
    )


def test_report_roofline_block_is_deterministic_and_static_only(tmp_path):
    """The run report's roofline block is byte-stable across runs and
    carries NO XLA / device-spec / wall-clock fields (those live in the
    markdown rendering only)."""
    from stateright_tpu.telemetry.report import build_report

    bodies = []
    for i in range(2):
        c = (
            TwoPhaseSys(3).checker()
            .telemetry(roofline=True)
            .report(str(tmp_path / f"r{i}.json"))
            .spawn_tpu(sync=True, **_KW)
        )
        c.join()
        bodies.append(build_report(c)["roofline"])
    assert json.dumps(bodies[0], sort_keys=True) == json.dumps(
        bodies[1], sort_keys=True
    )
    blk = bodies[0]
    assert blk["v"] == COSTMODEL_V
    for forbidden in ("reconciliation", "device_spec", "verdicts",
                      "achieved"):
        assert forbidden not in blk, forbidden
    # totals reconcile against the per-stage sums (the regress gate)
    assert blk["totals"]["flops"] == sum(
        s["flops"] for s in blk["stages"].values()
    )
    assert blk["totals"]["bytes"] == sum(
        s["bytes_read"] + s["bytes_written"]
        for s in blk["stages"].values()
    )
    md = (tmp_path / "r1.md").read_text()
    assert "## Roofline (static cost model)" in md


def test_roofline_ring_record_and_metrics_block():
    c = _spawn()
    # the ledger's findings accessor mirrors CostReport's (JX4xx)
    assert {f.rule_id for f in c._roofline_ledger.findings()} >= {
        "JX400", "JX402",
    }
    recs = c.flight_recorder.records("roofline")
    assert len(recs) == 1 and recs[0]["at"] == "init"
    assert recs[0]["v"] == ROOFLINE_V
    assert recs[0]["reconciled"] is True
    assert "roofline" in c.flight_recorder.summary()
    from stateright_tpu.explorer import _metrics_view

    view = _metrics_view(c)
    assert view["roofline"]["totals"]["bytes"] > 0


def test_costmodel_verb_fleet_entry(capsys):
    """The per-example verb runs end-to-end and exits clean on 2pc."""
    from stateright_tpu.models import two_phase_commit

    two_phase_commit.main(["costmodel"])
    out = capsys.readouterr().out
    assert "XLA reconciliation: ok" in out
    assert "JX402" in out

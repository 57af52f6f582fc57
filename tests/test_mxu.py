"""MXU recast round (ops/mxu.py; docs/roofline.md "Executing the
hot-spot list"): expand-scatter coalescing and the BLEST one-hot
membership probe.  (The queue append in ``batch``-sized chunks is no
knob: it is the engine's one append, and tests/test_append_novel.py holds
it to the window it replaced.)

The contracts pinned here, in the family's strongest form:

 - every knob OFF leaves the step jaxpr bit-identical to a pre-MXU
   engine and the engine cache unkeyed (both engines);
 - every knob ON keeps counts, the visited table, and discovery traces
   bit-identical (2pc-3 strongest form; compositions with symmetry /
   POR / prededup / spill / kill+resume in the tiered crawls);
 - the coalesced step kernels compute bit-identical successors over the
   WHOLE per-channel paxos-1 space (and the hand twin's paxos-1 space);
 - the flagged cost ledger proves the bytes actually dropped: paxos-3's
   expand charged bytes fall >=30% and dedup-insert carries a
   genuine dot-class op with raised arithmetic intensity;
 - the roofline device table judges dot-dominated stages against the
   MXU ridge and everything else against the VPU ridge;
 - JX400 findings name the landed ``--mxu`` escape hatch pre-flag and
   go silent post-flag (the JX305 pattern);
 - ``regress.py --mxu`` validates present legs and never trips on
   absent/stale ones (injectable artifacts).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.mxu import MxuConfig, coalesced_step_fn, resolve_mxu

TPC3_UNIQUE = 288


def _spawn(m, mxu=None, **kw):
    b = m.checker()
    if mxu is not None:
        b = b.mxu(**mxu) if isinstance(mxu, dict) else b.mxu(mxu)
    kw.setdefault("sync", True)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return b.spawn_tpu(**kw)


def _counts(c):
    return (c.state_count(), c.unique_state_count(),
            sorted(c.discoveries()))


# -- config resolution --------------------------------------------------------


def test_resolve_mxu_builder_and_env(monkeypatch):
    monkeypatch.delenv("STATERIGHT_TPU_MXU", raising=False)
    assert resolve_mxu(None) is None
    monkeypatch.setenv("STATERIGHT_TPU_MXU", "1")
    assert resolve_mxu(None) == MxuConfig(True, True)
    # explicit builder off beats the env knob (resolve_flag's rule)
    assert resolve_mxu({"coalesce": False, "probe": False}) is None
    # component subset survives resolution
    cfg = resolve_mxu({"coalesce": False, "probe": True})
    assert cfg == MxuConfig(False, True)
    assert cfg.key() == ("mxu", False, True)
    # the chunked append is no knob: no builder keyword names it
    with pytest.raises(TypeError):
        TwoPhaseSys(3).checker().mxu(slim_queue=True)


def test_builder_mxu_off_overrides_env(monkeypatch):
    monkeypatch.setenv("STATERIGHT_TPU_MXU", "1")
    b = TwoPhaseSys(3).checker().mxu(False)
    assert resolve_mxu(b.mxu_opts) is None


# -- jaxpr + engine-cache-key pins (wavefront) --------------------------------


def test_mxu_off_leaves_run_jaxpr_bit_identical():
    """The prededup contract: OFF must be the pre-flag engine program;
    each component ON must actually change it, and the probe must put a
    real dot_general in the step."""

    def run_jaxpr(opts):
        m = TwoPhaseSys(3)
        b = m.checker()
        if opts is not None:
            b = b.mxu(**opts) if opts else b.mxu(False)
        c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    baseline = run_jaxpr(None)
    assert baseline == run_jaxpr({})  # .mxu(False): explicit off
    assert "dot_general" not in baseline
    probe = run_jaxpr({"coalesce": False, "probe": True})
    assert probe != baseline and "dot_general" in probe
    coalesce = run_jaxpr({"coalesce": True, "probe": False})
    assert coalesce != baseline and "dot_general" not in coalesce
    # the append is the same loop flag or no flag: the step's
    # body holds the insert's two ``while`` and the append's one, with
    # ``qchunk`` = batch = 64 lanes a gather, in all three programs
    for text in (baseline, probe, coalesce):
        assert text.count("dynamic_update_slice") == baseline.count(
            "dynamic_update_slice")


def test_mxu_engine_cache_key_pin():
    """OFF leaves the cache key exactly the pre-MXU tuple (unkeyed by
    the feature's absence); ON appends the EFFECTIVE component tuple —
    a component that falls back to an identical program (a twin without
    a coalesced kernel) is keyed off, so equivalent configs share one
    engine compile."""
    off = _spawn(TwoPhaseSys(3))
    on = _spawn(TwoPhaseSys(3), mxu=True)
    k_off = off._engine_key(off._cap, off._qcap, off._batch, off._cand)
    k_on = on._engine_key(on._cap, on._qcap, on._batch, on._cand)
    assert not any(
        isinstance(e, tuple) and e and e[0] == "mxu" for e in k_off
    )
    assert k_on[:-1] == k_off
    # the 2pc hand twin gained a real coalesced kernel (the FieldWriter
    # round): the component keys ON and the config is its own entry
    assert k_on[-1] == ("mxu", True, True)
    no_co = _spawn(TwoPhaseSys(3), mxu={"coalesce": False})
    k_no_co = no_co._engine_key(
        no_co._cap, no_co._qcap, no_co._batch, no_co._cand
    )
    assert k_no_co[-1] == ("mxu", False, True)
    assert k_on != k_no_co
    # effective_mxu still downgrades for twins WITHOUT a coalesced
    # kernel (ops/mxu.py fallback pin lives in
    # test_coalesced_step_fn_fallback_without_method)
    pax = paxos_model(1, 3).checker().mxu().spawn_tpu(
        sync=True, capacity=1 << 15, batch=256
    )
    k_pax = pax._engine_key(pax._cap, pax._qcap, pax._batch, pax._cand)
    assert k_pax[-1] == ("mxu", True, True)


# -- bit-identical engine runs (strongest form) -------------------------------


def test_mxu_is_bit_identical_on_2pc3():
    """With capacities pre-sized (no growth), the visited TABLE itself —
    every slot's fingerprint and parent payload — must be bit-identical
    with the flag on and off, along with every count and discovery."""
    a = _spawn(TwoPhaseSys(3))
    b = _spawn(TwoPhaseSys(3), mxu=True)
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count()
    assert a.max_depth() == b.max_depth()
    ta, tb = a._table_np(), b._table_np()
    assert np.array_equal(ta[0], tb[0])
    assert np.array_equal(ta[1], tb[1])
    da, db = a.discoveries(), b.discoveries()
    assert sorted(da) == sorted(db)
    for name in da:
        assert [str(s) for s in da[name].states()] == [
            str(s) for s in db[name].states()
        ]


def test_mxu_parity_per_channel_paxos1_with_por_and_prededup():
    """The composition the round exists for: per-channel paxos-1 under
    --mxu must reproduce the pinned full space AND the pinned reduced
    space under por(), with prededup stacked on top."""
    def pc():
        m = paxos_model(1, 3)
        m.per_channel_()
        return m

    full = _counts(_spawn(pc(), capacity=1 << 15, batch=256))
    full_m = _counts(_spawn(pc(), mxu=True, capacity=1 << 15, batch=256))
    assert full == full_m
    assert (full[0], full[1]) == (482, 265)
    por = _counts(
        pc().checker().por().mxu().prededup().spawn_tpu(
            sync=True, capacity=1 << 15, batch=256
        )
    )
    assert (por[0], por[1]) == (437, 250)
    assert por[2] == full[2]


def test_fieldwriter_get_after_or_matches_eager():
    """get() after or_field must see the pending OR in BOTH modes (the
    eager mode reads the running block; the coalesced mode must not
    return the stale base) — and the assembled blocks stay equal."""
    from stateright_tpu.parallel.tensor_model import FieldWriter

    t = paxos_model(1, 3).tensor_model()
    pk = t.pk
    name = next(n for n, (_w, _o, bits) in pk.layout.items() if bits == 1)
    base = jnp.zeros((2, 1, pk.width), jnp.uint64)
    flag = jnp.asarray([[True], [False]])
    eager = FieldWriter(pk, base, coalesce=False).or_field(name, flag)
    co = FieldWriter(pk, base, coalesce=True).or_field(name, flag)
    assert np.array_equal(np.asarray(eager.get(name)),
                          np.asarray(co.get(name)))
    assert np.array_equal(np.asarray(eager.done()), np.asarray(co.done()))
    # a later set SUPERSEDES the OR (done applies ops in call order;
    # get must agree in both modes) — and an OR after a set stacks
    for ops in (("or", "set"), ("set", "or"), ("or", "set", "or")):
        fe = FieldWriter(pk, base, coalesce=False)
        fc = FieldWriter(pk, base, coalesce=True)
        for op in ops:
            for fw in (fe, fc):
                if op == "or":
                    fw.or_field(name, flag)
                else:
                    fw.set(name, jnp.zeros((2, 1), jnp.uint64))
        assert np.array_equal(np.asarray(fe.get(name)),
                              np.asarray(fc.get(name))), ops
        assert np.array_equal(np.asarray(fe.done()),
                              np.asarray(fc.done())), ops


def test_coalesce_fallback_keeps_expand_findings():
    """A twin that advertises no coalesced kernel keeps the plain step
    under the flag - its expand-scatter JX400 findings must then keep
    firing (a fallen-back recast never silences its advice, the
    effective_mxu discipline).  The queue's advice is no flag's to
    silence and no cand budget's to change: one append for every engine,
    the walk charging its loop's body - one chunk - once."""
    from stateright_tpu.analysis.costmodel import wavefront_costs

    def expand_advice(rep):
        return [f for f in rep.findings
                if f.rule_id == "JX400" and "stage:expand" in f.location]

    real = TwoPhaseSys(5).tensor_model()
    bare = TwoPhaseSys(5).tensor_model()
    bare.has_coalesced_step = False
    assert coalesced_step_fn(bare, MxuConfig()) == bare.step_rows
    on_real = wavefront_costs(
        real, 1 << 16, 1 << 15, 512, reconcile=False, mxu=MxuConfig())
    on_bare = wavefront_costs(
        bare, 1 << 16, 1 << 15, 512, reconcile=False, mxu=MxuConfig())
    assert not expand_advice(on_real)
    assert expand_advice(on_bare), "fallen-back coalesce must keep its advice"
    assert not any(c.get("recast_landed") for c in on_bare.candidates
                   if c["stage"] == "expand")
    t3 = TwoPhaseSys(3).tensor_model()

    def queue_advice(cand, mxu):
        rep = wavefront_costs(
            t3, 1 << 12, 1 << 11, 64, cand, reconcile=False, mxu=mxu)
        queue = [c for c in rep.candidates if c["stage"] == "queue"]
        assert not any("recast_landed" in c or "escape_hatch" in c
                       for c in queue)
        # no site wider than a chunk (64 lanes): at 2pc-3's one-word rows
        # that is under the ledger's candidate threshold, whatever the cand
        assert all(c["shape"][0] == 64 for c in queue)
        stage = rep.stages["queue"]
        return ([(c["op"], tuple(c["shape"]), c["count"], c["bytes"])
                 for c in queue], stage.bytes_total, stage.flops)

    advice = {(cand, mxu is not None): queue_advice(cand, mxu)
              for cand in (100, 128) for mxu in (None, MxuConfig())}
    assert len({(tuple(sites), b, f) for sites, b, f in advice.values()}) == 1
    assert all(b > 0 for _, b, _ in advice.values())


# -- coalesced-step whole-space successor parity ------------------------------


def _crawl_step_parity(tensor, batch=64, max_unique=4000):
    """Drive the whole reachable space with the PLAIN kernel as oracle,
    asserting per batch that the coalesced kernel produces bit-identical
    (valid, successor) pairs.  Returns the unique-row count."""
    step_a = jax.jit(tensor.step_rows)
    step_b = jax.jit(tensor.step_rows_coalesced)
    init = np.asarray(tensor.init_rows(), np.uint64)
    seen = {tuple(int(w) for w in r) for r in init}
    frontier = list(init)
    while frontier:
        chunk, frontier = frontier[:batch], frontier[batch:]
        pad = batch - len(chunk)
        rows = np.stack(chunk + [chunk[0]] * pad).astype(np.uint64)
        s_a, v_a = step_a(jnp.asarray(rows))
        s_b, v_b = step_b(jnp.asarray(rows))
        s_a, v_a = np.asarray(s_a), np.asarray(v_a)
        s_b, v_b = np.asarray(s_b), np.asarray(v_b)
        assert np.array_equal(v_a, v_b)
        # invalid lanes may hold garbage in BOTH kernels; compare masked
        assert np.array_equal(
            np.where(v_a[..., None], s_a, 0),
            np.where(v_b[..., None], s_b, 0),
        )
        n_real = batch - pad
        for b_i in range(n_real):
            for a_i in range(v_a.shape[1]):
                if not v_a[b_i, a_i]:
                    continue
                key = tuple(int(w) for w in s_a[b_i, a_i])
                if key not in seen:
                    seen.add(key)
                    frontier.append(s_a[b_i, a_i])
        assert len(seen) <= max_unique, "space exceeded the test bound"
    return len(seen)


def test_coalesced_whole_space_parity_per_channel_paxos1():
    m = paxos_model(1, 3)
    m.per_channel_()
    t = m._tensor_cached()
    assert _crawl_step_parity(t) == 265


def test_coalesced_whole_space_parity_hand_twin_paxos1():
    t = paxos_model(1, 3).tensor_model()
    assert _crawl_step_parity(t) == 265


def test_coalesced_step_fn_fallback_without_method():
    """Twins without a coalesced kernel silently keep the plain step —
    the flag then still buys the queue/probe recasts."""
    class Bare:
        def step_rows(self, rows):
            return rows

    t = Bare()
    assert coalesced_step_fn(t, MxuConfig()) == t.step_rows
    assert coalesced_step_fn(t, None) == t.step_rows
    t2 = paxos_model(1, 3).tensor_model()
    assert coalesced_step_fn(t2, MxuConfig()) == t2.step_rows_coalesced
    assert coalesced_step_fn(
        t2, MxuConfig(coalesce=False)
    ) == t2.step_rows


def test_multiset_compiled_twin_coalesce_is_real():
    """The slot-multiset compiled twin's coalesce is REAL since its
    history/timer/poison write-backs were threaded through the
    FieldWriter seam — has_coalesced_step advertises it, the engines
    trace the coalesced kernel, and its successors stay bit-identical
    over the whole actor-2pc space."""
    from fixtures_actor import actor_2pc_model

    from stateright_tpu.ops.mxu import has_coalesced_step

    ms = actor_2pc_model(2)._tensor_cached()
    assert has_coalesced_step(ms)
    assert coalesced_step_fn(ms, MxuConfig()) == ms.step_rows_coalesced
    pc = actor_2pc_model(2)
    pc.per_channel_()
    tpc = pc._tensor_cached()
    assert has_coalesced_step(tpc)
    assert coalesced_step_fn(tpc, MxuConfig()) == tpc.step_rows_coalesced
    assert _crawl_step_parity(ms, max_unique=6000) == _crawl_step_parity(
        tpc, max_unique=6000
    )


def test_coalesced_whole_space_parity_hand_twin_2pc3():
    """The 2pc hand twin's new coalesced kernel: bit-identical
    successors over the whole 2pc-3 space (the per-action FieldWriter
    assembly must preserve every mask and write)."""
    t = TwoPhaseSys(3).tensor_model()
    assert _crawl_step_parity(t) == TPC3_UNIQUE


# -- cost-model payoff (the regress --mxu bars, statically) -------------------


def test_costmodel_mxu_reduction_and_dot_class():
    """The flagged ledger must prove the bytes dropped: paxos-3's (hand
    twin, the bench's own) expand charged bytes fall by ``regress.py
    --mxu``'s bar (33.2% against 30%) - the expand stage alone, which is
    what coalescing changes: the queue stage is one program with the flag
    and without it - and dedup-insert carries a dot-class op with raised
    arithmetic intensity.  Also pins that the twin-level cost cache keys
    flagged and unflagged ledgers separately."""
    from regress import MXU_EXPAND_DROP
    from stateright_tpu.analysis.costmodel import wavefront_costs

    t = paxos_model(3).tensor_model()
    off = wavefront_costs(t, 1 << 16, 1 << 15, 512, reconcile=False)
    on = wavefront_costs(
        t, 1 << 16, 1 << 15, 512, reconcile=False, mxu=MxuConfig()
    )
    assert off is not None and on is not None and off is not on
    e_off = off.stages["expand"].bytes_total
    e_on = on.stages["expand"].bytes_total
    assert (e_off, e_on) == (547_875_208, 365_791_336)
    assert 1 - e_on / e_off >= MXU_EXPAND_DROP == 0.30
    assert on.stages["queue"].bytes_total == off.stages["queue"].bytes_total
    # the probe landed a genuine dot op on the insert stage
    assert "dot" not in off.stages["dedup-insert"].classes
    dot = on.stages["dedup-insert"].classes.get("dot")
    assert dot and dot["flops"] > 0
    assert (on.stages["dedup-insert"].intensity
            > off.stages["dedup-insert"].intensity)
    # expand scatters collapse under coalescing
    assert "scatter" in off.stages["expand"].classes
    assert "scatter" not in on.stages["expand"].classes


def test_jx400_escape_hatch_pre_flag_and_silent_post():
    """The JX305 pattern: pre-flag, the dedup-gather JX400 finding
    names the --mxu hatch; with the probe armed, the finding goes
    silent (the recast is live)."""
    from stateright_tpu.analysis.costmodel import wavefront_costs

    t = TwoPhaseSys(5).tensor_model()
    off = wavefront_costs(t, 1 << 16, 1 << 15, 512, reconcile=False)
    dedup_off = [
        f for f in off.findings
        if f.rule_id == "JX400" and "dedup-insert" in f.location
    ]
    assert dedup_off, "pre-flag JX400 dedup finding must fire"
    assert any("--mxu" in f.message for f in dedup_off)
    on = wavefront_costs(
        t, 1 << 16, 1 << 15, 512, reconcile=False, mxu=MxuConfig()
    )
    assert not [
        f for f in on.findings
        if f.rule_id == "JX400" and "dedup-insert" in f.location
        and "gather" in f.message
    ], "post-flag the dedup gather JX400 finding must go silent"
    # the insert-stage SCATTER (the table write-back) is NOT retired by
    # the probe — its finding must keep firing (honest ranking)
    assert [
        f for f in on.findings
        if f.rule_id == "JX400" and "dedup-insert" in f.location
        and "scatter" in f.message
    ], "the un-recast dedup scatter finding must stay live"
    # the candidate row itself survives, marked landed (the ranking is
    # still the hot-spot table; only the advice retires)
    assert any(
        c.get("recast_landed")
        for c in on.candidates
        if c["stage"] == "dedup-insert" and c["op_class"] == "gather"
    )
    # 2pc's hand twin gained a real coalesced kernel (the FieldWriter
    # round): its expand scatters vanish from the flagged trace, exactly
    # like the paxos hand twin's
    assert "scatter" in off.stages["expand"].classes
    assert "scatter" not in on.stages["expand"].classes


# -- roofline two-peak verdicts -----------------------------------------------


def test_roofline_judges_dot_stages_against_mxu_ridge(monkeypatch):
    """The satellite pin: one shared peak hands a recast stage the
    wrong verdict.  A synthetic dot-heavy stage whose intensity sits
    between the VPU and MXU ridges must judge memory-bound (MXU ridge),
    while an elementwise stage at the same intensity judges
    compute-bound (VPU ridge)."""
    from stateright_tpu.telemetry.roofline import (
        classify_stages,
        device_spec,
    )

    # peak 1e14 MXU, 1e12 VPU, 1e11 B/s: mxu ridge 1000, vpu ridge 10
    monkeypatch.setenv(
        "STATERIGHT_TPU_DEVICE_SPEC", "1e14:1e11:synth:1e12"
    )
    spec = device_spec()
    assert spec["mxu_peak"] == 1e14 and spec["vpu_peak"] == 1e12
    assert spec["mxu_ridge"] == 1000.0 and spec["vpu_ridge"] == 10.0
    static = {"stages": {
        "recast": {
            "flops": 100_000, "bytes_read": 500, "bytes_written": 500,
            "intensity": 100.0,
            "classes": {"dot": {"flops": 90_000, "bytes": 600,
                                "count": 1}},
        },
        "plain": {
            "flops": 100_000, "bytes_read": 500, "bytes_written": 500,
            "intensity": 100.0,
            "classes": {"elementwise": {"flops": 100_000, "bytes": 1000,
                                        "count": 4}},
        },
    }}
    v = classify_stages(static, spec)
    assert v["recast"]["ridge_kind"] == "mxu"
    assert v["recast"]["verdict"] == "memory-bound"
    assert v["plain"]["ridge_kind"] == "vpu"
    assert v["plain"]["verdict"] == "compute-bound"


def test_roofline_env_spec_back_compat(monkeypatch):
    """The pre-split 3-field env format still parses; VPU defaults to
    PEAK/64 and the pre-split ``peak_flops``/``ridge`` aliases hold."""
    from stateright_tpu.telemetry.roofline import device_spec

    monkeypatch.setenv("STATERIGHT_TPU_DEVICE_SPEC", "6.4e13:1e11:old")
    spec = device_spec()
    assert spec["peak_flops"] == spec["mxu_peak"] == 6.4e13
    assert spec["vpu_peak"] == 1e12
    assert spec["ridge"] == spec["mxu_ridge"]


def test_roofline_device_table_carries_both_peaks():
    from stateright_tpu.telemetry.roofline import DEVICE_SPECS

    for _needle, _name, mxu_peak, vpu_peak, bw in DEVICE_SPECS:
        assert mxu_peak > vpu_peak > 0 and bw > 0


# -- regress --mxu gate (injectable artifacts) --------------------------------


def _roof(expand_b, queue_b, dedup=None):
    stages = {
        "expand": {"flops": 1, "bytes_read": expand_b, "bytes_written": 0},
        "queue": {"flops": 1, "bytes_read": queue_b, "bytes_written": 0},
    }
    if dedup is not None:
        stages["dedup-insert"] = dedup
    return {"v": 1, "stages": stages}


def _good_mxu_run():
    return {
        "tpu_paxos3_unique": 100, "tpu_paxos3_mxu_unique": 100,
        "tpu_2pc7_unique": 50, "tpu_2pc7_mxu_unique": 50,
        "tpu_paxos3_roofline": _roof(1000, 200),
        "tpu_paxos3_mxu_roofline": _roof(600, 20),
        "tpu_2pc7_roofline": _roof(10, 10, {
            "flops": 10, "bytes_read": 100, "bytes_written": 0,
            "intensity": 0.1, "classes": {},
        }),
        "tpu_2pc7_mxu_roofline": _roof(10, 10, {
            "flops": 50, "bytes_read": 100, "bytes_written": 0,
            "intensity": 0.5,
            "classes": {"dot": {"flops": 40, "bytes": 10, "count": 1}},
        }),
    }


def test_regress_mxu_gate_absence_never_trips():
    import regress

    v = regress.mxu_verdict({}, {})
    assert v["ok"] and not v["present"]
    # a stale/pre-mxu BASELINE never trips a run either way
    v = regress.mxu_verdict(_good_mxu_run(), {})
    assert v["ok"] and v["present"] and not v["baseline_present"]


def test_regress_mxu_gate_validates_present_legs():
    import regress

    good = _good_mxu_run()
    v = regress.mxu_verdict(good, {})
    assert v["ok"], v
    assert v["paxos3_expand_bytes"] == {
        "unflagged": 1000, "mxu": 600, "drop": 0.4}

    crashed = dict(good, tpu_paxos3_mxu_error="RuntimeError: boom")
    assert not regress.mxu_verdict(crashed, {})["ok"]

    drifted = dict(good, tpu_paxos3_mxu_unique=99)
    v = regress.mxu_verdict(drifted, {})
    assert not v["ok"] and any(
        "must not change counts" in p for p in v["problems"]
    )

    # a queue stage that shrinks carries no expand stage over the bar
    shallow = dict(good, tpu_paxos3_mxu_roofline=_roof(800, 20))
    v = regress.mxu_verdict(shallow, {})
    assert not v["ok"] and any("30%" in p for p in v["problems"])

    no_dot = dict(good)
    no_dot["tpu_2pc7_mxu_roofline"] = good["tpu_2pc7_roofline"]
    v = regress.mxu_verdict(no_dot, {})
    assert not v["ok"] and any("dot-class" in p for p in v["problems"])

    no_base = dict(good)
    del no_base["tpu_paxos3_roofline"]
    v = regress.mxu_verdict(no_base, {})
    assert not v["ok"] and any("unflagged" in p for p in v["problems"])

    # injected artifacts are arbitrary JSON: a non-dict roofline block
    # (e.g. a stringified crash) must produce a verdict, not a traceback
    for key in ("tpu_2pc7_mxu_roofline", "tpu_paxos3_mxu_roofline"):
        trash = dict(good, **{key: "XlaRuntimeError: boom"})
        v = regress.mxu_verdict(trash, {})
        assert not v["ok"], key
    nested = dict(good)
    nested["tpu_2pc7_mxu_roofline"] = {"v": 1, "stages": "corrupt"}
    assert not regress.mxu_verdict(nested, {})["ok"]


def test_regress_main_mxu_flag(tmp_path, capsys):
    """End-to-end through regress.main: a fresh run with good legs
    passes; one with a crashed leg exits 1; a run WITHOUT legs passes
    (flag-gated)."""
    import json

    import regress

    base = {}

    def run_file(extra):
        doc = {"fresh": True, **extra}
        p = tmp_path / f"run{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(doc))
        return str(p)

    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))
    args = ["--baseline=" + str(bp), "--mxu"]
    assert regress.main([run_file(_good_mxu_run())] + args) == 0
    assert regress.main([run_file({})] + args) == 0
    rc = regress.main(
        [run_file({"tpu_2pc7_mxu_error": "boom"})] + args
    )
    assert rc == 1
    capsys.readouterr()


# -- heavier compositions (tiered) --------------------------------------------


@pytest.mark.medium
def test_mxu_parity_under_growth_symmetry_and_spill(monkeypatch):
    """Counts/discoveries identical when growth interleaves, under
    symmetry's generation-order compaction, and with the spill tier
    evicting under a simulated budget."""
    a = TwoPhaseSys(4).checker().spawn_tpu(
        sync=True, capacity=1 << 8, batch=32, cand=128,
        queue_capacity=1 << 12,
    )
    b = TwoPhaseSys(4).checker().mxu().spawn_tpu(
        sync=True, capacity=1 << 8, batch=32, cand=128,
        queue_capacity=1 << 12,
    )
    assert _counts(a) == _counts(b)
    sa = TwoPhaseSys(3).checker().symmetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    sb = TwoPhaseSys(3).checker().symmetry().mxu().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert _counts(sa) == _counts(sb)
    ta, tb = sa._table_np(), sb._table_np()
    assert np.array_equal(ta[0], tb[0])  # no growth: bit-identical
    assert np.array_equal(ta[1], tb[1])
    # spill composition: a budget that forces eviction, counts pinned
    from stateright_tpu.parallel.tensor_model import twin_or_none
    from stateright_tpu.telemetry.memory import (
        ENV_DEVICE_BYTES,
        total_bytes,
        wavefront_specs,
    )

    m5 = TwoPhaseSys(5)
    twin = twin_or_none(m5)
    n_props = len(list(m5.properties()))
    sp = (1 << 14, 128 * twin.max_actions)

    def tot(cap):
        return total_bytes(
            wavefront_specs(twin, n_props, cap, 4096, 128, spill=sp)
        )

    monkeypatch.setenv(ENV_DEVICE_BYTES, str(tot(1 << 12) + tot(1 << 13) - 1))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    c = TwoPhaseSys(5).checker().spill().mxu().spawn_tpu(
        sync=True, capacity=1 << 12, batch=128, queue_capacity=4096,
        spill_bloom_bits=1 << 14, steps_per_call=8,
    )
    assert c.unique_state_count() == 8832
    assert c.spill_status()["evictions"] >= 1


@pytest.mark.medium
def test_mxu_kill_and_resume_parity():
    """Checkpoint an mxu run mid-flight and resume it (still flagged):
    totals must equal the uninterrupted flagged run's."""
    m = TwoPhaseSys(5)
    ref = m.checker().mxu().spawn_tpu(
        sync=True, capacity=1 << 14, batch=128
    )
    c = TwoPhaseSys(5).checker().mxu().spawn_tpu(
        sync=False, capacity=1 << 14, batch=128, steps_per_call=2
    )
    snap = c.checkpoint()
    c.stop()
    c.join()
    r = TwoPhaseSys(5).checker().mxu().spawn_tpu(
        sync=True, capacity=1 << 14, batch=128, resume=snap
    )
    assert r.unique_state_count() == ref.unique_state_count()
    assert sorted(r.discoveries()) == sorted(ref.discoveries())


@pytest.mark.medium
def test_mxu_parity_on_mesh_engine():
    a = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    b = TwoPhaseSys(3).checker().mxu().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())
    # cache-key pin: the unflagged key carries no mxu element (MXU off
    # leaves the cache unkeyed), the flagged one carries the effective
    # config, each before the mesh engine's own device tail
    assert a._last_engine_key[-1] == ("mesh", 0, 1)
    assert not any(
        isinstance(e, tuple) and e and e[0] == "mxu"
        for e in a._last_engine_key
    )
    assert b._last_engine_key[-1] == ("mesh", 0, 1)
    assert b._last_engine_key[-2][0] == "mxu"


@pytest.mark.slow
def test_mxu_fleet_parity_across_semantics():
    """The fleet crawl: every network semantics (unordered
    non-duplicating, ordered, duplicating actor-2pc, lossy ordered) on
    the per-channel compiled twins, mxu-on vs mxu-off, counts and
    discoveries identical."""
    from fixtures_actor import actor_2pc_model
    from stateright_tpu.actor import Network

    def pc(m):
        m.per_channel_()
        return m

    builds = [
        lambda: pc(paxos_model(1, 3)),
        lambda: pc(paxos_model(1, 3, Network.new_ordered())),
        lambda: pc(actor_2pc_model(2)),
        lambda: pc(actor_2pc_model(2, lossy=True)),
    ]
    ml = paxos_model(1, 3, Network.new_ordered())
    ml.lossy_network(True)
    ml.per_channel_()

    def lossy_ordered():
        m = paxos_model(1, 3, Network.new_ordered())
        m.lossy_network(True)
        m.per_channel_()
        return m

    builds.append(lossy_ordered)
    for build in builds:
        a = _counts(_spawn(build(), capacity=1 << 14, batch=128))
        b = _counts(_spawn(build(), mxu=True, capacity=1 << 14, batch=128))
        assert a == b, build

"""Static independence analysis → partial-order reduction
(``analysis/footprint.py``, ``analysis/independence.py``, ``ops/por.py``,
and both device engines' ample-set successor generation).

The load-bearing contracts pinned here:

 - footprints are BIT-exact on the flagship hand-written twin (2pc): the
   per-action write/guard masks equal the hand-derived BitPacker fields;
 - the conflict matrix is symmetric, dependent on the diagonal, and every
   UNDECIDABLE site defaults to dependent (paxos/dining: the
   slot-multiset twins do not decompose — JX302 — and their matrices are
   all-dependent);
 - ``por()`` OFF leaves the run jaxpr BIT-IDENTICAL (the
   telemetry/checked/prededup discipline); ON, property verdicts are
   identical everywhere — with a strict generated-candidate reduction on
   the locality-structured fixtures (``fixtures_por.py``) and EXACT
   count/table parity on 2pc, whose verdict-relevant actions are all
   property-visible (the C2 invisibility condition — the honest result
   of a sound analysis, documented in docs/analysis.md);
 - the cycle proviso (all-ample-duplicates ⇒ full expansion) is what
   keeps the toggle fixture's visible action reachable;
 - POR composes with symmetry and prededup, and survives kill+resume.
"""

import numpy as np
import pytest

import jax

from fixtures_por import ToggleSys, WorkersSys

from stateright_tpu.analysis.footprint import (
    FieldSet,
    conjunct_eval_fn,
    extract_footprints,
)
from stateright_tpu.analysis.independence import por_plan, run_independence
from stateright_tpu.models.two_phase_commit import TwoPhaseSys

TPC3_UNIQUE, TPC3_STATES = 288, 1146
WORKERS7_FULL = (2187, 10207)  # 3^7 unique; generated + 1 init
WORKERS7_POR = (15, 15)  # linear in n: the reduction the analysis buys
TOGGLE_FULL = (4, 7)
TOGGLE_POR = (4, 6)  # strictly fewer generated candidates


# -- footprints (2pc is the bit-exactness oracle) ----------------------------


def _tpc3_footprints():
    return extract_footprints(TwoPhaseSys(3)._tensor_cached())


def test_2pc_footprints_are_bit_exact():
    fp = _tpc3_footprints()
    assert fp.decomposed and fp.n_actions == 17
    assert all(a.decided for a in fp.actions)
    # layout: rm 2b*3 @0, tm 2b @6, tm_prepared 3b @8, msg_prepared 3b
    # @11, msg_commit @14, msg_abort @15
    def masks(a):
        return (
            fp.actions[a].writes.to_json(),
            fp.actions[a].guard.to_json(),
        )

    assert masks(0) == ({"0": "0x40c0"}, {"0": "0x7c0"})  # tm_commit
    assert masks(1) == ({"0": "0x80c0"}, {"0": "0xc0"})  # tm_abort
    # per-RM block for RM 0: slots 2..6
    assert masks(2) == ({"0": "0x100"}, {"0": "0x8c0"})  # tm_rcv_prepared
    assert masks(3) == ({"0": "0x803"}, {"0": "0x3"})  # rm_prepare
    assert masks(4) == ({"0": "0x3"}, {"0": "0x3"})  # rm_choose_abort
    assert masks(5) == ({"0": "0x3"}, {"0": "0x4000"})  # rm_rcv_commit
    assert masks(6) == ({"0": "0x3"}, {"0": "0x8000"})  # rm_rcv_abort
    # every property reads exactly the rm field
    assert [p.to_json() for p in fp.prop_reads] == [{"0": "0x3f"}] * 3


def test_2pc_guard_conjuncts_and_kernel_agree_with_guard():
    import jax.numpy as jnp

    m = TwoPhaseSys(3)
    t = m._tensor_cached()
    fp = extract_footprints(t)
    cj = fp.conjuncts
    assert cj is not None and cj.n_leaves == 17 and cj.max_conjuncts == 2
    # tm_commit = (tm == init) AND (all prepared): two conjuncts with the
    # tm / tm_prepared read sets
    assert [s.to_json() for s in cj.sets[0]] == [
        {"0": "0xc0"}, {"0": "0x700"}
    ]
    fn = conjunct_eval_fn(t)
    rows = jnp.asarray(np.asarray(t.init_rows(), np.uint64))
    leaves = [np.asarray(x) for x in fn(rows)]
    _, valid = t.step_rows(rows)
    v = np.asarray(valid)[0]
    for a in range(fp.n_actions):
        idx = cj.leaf_idx[a]
        assert idx is not None
        assert v[a] == all(
            bool(leaves[j][0] if lane is None else leaves[j][0, lane])
            for (j, lane) in idx
        )


def test_fieldset_top_is_conservative():
    top = FieldSet.top_set()
    assert top.intersects(FieldSet.of(0, 1))
    assert top.intersects(top)
    assert not top.intersects(FieldSet.empty())
    assert FieldSet.of(0, 0b1100).intersects(FieldSet.of(0, 0b0100))
    assert not FieldSet.of(0, 0b1100).intersects(FieldSet.of(0, 0b0011))
    assert not FieldSet.of(0, 1).intersects(FieldSet.of(1, 1))


# -- the conflict matrix ------------------------------------------------------


def test_2pc_conflict_matrix_pins():
    m = TwoPhaseSys(3)
    rep = run_independence(m._tensor_cached(), list(m.properties()))
    c = rep.conflict
    assert c.shape == (17, 17)
    assert np.array_equal(c, c.T) and c.diagonal().all()
    assert rep.independent_pairs == 102
    # per-RM blocks: RM0's rm_prepare is independent of every RM1 action
    for j in range(7, 12):
        assert not c[3, j]
    # tm_commit writes msg_commit, which guards every rm_rcv_commit
    for i in range(3):
        assert c[0, 5 + 5 * i]
    # visibility: every rm-writing action is visible to the properties
    # (they read the whole rm field) — the C2 reason 2pc cannot reduce
    assert rep.visible.sum() == 12
    assert not rep.visible[0] and not rep.visible[1]  # tm actions


def test_undecidable_defaults_to_dependent_on_slot_multiset_twins():
    """paxos's per-slot delivery writes are data-dependent (dst comes from
    the message): the kernel does not decompose, JX302 fires, and the
    matrix is conservatively ALL-dependent — the acceptance contract that
    undecidable pairs can never claim independence."""
    from stateright_tpu.models.paxos import paxos_model

    m = paxos_model(2)
    rep = run_independence(m._tensor_cached(), list(m.properties()))
    assert not rep.footprints.decomposed
    assert rep.independent_pairs == 0
    assert rep.conflict.all()
    assert "JX302" in {f.rule_id for f in rep.findings}
    plan = por_plan(m._tensor_cached(), list(m.properties()))
    assert not plan.usable


def test_por_plan_fallback_reasons():
    from stateright_tpu.models.dining import dining_model

    dm = dining_model(3)
    plan = por_plan(dm._tensor_cached(), list(dm.properties()))
    assert not plan.usable
    assert "eventually" in plan.fallback_reason
    rep = run_independence(dm._tensor_cached(), list(dm.properties()))
    assert "JX304" in {f.rule_id for f in rep.findings}

    wm = WorkersSys(4)
    wplan = por_plan(wm._tensor_cached(), list(wm.properties()))
    assert wplan.usable and wplan.fallback_reason is None
    # workers 1..3 are invisible; worker 0 is visible to both properties
    assert list(wplan.visible.astype(int)) == [1, 0, 0, 0]


def test_jx301_undecidable_action_is_dependent_on_everything():
    """A kernel that decomposes but contains one data-dependent write
    (scatter with a traced index) gets JX301 on that action, whose
    conflict row is all-True."""
    from stateright_tpu.core import Property
    from stateright_tpu.parallel.tensor_model import BitPacker, TensorModel

    class OneBad(TensorModel):
        def __init__(self):
            self.packer = BitPacker([("a", 4), ("b", 4)])
            self.width = 2  # word 1 is an extra scratch word
            self.max_actions = 2
            self.model = None

        def init_rows(self):
            return np.zeros((1, 2), np.uint64)

        def step_rows(self, rows):
            import jax.numpy as jnp

            pk = self.packer
            a = pk.get(rows, "a")
            s0 = pk.set(rows, "a", jnp.minimum(a + jnp.uint64(1),
                                               jnp.uint64(15)))
            # data-dependent write: the target word comes from a field
            idx = (a & jnp.uint64(1)).astype(jnp.int32)
            s1 = jnp.stack([rows[..., 0], rows[..., 1]], -1)
            s1 = jnp.take_along_axis(
                jnp.broadcast_to(s1[..., None], s1.shape + (2,)),
                idx[..., None, None], axis=-1,
            )[..., 0]
            return (
                jnp.stack([s0, s1], -2),
                jnp.stack([a < jnp.uint64(15),
                           jnp.ones_like(a, bool)], -1),
            )

        def property_masks(self, rows):
            import jax.numpy as jnp

            return jnp.stack(
                [self.packer.get(rows, "a") <= jnp.uint64(15)], -1
            )

    t = OneBad()
    rep = run_independence(t, [Property.always("p", lambda m, s: True)])
    assert rep.footprints.decomposed
    und = rep.footprints.undecided_actions
    assert und == [1]
    assert rep.conflict[1].all() and rep.conflict[:, 1].all()
    assert "JX301" in {f.rule_id for f in rep.findings}


# -- JX303: the vacuous-property lint (satellite) ----------------------------


def test_jx303_fires_on_property_reading_never_written_field():
    from stateright_tpu.core import Property
    from stateright_tpu.parallel.tensor_model import BitPacker, TensorModel

    class DeadProp(TensorModel):
        def __init__(self):
            self.packer = BitPacker([("live", 2), ("frozen", 2)])
            self.width = 1
            self.max_actions = 1
            self.model = None

        def init_rows(self):
            return np.zeros((1, 1), np.uint64)

        def step_rows(self, rows):
            import jax.numpy as jnp

            pk = self.packer
            v = pk.get(rows, "live")
            return (
                jnp.stack(
                    [pk.set(rows, "live", v + jnp.uint64(1))], -2
                ),
                jnp.stack([v < jnp.uint64(2)], -1),
            )

        def property_masks(self, rows):
            import jax.numpy as jnp

            # reads ONLY the never-written field
            return jnp.stack(
                [self.packer.get(rows, "frozen") == jnp.uint64(0)], -1
            )

    from stateright_tpu.core import Property

    rep = run_independence(
        DeadProp(), [Property.always("frozen is 0", lambda m, s: True)]
    )
    jx303 = [f for f in rep.findings if f.rule_id == "JX303"]
    assert len(jx303) == 1
    assert jx303[0].severity == "warning"
    assert "frozen is 0" in jx303[0].location

    # and the flagship example is CLEAN: its properties read written fields
    m = TwoPhaseSys(3)
    rep2 = run_independence(m._tensor_cached(), list(m.properties()))
    assert not [f for f in rep2.findings if f.rule_id == "JX303"]


@pytest.mark.medium
def test_fleet_independence_gate_is_clean():
    """The CI gate's contract: every bundled example produces a
    well-formed conflict matrix with no ERROR-level JX3xx finding."""
    import io

    from stateright_tpu.models._cli import fleet_independence

    buf = io.StringIO()
    assert fleet_independence(stream=buf) == 0
    out = buf.getvalue()
    assert "independence fleet: CLEAN" in out
    # the flagship twin's pair count is visible in the fleet output
    assert "102 independent pair(s)" in out


# -- device-side ample selection ---------------------------------------------


def test_ample_mask_selects_singleton_invisible_worker():
    import jax.numpy as jnp

    from stateright_tpu.ops.por import ample_mask

    m = WorkersSys(4)
    t = m._tensor_cached()
    plan = por_plan(t, list(m.properties()))
    kernel = conjunct_eval_fn(t)
    rows = jnp.asarray(np.asarray(t.init_rows(), np.uint64))
    _, valid = t.step_rows(rows)
    amp = np.asarray(ample_mask(valid, rows, plan, kernel))
    # all 4 workers enabled; the ample set is one INVISIBLE worker
    assert np.asarray(valid).sum() == 4
    assert amp.sum() == 1
    assert not amp[0, 0]  # worker 0 is visible: never a reduced ample


# -- engine wiring: the por-off jaxpr pin ------------------------------------


def test_por_off_leaves_run_jaxpr_bit_identical():
    """The telemetry/checked/prededup contract applied to por()."""

    def run_jaxpr(flag):
        m = TwoPhaseSys(3)
        b = m.checker()
        if flag is not None:
            b = b.por(flag)
        c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    baseline = run_jaxpr(None)
    assert baseline == run_jaxpr(False)
    assert baseline != run_jaxpr(True)  # the selection is really there


# -- verdict parity + pinned reductions --------------------------------------


def test_por_parity_is_bit_identical_on_2pc3():
    """2pc's verdict-relevant actions are all property-visible, so a SOUND
    reduction must select ample == enabled everywhere: counts, traces and
    the visited TABLE itself are bit-identical, and the reduced-vs-full
    tallies honestly report zero reduction."""
    a = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    b = TwoPhaseSys(3).checker().por().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count() == TPC3_STATES
    ta, tb = a._table_np(), b._table_np()
    assert np.array_equal(ta[0], tb[0]) and np.array_equal(ta[1], tb[1])
    assert sorted(a.discoveries()) == sorted(b.discoveries())
    st = b.por_status()
    assert st["enabled"] is True
    assert st["rows_reduced"] == 0 and st["candidates_masked"] == 0


def test_por_strict_reduction_pinned_on_workers7():
    """The reduction the analysis buys where it IS sound: 3^7 = 2187
    unique states collapse to 15 (one interleaving of the independent
    invisible workers), with identical property verdicts."""
    full = WorkersSys(7).checker().spawn_tpu(
        sync=True, capacity=1 << 13, batch=64
    )
    por = WorkersSys(7).checker().por().spawn_tpu(
        sync=True, capacity=1 << 13, batch=64
    )
    assert (full.unique_state_count(), full.state_count()) == WORKERS7_FULL
    assert (por.unique_state_count(), por.state_count()) == WORKERS7_POR
    assert sorted(full.discoveries()) == sorted(por.discoveries()) == [
        "w0 done"
    ]
    st = por.por_status()
    assert st["rows_reduced"] > 0
    assert st["candidates_masked"] > 0


def test_cycle_proviso_keeps_visible_action_reachable_on_toggle():
    """The toggle cycle starves the visible one-shot action without the
    all-ample-duplicates proviso; with it, every state and the discovery
    survive — at strictly fewer generated candidates."""
    full = ToggleSys().checker().spawn_tpu(
        sync=True, capacity=1 << 8, batch=8
    )
    por = ToggleSys().checker().por().spawn_tpu(
        sync=True, capacity=1 << 8, batch=8
    )
    assert (full.unique_state_count(), full.state_count()) == TOGGLE_FULL
    assert (por.unique_state_count(), por.state_count()) == TOGGLE_POR
    assert sorted(por.discoveries()) == ["y set"]
    st = por.por_status()
    assert st["rows_full_proviso"] >= 1  # the proviso demonstrably fired


def test_por_fallback_on_liveness_model_runs_full_expansion():
    """dining declares eventually properties: por() must fall back (the
    JX304 contract) and produce exactly the plain run."""
    from stateright_tpu.models.dining import dining_model

    a = dining_model(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    b = dining_model(3).checker().por().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert b._por is False
    st = b.por_status()
    assert st["enabled"] is False and "eventually" in st["fallback"]
    assert a.unique_state_count() == b.unique_state_count()
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())


# -- cartography / status surfaces -------------------------------------------


def test_por_block_rides_cartography_and_reconciles():
    c = (
        WorkersSys(7).checker().por().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 13, batch=64)
    )
    assert (c.unique_state_count(), c.state_count()) == WORKERS7_POR
    cart = c.cartography()
    assert cart is not None
    # reconciliation holds with the REDUCED totals: the histogram counts
    # what was actually generated
    assert sum(cart["depth_hist"]) == c.unique_state_count()
    assert sum(cart["action_hist"]) == c.state_count() - 1
    por = cart["por"]
    assert set(por) == {
        "rows_reduced", "rows_full_proviso", "candidates_masked"
    }
    assert por["rows_reduced"] > 0
    status = c.por_status()
    assert all(status[k] == v for k, v in por.items())


def test_por_status_surfaces_in_explorer_status_view():
    from stateright_tpu.explorer import _Snapshot, _status_view

    m = WorkersSys(4)
    c = m.checker().por().spawn_tpu(sync=True, capacity=1 << 10, batch=16)
    view = _status_view(m, c, _Snapshot())
    assert view["por"]["enabled"] is True
    assert view["por"]["rows_reduced"] > 0
    # a por-less run reports null, never a fabricated block
    c2 = WorkersSys(4).checker().spawn_tpu(
        sync=True, capacity=1 << 10, batch=16
    )
    assert _status_view(m, c2, _Snapshot())["por"] is None


# -- composition + resume (satellites; heavier: daily tier) ------------------


@pytest.mark.slow
def test_por_composes_with_symmetry_and_prededup_on_2pc_and_dining():
    """Same verdicts, counts pinned: POR × symmetry × prededup on 2pc
    (sym-reduced space 94) and POR × prededup on dining (liveness
    fallback path)."""
    a = TwoPhaseSys(3).checker().symmetry().prededup().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    b = TwoPhaseSys(3).checker().symmetry().prededup().por().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert a.unique_state_count() == b.unique_state_count() == 94
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())

    from stateright_tpu.models.dining import dining_model

    da = dining_model(3).checker().prededup().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    db = dining_model(3).checker().prededup().por().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert da.unique_state_count() == db.unique_state_count()
    assert sorted(da.discoveries()) == sorted(db.discoveries())


@pytest.mark.slow
def test_killed_and_resumed_por_run_matches_uninterrupted():
    """Kill a por() 2pc-5 run mid-flight, resume from the snapshot: the
    final totals match an uninterrupted run exactly (2pc reduces nothing,
    so the resume-boundary full-expansion boost is also count-neutral).
    On a REDUCING model the boost legitimately widens the explored
    lattice, so the contract there is verdict parity + soundness (a
    subset of the full space that still finds the discovery)."""
    import time

    m = TwoPhaseSys(5)
    c = m.checker().por().spawn_tpu(capacity=1 << 14, batch=256)
    time.sleep(0.3)
    c.stop()
    c.join()
    snap = c.checkpoint()
    r = TwoPhaseSys(5).checker().por().spawn_tpu(sync=True, resume=snap)
    u = TwoPhaseSys(5).checker().por().spawn_tpu(
        sync=True, capacity=1 << 14, batch=256
    )
    assert r.unique_state_count() == u.unique_state_count() == 8832
    assert sorted(r.discoveries()) == sorted(u.discoveries())

    w = WorkersSys(7).checker().por().spawn_tpu(
        capacity=1 << 13, batch=8, steps_per_call=1
    )
    time.sleep(0.1)
    w.stop()
    w.join()
    wr = WorkersSys(7).checker().por().spawn_tpu(
        sync=True, resume=w.checkpoint()
    )
    assert sorted(wr.discoveries()) == ["w0 done"]
    assert wr.unique_state_count() <= 2187  # sound subset of the space


@pytest.mark.slow
def test_2pc7_por_counts_pinned_full_parity():
    """The 2pc-7 pin the acceptance asks for, with the honest number: a
    SOUND reduction selects ample == enabled on 2pc (every rm action is
    property-visible), so the reduced successor count EQUALS full
    expansion — pinned so any future analysis change that starts
    reducing 2pc (or inflating it) trips loudly and gets re-verified."""
    caps = dict(capacity=1 << 21, queue_capacity=1 << 19, batch=1024,
                steps_per_call=32, cand=1 << 14)
    full = TwoPhaseSys(7).checker().spawn_tpu(sync=True, **caps)
    por = TwoPhaseSys(7).checker().por().spawn_tpu(sync=True, **caps)
    assert full.unique_state_count() == por.unique_state_count() == 296_448
    assert full.state_count() == por.state_count()
    st = por.por_status()
    assert st["rows_reduced"] == 0 and st["candidates_masked"] == 0


# -- mesh engine --------------------------------------------------------------


def test_mesh_por_parity_and_reduction():
    a = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    b = TwoPhaseSys(3).checker().por().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())
    # and the reducing fixture reduces on the mesh too, same verdicts
    wf = WorkersSys(7).checker().spawn_tpu(
        sync=True, devices=2, capacity=1 << 13, frontier_capacity=1 << 9
    )
    wp = WorkersSys(7).checker().por().spawn_tpu(
        sync=True, devices=2, capacity=1 << 13, frontier_capacity=1 << 9
    )
    assert wf.unique_state_count() == 2187
    assert wp.unique_state_count() < wf.unique_state_count()
    assert wp.state_count() < wf.state_count()
    assert sorted(wp.discoveries()) == ["w0 done"]

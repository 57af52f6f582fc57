"""The soundness sanitizer (``stateright_tpu/analysis/interval.py`` +
``sanitizer.py``) and checked execution mode: fault-injection models caught
BOTH statically (pinned JX2xx rule ids) and dynamically (checkify error
naming the row), the interval pass proving shipped twins' sites in range,
the checked-off bit-identity contract, and the CLI/Explorer/report
surfaces."""

from __future__ import annotations

import http.client
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu import Model, Property
from stateright_tpu.analysis import (
    AuditError,
    CheckedExecutionError,
    Severity,
    audit_model,
)
from stateright_tpu.analysis.interval import IVal
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.tensor_model import (
    BitPacker,
    RowDomain,
    TensorBackedModel,
    TensorModel,
)

EMPTY = (1 << 64) - 1


# ---------------------------------------------------------------------------
# fault-injection twins (the seeded corrupted models of the satellite task)
# ---------------------------------------------------------------------------


class _FaultBase(TensorModel):
    width = 1
    max_actions = 1

    def __init__(self, model):
        self.model = model

    def init_rows(self):
        return np.zeros((1, 1), np.uint64)

    def encode_state(self, s):
        return (int(s),)

    def decode_state(self, row):
        return int(row[0])

    def property_masks(self, rows):
        return jnp.ones((rows.shape[0], 1), bool)


class OOBGatherTwin(_FaultBase):
    """A 3-bit counter field indexes a 4-entry table: values 4..7 silently
    clamp on TPU — dropped successors, under-explored space (JX201)."""

    packer = BitPacker([("count", 3)])

    def __init__(self, model):
        super().__init__(model)
        self.pk = OOBGatherTwin.packer

    def step_rows(self, rows):
        c = self.pk.get(rows, "count").astype(jnp.int32)
        tbl = jnp.asarray([1, 2, 3, 4], jnp.uint64)
        nxt = tbl[c]  # OOB for c in 4..7
        succ = rows.at[..., 0].set(nxt)[:, None, :]
        valid = (c < 7)[:, None]
        return succ, valid


class OOBScatterTwin(_FaultBase):
    """A 3-bit field used as a dynamic-update start into a 4-wide vector:
    the write silently clamps/misplaces (JX202, the buckets.insert class)."""

    def __init__(self, model):
        super().__init__(model)
        self.pk = BitPacker([("slot", 3)])

    def step_rows(self, rows):
        s = self.pk.get(rows, "slot").astype(jnp.int32)
        vec = jnp.zeros((rows.shape[0], 4), jnp.uint64)
        upd = jnp.ones((rows.shape[0], 1), jnp.uint64)
        marked = jax.lax.dynamic_update_slice(vec, upd, (jnp.int32(0), s[0]))
        succ = rows.at[..., 0].set(marked[:, 0] + rows[..., 0])[:, None, :]
        valid = (s < 7)[:, None]
        return succ, valid


class OverflowCounterTwin(_FaultBase):
    """count + 5 into a 2-bit field: EVERY input overflows the declared
    width before the mask — the packed counter wraps (JX203 warning)."""

    def __init__(self, model):
        super().__init__(model)
        self.pk = BitPacker([("count", 2)])

    def step_rows(self, rows):
        c = self.pk.get(rows, "count")
        succ = self.pk.set(rows, "count", c + jnp.uint64(5))[:, None, :]
        valid = (c < jnp.uint64(3))[:, None]
        return succ, valid


class EmptyReadTwin(_FaultBase):
    """Gathers from a table whose tail is EMPTY padding, then does
    arithmetic on the result with no EMPTY comparison (JX204)."""

    def step_rows(self, rows):
        tbl = jnp.asarray([1, 2, EMPTY, EMPTY], jnp.uint64)
        v = tbl[(rows[..., 0] & jnp.uint64(3)).astype(jnp.int32)]
        succ = rows.at[..., 0].set(v + jnp.uint64(1))[:, None, :]
        valid = (rows[..., 0] < jnp.uint64(3))[:, None]
        return succ, valid


class DeadBranchTwin(_FaultBase):
    """A 3-bit field compared against 8: the predicate is constantly true,
    the other branch is dead (JX205, model smell)."""

    def __init__(self, model):
        super().__init__(model)
        self.pk = BitPacker([("v", 3)])

    def step_rows(self, rows):
        v = self.pk.get(rows, "v")
        nxt = jnp.where(v < jnp.uint64(8), v + jnp.uint64(1),
                        jnp.uint64(99))  # dead branch
        succ = self.pk.set(rows, "v", nxt & jnp.uint64(7))[:, None, :]
        valid = (v < jnp.uint64(1))[:, None]
        return succ, valid


class _HostModel(TensorBackedModel, Model):
    twin_cls = _FaultBase

    def tensor_model(self):
        return self.twin_cls(self)

    def init_states(self):
        return [0]

    def actions(self, s):
        return [0] if s < 7 else []

    def next_state(self, s, a):
        return s + 1

    def properties(self):
        return [Property.always("ok", lambda m, s: True)]


def _host_model(twin_cls):
    class M(_HostModel):
        pass

    M.__name__ = M.__qualname__ = f"Host_{twin_cls.__name__}"
    M.twin_cls = twin_cls
    return M()


# ---------------------------------------------------------------------------
# static: pinned rule ids per fault class
# ---------------------------------------------------------------------------


def _pinned(twin_cls, rule_id, severity):
    report = audit_model(_host_model(twin_cls))
    hits = [f for f in report.findings if f.rule_id == rule_id]
    assert hits, report.format()
    assert all(f.severity == severity for f in hits), report.format()
    return report, hits


def test_oob_gather_pins_jx201_error():
    report, hits = _pinned(OOBGatherTwin, "JX201", Severity.ERROR)
    # the message names the learned interval and the escaped axis
    assert "[0, 7]" in hits[0].message and "axis 4" in hits[0].message
    assert not report.ok


def test_oob_update_pins_jx202_error():
    report, _ = _pinned(OOBScatterTwin, "JX202", Severity.ERROR)
    assert not report.ok


def test_overflowing_counter_pins_jx203_warning():
    report, hits = _pinned(OverflowCounterTwin, "JX203", Severity.WARNING)
    assert "[5, 8]" in hits[0].message  # every input escapes mask 0x3
    assert report.ok  # warning severity: does not abort spawns


def test_empty_sentinel_read_pins_jx204_warning():
    _pinned(EmptyReadTwin, "JX204", Severity.WARNING)


def test_dead_branch_pins_jx205_info():
    _pinned(DeadBranchTwin, "JX205", Severity.INFO)


def test_spawn_preflight_aborts_on_jx201_with_machine_readable_rules():
    """The sanitizer is part of the spawn preflight: a JX201 aborts before
    any device work, and AuditError carries the rule ids machine-readably
    (the CLI exit-path contract)."""
    m = _host_model(OOBGatherTwin)
    with pytest.raises(AuditError, match="JX201") as exc:
        m.checker().spawn_tpu(sync=True, batch=8, capacity=1 << 10)
    assert "JX201" in exc.value.rule_ids


# ---------------------------------------------------------------------------
# static: precision on clean kernels
# ---------------------------------------------------------------------------


def test_2pc_twin_proves_every_site():
    report = audit_model(TwoPhaseSys(3), deep=True)
    s = report.metrics["sanitizer"]
    assert s["clean"] and s["sites"] > 0
    assert s["proved"] == s["sites"] and s["undecided"] == 0
    assert not report.by_rule("JX201") and not report.by_rule("JX202")


def test_compiled_actor_twin_proves_every_site():
    """The compiled actor twin's table gathers (``trans[sc * ne + ecode]``)
    are provable only through the declared RowDomain: state-code field
    bounds + EMPTY-sentinel slot words.  This is the tentpole's precision
    acceptance — compiled models must be PROVED, not undecided."""
    from stateright_tpu.models.dining import dining_model

    report = audit_model(dining_model(3), deep=True)
    s = report.metrics["sanitizer"]
    assert s["seeded"], "compiled twin must declare a row domain"
    assert s["sites"] > 0 and s["proved"] == s["sites"], s
    assert s["clean"]


def test_row_domain_field_bound_tightens_below_field_width():
    """A 3-bit field declared to hold only codes 0..4 proves a gather from
    a 5-entry table — the field-width fallback alone could not."""

    class FiveStateTwin(_FaultBase):
        def __init__(self, model):
            super().__init__(model)
            self.pk = BitPacker([("code", 3)])

        def row_domain(self):
            return RowDomain.from_packer(self.pk,
                                         field_bounds={"code": 4})

        def step_rows(self, rows):
            c = self.pk.get(rows, "code").astype(jnp.int32)
            tbl = jnp.asarray([1, 2, 3, 4, 0], jnp.uint64)
            succ = rows.at[..., 0].set(tbl[c])[:, None, :]
            valid = (c < 4)[:, None]
            return succ, valid

    report = audit_model(_host_model(FiveStateTwin))
    s = report.metrics["sanitizer"]
    assert not report.by_rule("JX201"), report.format()
    assert s["proved"] == s["sites"]

    class FiveStateUnseeded(FiveStateTwin):
        def row_domain(self):
            return None  # falls back to field WIDTH (0..7): escapes

    report = audit_model(_host_model(FiveStateUnseeded))
    assert report.by_rule("JX201"), report.format()


def test_scan_widening_never_narrows_ys():
    """Soundness of loop widening: a scan whose carry outgrows the
    widening budget must NOT report its ys at the narrow pre-widening
    bounds — the gather it feeds is *undecided* (info), never 'proved'
    against a small table."""

    class ScanTwin(_FaultBase):
        def step_rows(self, rows):
            def body(c, _):
                return c + jnp.int32(1), c

            _, ys = jax.lax.scan(body, jnp.int32(0), None, length=10)
            tbl = jnp.asarray([1, 2, 3, 4], jnp.uint64)
            v = tbl[jnp.broadcast_to(ys[-1], (rows.shape[0],))]
            succ = rows.at[..., 0].set(v)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(3))[:, None]
            return succ, valid

    report = audit_model(_host_model(ScanTwin))
    s = report.metrics["sanitizer"]
    # the index escaped the widened carry's knowledge: the site must not
    # count as proved, and must not be a false-positive ERROR either
    assert s["proved"] < s["sites"], s
    assert not [f for f in report.by_rule("JX201")
                if f.severity == Severity.ERROR], report.format()


def test_abs_index_does_not_false_positive():
    """|i - j| over masked fields is a classic in-range index; the abs
    rule must fold the negative half instead of keeping it (which would
    verdict a learned-bound escape -> spurious JX201 ERROR)."""

    class AbsTwin(_FaultBase):
        def __init__(self, model):
            super().__init__(model)
            self.pk = BitPacker([("i", 2), ("j", 2)])

        def step_rows(self, rows):
            i = self.pk.get(rows, "i").astype(jnp.int32)
            j = self.pk.get(rows, "j").astype(jnp.int32)
            tbl = jnp.asarray([1, 2, 3, 4], jnp.uint64)  # |i-j| in [0,3]
            succ = rows.at[..., 0].set(tbl[jnp.abs(i - j)])[:, None, :]
            valid = (i < 3)[:, None]
            return succ, valid

    report = audit_model(_host_model(AbsTwin))
    s = report.metrics["sanitizer"]
    assert not report.by_rule("JX201"), report.format()
    assert s["proved"] == s["sites"], s


def test_interval_domain_unit_ops():
    """Spot-checks of the IVal algebra the pass rests on."""
    a = IVal(0, 7)
    assert a.join(IVal(3, 12)).hull() == (0, 12)
    assert a.clip(2, 5).hull() == (2, 5)
    assert a.clip(9, 12) is None  # empty
    s = IVal(0, 100, frozenset({EMPTY}))
    assert s.may_contain(EMPTY)
    assert s.drop_point(EMPTY).hull() == (0, 100)
    assert s.map_exact(lambda v: v >> 6).hull() == (0, EMPTY >> 6)


# ---------------------------------------------------------------------------
# dynamic: checked execution mode
# ---------------------------------------------------------------------------


def test_checked_mode_clean_model_same_counts():
    c = (TwoPhaseSys(3).checker().checked()
         .spawn_tpu(sync=True, batch=64, capacity=1 << 12))
    assert c.unique_state_count() == 288
    assert len(c.discoveries()) == 2  # both sometimes-examples found


def test_checked_mode_names_the_offending_row():
    """The dynamic half of the fault-injection satellite: the OOB gather
    model (statically JX201) also fails loudly under ``.checked()``, with
    the error naming the batch row and decoded state.  skip_audit() is the
    documented route to reproducing a flagged defect on device."""
    m = _host_model(OOBGatherTwin)
    with pytest.raises(CheckedExecutionError) as exc:
        m.checker().skip_audit().checked().spawn_tpu(
            sync=True, batch=8, capacity=1 << 10
        )
    e = exc.value
    assert e.row_index is not None
    assert e.state == 4  # first state whose count field escapes the table
    assert "out-of-bounds" in str(e)
    # and WITHOUT checked mode the same model runs to a silently wrong
    # verdict — the exact failure class the sanitizer exists for
    c = m.checker().skip_audit().spawn_tpu(
        sync=True, batch=8, capacity=1 << 10
    )
    assert c.unique_state_count() == 5  # clamp truncated the 8-state chain


def test_checked_false_leaves_run_jaxpr_bit_identical():
    """The telemetry contract applied to checked mode: checked=False must
    build the exact device program an engine without the feature builds."""

    def run_jaxpr(flag):
        m = TwoPhaseSys(3)  # fresh model => fresh compiled-run cache
        b = m.checker()
        if flag is not None:
            b = b.checked(flag)
        c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    baseline = run_jaxpr(None)
    assert baseline == run_jaxpr(False)
    assert baseline != run_jaxpr(True)  # instrumentation is really there


def test_mesh_engine_runs_checked_at_parity():
    """``checked`` on two devices: the mesh engine runs the one-device
    program, instrumentation included (the deleted ``shard_map`` engine
    refused the flag)."""
    solo = TwoPhaseSys(3).checker().checked().spawn_tpu(sync=True)
    c = TwoPhaseSys(3).checker().checked().spawn_tpu(sync=True, devices=2)
    assert c.n_devices == 2 and c._checked
    assert c.unique_state_count() == solo.unique_state_count() == 288
    assert c.state_count() == solo.state_count()
    assert sorted(c.discoveries()) == sorted(solo.discoveries())


# ---------------------------------------------------------------------------
# surfaces: CLI verbs, Explorer, report plumbing
# ---------------------------------------------------------------------------


def test_cli_sanitize_verb(capsys):
    from stateright_tpu.models import two_phase_commit

    two_phase_commit.main(["sanitize"])
    out = capsys.readouterr().out
    assert "proved in range" in out


def test_cli_fleet_sanitize_subset(capsys):
    from stateright_tpu.models._cli import fleet_sanitize

    rc = fleet_sanitize(["two_phase_commit", "increment"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "sanitize fleet: CLEAN" in out


@pytest.mark.slow
def test_fleet_sanitize_all_examples():
    from stateright_tpu.models._cli import fleet_sanitize

    assert fleet_sanitize() == 0


def test_cli_checked_flag_parses():
    from stateright_tpu.models._cli import pop_checked

    assert pop_checked(["3", "--checked"]) == (True, ["3"])
    assert pop_checked(["--checked"]) == (True, [])
    assert pop_checked(["3"]) == (False, ["3"])


def test_explorer_status_exposes_sanitizer_block():
    from stateright_tpu.explorer import ExplorerServer

    server = ExplorerServer(
        TwoPhaseSys(3).checker(), "localhost:0", strategy="tpu", batch=64
    ).start_background()
    try:
        host, port = server.addr.rsplit(":", 1)
        deadline = time.monotonic() + 60
        status = None
        while time.monotonic() < deadline:
            conn = http.client.HTTPConnection(host, int(port), timeout=10)
            conn.request("GET", "/.status")
            status = json.loads(conn.getresponse().read())
            conn.close()
            if status["done"]:
                break
            time.sleep(0.2)
        assert status is not None and status["done"]
        s = status["sanitizer"]
        assert s is not None and s["clean"] is True
        assert s["proved"] == s["sites"] > 0
        assert s["checked_run"] is False
    finally:
        server.shutdown()


def test_report_merge_dedupes_across_passes():
    from stateright_tpu.analysis import AuditReport

    a = AuditReport(model="M")
    a.add("JX201", Severity.ERROR, "step_rows:gather#1", "escape")
    b = AuditReport(model="M")
    b.add("JX201", Severity.ERROR, "step_rows:gather#1", "escape")  # dup
    b.add("JX203", Severity.WARNING, "step_rows:and#1", "overflow")
    b.metrics["sanitizer"] = {"clean": False}
    a.merge(b)
    assert len(a.findings) == 2  # the duplicate folded away
    assert a.metrics["sanitizer"] == {"clean": False}
    # extend() itself is dedup-safe (cache re-extends must not double up)
    a.extend(list(b.findings))
    assert len(a.findings) == 2

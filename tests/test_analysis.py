"""The preflight static auditor (``stateright_tpu/analysis/``): every rule
class firing on a deliberately broken model, clean (or exactly-pinned)
reports for the shipped fleet, the ``spawn_tpu`` preflight abort +
``skip_audit()`` escape hatch, the ``audit`` CLI verbs, and the
bucket-occupancy counters in the audit/status report."""

from __future__ import annotations

import http.client
import json
import random  # noqa: F401 - referenced by a linted handler below
import time

import numpy as np
import pytest

import jax.numpy as jnp

from stateright_tpu import Model, Property
from stateright_tpu.analysis import (
    AuditError,
    AuditReport,
    Severity,
    audit_model,
)
from stateright_tpu.actor import Actor, ActorModel, Id, Network, Out
from stateright_tpu.actor.device_props import forall_actors
from stateright_tpu.core import Expectation
from stateright_tpu.parallel.tensor_model import (
    TensorBackedModel,
    TensorModel,
)

# ---------------------------------------------------------------------------
# synthetic twins: one per jaxpr rule class
# ---------------------------------------------------------------------------


class _TwinBase(TensorModel):
    """Minimal conformant twin: 2-state chain 0 -> 1."""

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

    def step_rows(self, rows):
        succ = (rows + jnp.uint64(1))[:, None, :]
        valid = (rows[..., 0] < jnp.uint64(1))[:, None]
        return succ, valid

    def property_masks(self, rows):
        return jnp.ones((rows.shape[0], 1), bool)


class _HostModel(TensorBackedModel, Model):
    twin_cls = _TwinBase

    def tensor_model(self):
        return self.twin_cls(self)

    def init_states(self):
        return [0]

    def actions(self, s):
        return [0] if s < 1 else []

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


def test_clean_twin_audits_clean():
    report = audit_model(_host_model(_TwinBase), deep=True)
    assert report.ok and not report.warnings
    # the perf preflight always reports
    assert "JX106" in report.rule_ids()
    assert report.metrics["step_rows"]["eqns"] > 0


def test_impure_kernel_retrace_literal():
    """Satellite: a deliberately impure step_rows (closure over a mutated
    host list) must be caught by the double-trace diff (JX104)."""

    class ImpureTwin(_TwinBase):
        def __init__(self, model):
            super().__init__(model)
            self.trace_log = []  # mutated host list the kernel closes over

        def step_rows(self, rows):
            self.trace_log.append(len(self.trace_log))
            k = jnp.uint64(len(self.trace_log))  # differs per trace
            succ = (rows + k)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(ImpureTwin))
    assert any(
        f.rule_id == "JX104" and f.severity == Severity.ERROR
        for f in report.findings
    ), report.format()


def test_impure_kernel_retrace_consts():
    """Same rule, other branch: identical jaxpr structure but a mutated
    closed-over array (constants differ between traces)."""

    class ConstMutTwin(_TwinBase):
        def __init__(self, model):
            super().__init__(model)
            self.offsets = np.zeros(4, np.uint64)

        def step_rows(self, rows):
            self.offsets = self.offsets + np.uint64(1)  # drifts per trace
            k = jnp.asarray(self.offsets)[0]
            succ = (rows + k)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(ConstMutTwin))
    assert any(f.rule_id == "JX104" for f in report.findings), report.format()


def test_dtype_escape_float():
    class FloatTwin(_TwinBase):
        def step_rows(self, rows):
            f = rows.astype(jnp.float32) + 1.0  # u64 -> f32 round trip
            succ = f.astype(jnp.uint64)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(FloatTwin))
    assert report.ok  # warning, not error: values < 2^53 survive
    assert any(
        f.rule_id == "JX102" and f.severity == Severity.WARNING
        for f in report.findings
    ), report.format()


def test_dtype_contract_violation():
    class I32Twin(_TwinBase):
        def step_rows(self, rows):
            succ = (rows + jnp.uint64(1)).astype(jnp.int32)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid  # int32 successors: fingerprint corruption

    report = audit_model(_host_model(I32Twin))
    assert any(
        f.rule_id == "JX103" and f.severity == Severity.ERROR
        for f in report.findings
    ), report.format()


def test_shape_contract_violation():
    class WrongArityTwin(_TwinBase):
        max_actions = 2  # declares 2, produces 1

        def step_rows(self, rows):
            succ = (rows + jnp.uint64(1))[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(WrongArityTwin))
    assert any(f.rule_id == "JX103" for f in report.findings), report.format()


def test_dtype_escape_integer_narrowing():
    """The other fingerprint-corrupting dtype class: casting raw u64 row
    words to 32-bit integers (JX107).  Masked field extraction
    (BitPacker.get) must stay quiet — it's the idiom every twin uses."""

    class NarrowTwin(_TwinBase):
        def step_rows(self, rows):
            w = rows.astype(jnp.uint32)  # raw words: top 32 bits zeroed
            succ = (w + jnp.uint32(1)).astype(jnp.uint64)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(NarrowTwin))
    assert any(
        f.rule_id == "JX107" and f.severity == Severity.WARNING
        for f in report.findings
    ), report.format()

    class MaskedTwin(_TwinBase):
        def step_rows(self, rows):
            field = (rows & jnp.uint64(0xFF)).astype(jnp.int32)  # provably small
            succ = (field + 1).astype(jnp.uint64)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(MaskedTwin))
    assert "JX107" not in report.rule_ids(), report.format()


def test_side_effecting_kernel():
    class CallbackTwin(_TwinBase):
        def step_rows(self, rows):
            import jax

            jax.debug.print("row {}", rows[0, 0])
            succ = (rows + jnp.uint64(1))[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    report = audit_model(_host_model(CallbackTwin))
    assert any(
        f.rule_id == "JX101" and f.severity == Severity.ERROR
        for f in report.findings
    ), report.format()


def test_untraceable_kernel():
    class BrokenTwin(_TwinBase):
        def step_rows(self, rows):
            if rows[0, 0] > 0:  # traced-bool branch: TracerBoolConversionError
                return rows[:, None, :], jnp.ones((rows.shape[0], 1), bool)
            return rows[:, None, :], jnp.zeros((rows.shape[0], 1), bool)

    report = audit_model(_host_model(BrokenTwin))
    assert any(
        f.rule_id == "JX000" and f.severity == Severity.ERROR
        for f in report.findings
    ), report.format()


# ---------------------------------------------------------------------------
# preflight integration: spawn_tpu aborts on errors, skip_audit overrides
# ---------------------------------------------------------------------------


def test_spawn_tpu_preflight_aborts_before_launch():
    class I32Twin(_TwinBase):
        def step_rows(self, rows):
            succ = (rows + jnp.uint64(1)).astype(jnp.int32)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    m = _host_model(I32Twin)
    with pytest.raises(AuditError, match="JX103"):
        m.checker().spawn_tpu(sync=True, batch=8, capacity=1 << 10)
    # escape hatch: the preflight itself is silenced (no AuditError)
    b = m.checker().skip_audit()
    assert b._preflight_audit() is None


def test_preflight_warning_prints_once(capsys):
    class FloatTwin(_TwinBase):
        def step_rows(self, rows):
            f = rows.astype(jnp.float32) + 1.0
            succ = f.astype(jnp.uint64)[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

    m = _host_model(FloatTwin)
    c = m.checker().spawn_tpu(sync=True, batch=8, capacity=1 << 10)
    assert c.unique_state_count() == 2  # warnings do NOT abort the launch
    first = capsys.readouterr().err
    assert "JX102" in first
    m.checker().spawn_tpu(sync=True, batch=8, capacity=1 << 10)
    assert "JX102" not in capsys.readouterr().err  # printed once per model


def test_builder_audit_returns_report():
    report = _host_model(_TwinBase).checker().audit()
    assert isinstance(report, AuditReport)
    assert report.ok
    assert report.to_json()["ok"] is True


# ---------------------------------------------------------------------------
# handler lint rules
# ---------------------------------------------------------------------------


def _actor_model(*actors):
    m = ActorModel(cfg=None)
    for a in actors:
        m.actor(a)
    m.init_network_(Network.new_unordered_nonduplicating())
    return m


def test_handler_nondeterminism():
    class DiceActor(Actor):
        def on_start(self, id: Id, out: Out):
            return 0

        def on_msg(self, id: Id, state, src: Id, msg, out: Out):
            return int(random.random() * 10)  # AH201

    report = audit_model(_actor_model(DiceActor()))
    hits = [f for f in report.findings if f.rule_id == "AH201"]
    assert hits and hits[0].severity == Severity.ERROR, report.format()
    assert "DiceActor" in hits[0].location


def test_handler_inplace_mutation():
    class MutActor(Actor):
        def on_start(self, id: Id, out: Out):
            return 0

        def on_msg(self, id: Id, state, src: Id, msg, out: Out):
            state.items.append(msg)  # AH203: mutating method call
            state.count = 1  # AH203: assignment into the state
            return state

    report = audit_model(_actor_model(MutActor()))
    hits = [f for f in report.findings if f.rule_id == "AH203"]
    assert len(hits) == 2, report.format()
    assert all(f.severity == Severity.ERROR for f in hits)


def test_handler_rebound_state_not_flagged():
    """Rebinding the state name to a local copy and mutating THAT is
    sound; AH203 must not abort it."""

    class CopyActor(Actor):
        def on_start(self, id: Id, out: Out):
            return (0,)

        def on_msg(self, id: Id, state, src: Id, msg, out: Out):
            state = list(state)  # fresh local copy under the same name
            state.append(msg)
            return tuple(state)

    report = audit_model(_actor_model(CopyActor()))
    assert "AH203" not in report.rule_ids(), report.format()


def test_handler_set_iteration_order():
    class SetActor(Actor):
        def on_start(self, id: Id, out: Out):
            return 0

        def on_msg(self, id: Id, state, src: Id, msg, out: Out):
            for peer in {Id(1), Id(2)}:  # AH202: hash-ordered sends
                out.send(peer, msg)
            return None

    report = audit_model(_actor_model(SetActor()))
    assert any(
        f.rule_id == "AH202" and f.severity == Severity.WARNING
        for f in report.findings
    ), report.format()


def test_unhashable_state():
    class ListActor(Actor):
        def on_start(self, id: Id, out: Out):
            return []  # unhashable state

        def on_msg(self, id: Id, state, src: Id, msg, out: Out):
            return None

    report = audit_model(_actor_model(ListActor()))
    assert any(
        f.rule_id == "AH204" and f.severity == Severity.ERROR
        for f in report.findings
    ), report.format()


# -- AH205: the Paxos-ballot trap --------------------------------------------

from dataclasses import dataclass


@dataclass(frozen=True)
class TickState:
    n: int


class Ticker(Actor):
    """Counter that grows forever via a self-addressed message loop — the
    minimal ballot-style unbounded domain."""

    def on_start(self, id: Id, out: Out):
        out.send(id, ("tick",))
        return TickState(0)

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        out.send(id, ("tick",))
        return TickState(state.n + 1)


def test_unbounded_domain_warns():
    report = audit_model(_actor_model(Ticker()), deep=True)
    hits = [f for f in report.findings if f.rule_id == "AH205"]
    assert hits and hits[0].severity == Severity.WARNING, report.format()
    assert "state_bound" in hits[0].message


def test_unbounded_domain_downgraded_with_state_bound():
    class BoundedTicker(TensorBackedModel, ActorModel):
        def tensor_model(self):
            from stateright_tpu.parallel.actor_compiler import (
                compile_actor_model,
            )

            return compile_actor_model(
                self, state_bound=lambda i, s: s.n <= 3
            )

    m = BoundedTicker(cfg=None, init_history=None)
    m.actor(Ticker())
    m.init_network_(Network.new_unordered_nonduplicating())
    m.property(
        Expectation.ALWAYS, "trivial", forall_actors(lambda i, s: True)
    )
    report = audit_model(m, deep=True)
    hits = [f for f in report.findings if f.rule_id == "AH205"]
    assert hits and hits[0].severity == Severity.INFO, report.format()
    assert report.ok and not report.warnings


# ---------------------------------------------------------------------------
# CF301: config mutation after twin resolution is a preflight failure
# ---------------------------------------------------------------------------


def test_config_mutation_after_resolution_flagged():
    """Satellite: TensorBackedModel._config_mutated raises only after the
    first fingerprint; the audit flags the silent window before that."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(3)
    assert m._tensor_cached() is not None  # resolve + snapshot the config
    m.rm_count = 2  # direct write: bypasses _config_mutated entirely
    report = audit_model(m)
    hits = [f for f in report.findings if f.rule_id == "CF301"]
    assert hits and hits[0].severity == Severity.ERROR, report.format()
    with pytest.raises(AuditError, match="CF301"):
        m.checker().spawn_tpu()


def test_config_mutation_invisible_to_signature_caught_deep():
    """The deep tier re-resolves the twin and diffs it against the cache,
    catching drift the cheap signature cannot see (config behind a dict)."""

    class WidthTwin(_TwinBase):
        def __init__(self, model):
            super().__init__(model)
            self.width = model.cfg["w"]

        def init_rows(self):
            return np.zeros((1, self.width), np.uint64)

        def step_rows(self, rows):
            succ = (rows + jnp.uint64(1))[:, None, :]
            valid = (rows[..., 0] < jnp.uint64(1))[:, None]
            return succ, valid

        def encode_state(self, s):
            return (int(s),) * self.width

    class DictCfg(_HostModel):
        twin_cls = WidthTwin

        def __init__(self):
            self.cfg = {"w": 1}  # mutable config the signature cannot see

    m = DictCfg()
    assert m._tensor_cached() is not None
    m.cfg["w"] = 2
    report = audit_model(m, deep=True)
    assert any(f.rule_id == "CF301" for f in report.findings), report.format()


# ---------------------------------------------------------------------------
# satellite: every shipped model audits clean (or exactly-pinned)
# ---------------------------------------------------------------------------


def _shipped_models():
    from stateright_tpu.models.dining import dining_model
    from stateright_tpu.models.increment import Increment
    from stateright_tpu.models.increment_lock import IncrementLock
    from stateright_tpu.models.linearizable_register import abd_model
    from stateright_tpu.models.paxos import paxos_model
    from stateright_tpu.models.quickstart import (
        SlidingPuzzle,
        vector_clock_model,
    )
    from stateright_tpu.models.single_copy_register import single_copy_model
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.models.write_once_register import wo_register_model

    return [
        ("two_phase_commit", TwoPhaseSys(3)),
        ("paxos", paxos_model(1)),
        ("linearizable_register", abd_model(2, 2)),
        ("single_copy_register", single_copy_model(1)),
        ("write_once_register", wo_register_model(1, 2)),
        ("dining", dining_model(3)),
        ("increment", Increment(2)),
        ("increment_lock", IncrementLock(2)),
        ("sliding_puzzle", SlidingPuzzle()),
        ("vector_clocks", vector_clock_model()),
    ]


def test_shipped_models_audit_clean():
    """New rules cannot silently break the fleet: every shipped model must
    stay free of errors AND warnings (infos are advisory)."""
    bad = []
    for name, model in _shipped_models():
        report = audit_model(model, deep=True)
        if report.errors or report.warnings:
            bad.append((name, report.format()))
    assert not bad, "\n\n".join(f"{n}:\n{r}" for n, r in bad)


@pytest.mark.parametrize("name", ["abd_ordered_2x3", "raft2_ordered", "paxos_lossy_1"])
def test_the_overlaid_record_gather_is_proved_in_range(name):
    """The compiled twin's deliver block indexes ONE transition record,
    overlaid over the actors, by ``sc_dst * ne + ecode``: the interval
    pass PROVES it in range (``sc_dst`` is bounded by the union of the
    ``a{i}`` bounds), every site of both kernels decided - not a weaker
    claim than the per-actor ``trans[sc * ne + ecode]`` gathers had."""
    from stateright_tpu.actor import Network
    from stateright_tpu.models.linearizable_register import abd_ordered
    from stateright_tpu.models.paxos import paxos_lossy
    from stateright_tpu.models.raft import raft_model

    model = {
        "abd_ordered_2x3": lambda: abd_ordered(2, 3),
        "raft2_ordered": lambda: raft_model(2, network=Network.new_ordered()),
        "paxos_lossy_1": lambda: paxos_lossy(1, 3),
    }[name]()
    report = audit_model(model, deep=True)
    kernels = report.metrics["sanitizer"]["kernels"]
    assert kernels["step_rows"]["sites"] >= 1
    for k in kernels.values():
        assert k["proved"] == k["sites"] and k["undecided"] == 0, kernels
    assert not [f for f in report.findings if f.rule_id in ("JX201", "JX203")]
    assert not report.errors and not report.warnings, report.format()


def test_quickstart_clock_pinned_finding():
    """The Lamport clock model is the one shipped example with a pinned
    non-clean report: logical clocks grow without bound (AH205), which is
    exactly what the rule exists to catch."""
    from stateright_tpu.models.quickstart import clock_model

    report = audit_model(clock_model(), deep=True)
    assert report.ok  # warning-severity only
    assert {f.rule_id for f in report.warnings} == {"AH205"}


@pytest.mark.slow
def test_fleet_audit_all_examples():
    from stateright_tpu.models._cli import fleet_audit

    assert fleet_audit() == 0


# ---------------------------------------------------------------------------
# CLI verbs
# ---------------------------------------------------------------------------


def test_cli_audit_verb(capsys):
    from stateright_tpu.models import increment

    increment.main(["audit"])
    out = capsys.readouterr().out
    assert "audit Increment" in out
    assert "0 error(s)" in out


def test_cli_fleet_audit_subset(capsys):
    from stateright_tpu.models._cli import fleet_audit

    rc = fleet_audit(
        ["increment", "increment_lock", "two_phase_commit", "quickstart"]
    )
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "audit fleet: CLEAN" in out
    # the lamport example's pinned AH205 warning rides along without
    # failing the fleet (errors fail, warnings do not)
    assert "AH205" in out


# ---------------------------------------------------------------------------
# satellite: bucket-occupancy counters in the audit/status report
# ---------------------------------------------------------------------------


def test_occupancy_stats_and_audit_metrics():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(3)
    c = m.checker().spawn_tpu(sync=True, batch=64, capacity=1 << 12)
    stats = c.occupancy_stats()
    assert stats is not None
    assert stats["occupied"] == c.unique_state_count() == 288
    assert 0 < stats["load_factor"] <= 1
    assert (
        sum(k * v for k, v in enumerate(stats["histogram"]))
        == stats["occupied"]
    )
    assert stats["max_bucket"] <= stats["slots_per_bucket"]
    # the counters fold into the model's last audit report
    assert m._audit_report.metrics["table"]["occupied"] == 288


def test_explorer_status_exposes_audit_and_table():
    from stateright_tpu.explorer import ExplorerServer
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

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
        # the preflight audit report rides /.status
        assert status["audit"] is not None
        assert status["audit"]["ok"] is True
        assert status["audit"]["model"] == "TwoPhaseSys"
        # ... and so do the visited-table occupancy counters
        assert status["table"]["occupied"] == status["unique_state_count"]
    finally:
        server.shutdown()

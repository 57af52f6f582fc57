"""Write-once register example: first write wins, later writes fail.

The reference ships the write-once *harness* (client + history recorder +
``Rewrite`` impls, ``src/actor/write_once_register.rs:119-299``) but never an
example server validated with it; this module closes that loop end-to-end.
Each server stores at most one value: the first ``put`` is acknowledged with
``put_ok`` and every later one with ``put_fail`` (recorded as the spec's
``write_fail`` return); ``get`` returns the stored value.

With one server the system is linearizable against the
:class:`~stateright_tpu.semantics.WORegister` spec.  With two independent
servers it is not — a client can read ``NULL`` from a server that never saw
the successful write — and the checker finds the violating trace.

Symmetry: servers are interchangeable, clients are not (they write distinct
values), so ``check-sym`` canonicalizes by sorting the *server block* only
and rewriting server ids through the network and history, the role-restricted
analogue of the reference's ``Rewrite`` impls
(``write_once_register.rs:269-299``).
"""

from __future__ import annotations

from typing import Optional

from .. import Expectation
from ..actor import Actor, ActorModel, Id, Network, Out
from ..actor.register import NULL_VALUE, GetOk, value_chosen
from ..actor.write_once_register import (
    PutFail,
    WORegisterClient,
    record_returns,
)
from ..actor.register import PutOk, record_invocations
from ..fingerprint import stable_hash
from ..parallel.tensor_model import TensorBackedModel
from ..semantics import LinearizabilityTester, WORegister
from ..symmetry import RewritePlan, rewrite_value
from ._cli import (
    apply_encoding,
    apply_perf,
    default_threads,
    make_audit_cmd,
    make_profile_cmd,
    make_capacity_cmd,
    make_compare_cmd,
    make_costmodel_cmd,
    make_report_cmd,
    make_independence_cmd,
    make_sanitize_cmd,
    pop_checked,
    pop_perf,
    pop_watch,
    run_cli,
    spawn_watched,
)


class WOServer(Actor):
    """Stores the first value put; later puts fail (write-once)."""

    def on_start(self, id: Id, out: Out):
        return NULL_VALUE

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        kind = msg[0]
        if kind == "put":
            if state == NULL_VALUE:
                out.send(src, PutOk(msg[1]))
                return msg[2]
            out.send(src, PutFail(msg[1]))
            return None
        if kind == "get":
            out.send(src, GetOk(msg[1], state))
            return None
        return None


def server_representative(state, server_count: int):
    """Canonical member of ``state``'s class under server permutations only:
    the plan sorts indices ``< server_count`` by state hash and pins every
    client index, then rewrites ids through network/history."""
    keys = [
        (0, stable_hash(s)) if i < server_count else (1, i)
        for i, s in enumerate(state.actor_states)
    ]
    plan = RewritePlan.from_values_to_sort(keys)
    return type(state)(
        actor_states=tuple(
            rewrite_value(s, plan) for s in plan.reindex(state.actor_states)
        ),
        network=rewrite_value(state.network, plan),
        is_timer_set=tuple(plan.reindex(state.is_timer_set)),
        history=rewrite_value(state.history, plan),
    )


class WORegisterModel(TensorBackedModel, ActorModel):
    """ActorModel with a mechanically compiled device twin."""

    def tensor_model(self):
        from ..parallel.actor_compiler import CompileError, compile_actor_model

        try:
            return compile_actor_model(self)
        except (CompileError, ValueError):
            return None


def wo_register_model(
    client_count: int, server_count: int = 1, network: Optional[Network] = None
) -> ActorModel:
    if network is None:
        network = Network.new_unordered_nonduplicating()
    m = WORegisterModel(
        cfg=None, init_history=LinearizabilityTester(WORegister(None))
    )
    for _ in range(server_count):
        m.actor(WOServer())
    for _ in range(client_count):
        m.actor(WORegisterClient(put_count=1, server_count=server_count))
    m.init_network_(network)
    m.property(
        Expectation.ALWAYS,
        "linearizable",
        lambda model, s: s.history.is_consistent(),
    )
    m.property(Expectation.SOMETIMES, "value chosen", value_chosen)
    m.record_msg_in(record_returns)
    m.record_msg_out(record_invocations)
    return m


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    c = int(rest[0]) if rest else 1
    return [(f"write_once_register clients={c} servers=2", wo_register_model(c, 2))]


def main(argv=None):
    def parse(rest):
        client_count = int(rest[0]) if rest else 2
        server_count = int(rest[1]) if len(rest) > 1 else 1
        network = (
            Network.from_name(rest[2])
            if len(rest) > 2
            else Network.new_unordered_nonduplicating()
        )
        return client_count, server_count, network

    def check(rest):
        client_count, server_count, network = parse(rest)
        print(
            f"Model checking a write-once register with {client_count} "
            f"clients and {server_count} servers."
        )
        wo_register_model(client_count, server_count, network).checker().threads(
            default_threads()
        ).spawn_dfs().report()

    def check_sym(rest):
        client_count, server_count, network = parse(rest)
        print(
            f"Checking a write-once register with {client_count} clients and "
            f"{server_count} servers using symmetry reduction."
        )
        wo_register_model(client_count, server_count, network).checker().threads(
            default_threads()
        ).symmetry_with(
            lambda s: server_representative(s, server_count)
        ).spawn_dfs().report()

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        client_count, server_count, network = parse(rest)
        print(
            f"Model checking a write-once register with {client_count} "
            f"clients and {server_count} servers on the device wavefront "
            "engine."
        )
        m = apply_encoding(
            wo_register_model(client_count, server_count, network), perf
        )
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check` (CPU)"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf), watch,
            lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        client_count, server_count, network = parse(rest)
        print(
            f"Model checking a write-once register with {client_count} "
            f"clients and {server_count} servers (auto engine selection)."
        )
        wo_register_model(
            client_count, server_count, network
        ).checker().threads(default_threads()).spawn_auto().report()

    def explore(rest):
        client_count = int(rest[0]) if rest else 2
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        wo_register_model(client_count, 1).checker().serve(addr)

    def spawn_cmd(rest):
        from ..actor import spawn

        id = Id.from_addr("127.0.0.1", 3000)
        print(f"  Server listening on {id.to_addr()}")
        spawn([(id, WOServer())], background=False)

    run_cli(
        "  write_once_register check [CLIENT_COUNT] [SERVER_COUNT] [NETWORK]\n"
        "  write_once_register check-sym [CLIENT_COUNT] [SERVER_COUNT] [NETWORK]\n"
        "  write_once_register check-tpu [CLIENT_COUNT] [SERVER_COUNT] [NETWORK]\n"
        "  write_once_register check-auto [CLIENT_COUNT] [SERVER_COUNT] [NETWORK]\n"
        "  write_once_register explore [CLIENT_COUNT] [ADDRESS]\n"
        "  write_once_register spawn",
        check,
        check_sym=check_sym,
        check_tpu=check_tpu,
        check_auto=check_auto,
        explore=explore,
        spawn=spawn_cmd,
        audit=make_audit_cmd(_audit_models),
        sanitize=make_sanitize_cmd(_audit_models),
        independence=make_independence_cmd(_audit_models),
        profile=make_profile_cmd(_audit_models),
        report=make_report_cmd(_audit_models),
        capacity=make_capacity_cmd(_audit_models),
        costmodel=make_costmodel_cmd(_audit_models),
        compare=make_compare_cmd(),
        argv=argv,
    )


if __name__ == "__main__":
    main()

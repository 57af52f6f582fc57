"""Unreplicated single-copy register (reference
``examples/single-copy-register.rs``): each server exposes its own register
with no consensus.  One server is linearizable; two servers are not — the
checker finds the violating trace, demonstrating counterexample discovery
through the linearizability tester.

Pinned counts (reference ``single-copy-register.rs:100,121``): 93 unique
states @ 2 clients / 1 server; 20 @ 2 clients / 2 servers (violation found
early).
"""

from __future__ import annotations

from typing import Optional

from .. import Expectation
from ..actor import Actor, ActorModel, Id, Network, Out
from ..actor.register import (
    NULL_VALUE,
    GetOk,
    PutOk,
    RegisterClient,
    record_invocations,
    record_returns,
    value_chosen,
)
from ..parallel.tensor_model import TensorBackedModel
from ..semantics import LinearizabilityTester, Register
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


class SingleCopyServer(Actor):
    """State is just the stored value (reference
    ``single-copy-register.rs:16-37``)."""

    def on_start(self, id: Id, out: Out):
        return NULL_VALUE

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        kind = msg[0]
        if kind == "put":
            out.send(src, PutOk(msg[1]))
            return msg[2]
        if kind == "get":
            out.send(src, GetOk(msg[1], state))
            return state
        return None


class SingleCopyModel(TensorBackedModel, ActorModel):
    """ActorModel with a mechanically compiled device twin; single-copy
    server state is just the stored value, so no closure bounds are needed."""

    def tensor_model(self):
        from ..parallel.actor_compiler import CompileError, compile_actor_model

        try:
            return compile_actor_model(self)
        except (CompileError, ValueError):
            return None


def single_copy_model(
    client_count: int,
    server_count: int = 1,
    network: Optional[Network] = None,
    put_count: int = 1,
) -> ActorModel:
    if network is None:
        network = Network.new_unordered_nonduplicating()
    m = SingleCopyModel(
        cfg=None, init_history=LinearizabilityTester(Register(NULL_VALUE))
    )
    for _ in range(server_count):
        m.actor(SingleCopyServer())
    for _ in range(client_count):
        m.actor(RegisterClient(put_count=put_count, server_count=server_count))
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
    return [(f"single_copy_register clients={c}", single_copy_model(c))]


def main(argv=None):
    def check(rest):
        client_count = int(rest[0]) if rest else 2
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        print(f"Model checking a single-copy register with {client_count} clients.")
        single_copy_model(client_count, 1, network).checker().threads(
            default_threads()
        ).spawn_dfs().report()

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        client_count = int(rest[0]) if rest else 2
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        print(
            f"Model checking a single-copy register with {client_count} "
            "clients on the device wavefront engine."
        )
        m = apply_encoding(single_copy_model(client_count, 1, network), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check` (CPU)"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf), watch,
            lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        client_count = int(rest[0]) if rest else 2
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        print(
            f"Model checking a single-copy register with {client_count} "
            "clients (auto engine selection)."
        )
        single_copy_model(client_count, 1, network).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        client_count = int(rest[0]) if rest else 2
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        single_copy_model(client_count, 1).checker().serve(addr)

    def spawn_cmd(rest):
        from ..actor import spawn

        id = Id.from_addr("127.0.0.1", 3000)
        print(f"  Server listening on {id.to_addr()}")
        spawn([(id, SingleCopyServer())], background=False)

    run_cli(
        "  single_copy_register check [CLIENT_COUNT] [NETWORK]\n"
        "  single_copy_register check-tpu [CLIENT_COUNT] [NETWORK]\n"
        "  single_copy_register check-auto [CLIENT_COUNT] [NETWORK]\n"
        "  single_copy_register explore [CLIENT_COUNT] [ADDRESS]\n"
        "  single_copy_register spawn",
        check,
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

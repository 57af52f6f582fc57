"""Single-decree Paxos under linearizability checking
(reference ``examples/paxos.rs``).

Each server is simultaneously a potential leader (proposer) and an acceptor.
A client ``put`` triggers a new ballot: the leader broadcasts ``prepare``,
collects a majority of ``prepared`` replies (adopting the most recently
accepted proposal if any), broadcasts ``accept``, and on a majority of
``accepted`` declares the value decided, replying ``put_ok`` and broadcasting
``decided``.  Clients then ``get``; servers only answer once decided.

The model wires :class:`~stateright_tpu.actor.register.RegisterClient`
workloads and a :class:`~stateright_tpu.semantics.LinearizabilityTester`
history; the ``linearizable`` property runs the interleaving search per state.

Pinned count (reference ``examples/paxos.rs:291,311``): 16,668 unique states
@ 2 clients / 3 servers on an unordered non-duplicating network.
This workload is the driver's primary benchmark (``paxos check 3``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

from .. import Expectation
from ..actor import Actor, ActorModel, Id, Network, Out, majority, model_peers
from ..actor.register import (
    NULL_VALUE,
    GetOk,
    Internal,
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
    make_sweep_cmd,
    pop_checked,
    pop_perf,
    pop_supervise_opts,
    pop_watch,
    run_cli,
    run_supervised,
    spawn_watched,
)

def _ballot_zero() -> tuple:
    return (0, Id(0))


@dataclass(frozen=True)
class PaxosState:
    """Per-server state (reference ``paxos.rs:78-91``)."""

    ballot: tuple  # (round, leader id)
    # leader state
    proposal: Optional[tuple]  # (request id, requester id, value)
    prepares: tuple  # sorted ((acceptor id, last_accepted), ...)
    accepts: frozenset  # acceptor ids
    # acceptor state
    accepted: Optional[tuple]  # (ballot, proposal)
    is_decided: bool


def _accepted_key(last_accepted):
    """Total order on Option<(Ballot, Proposal)> matching the reference's
    ``max`` over ``prepares.values()`` (None is least)."""
    if last_accepted is None:
        return (0,)
    return (1, last_accepted)


@dataclass
class PaxosServer(Actor):
    """One Paxos server (reference ``paxos.rs:96-222``)."""

    peer_ids: list

    def on_start(self, id: Id, out: Out):
        return PaxosState(
            ballot=_ballot_zero(),
            proposal=None,
            prepares=(),
            accepts=frozenset(),
            accepted=None,
            is_decided=False,
        )

    def on_msg(self, id: Id, state: PaxosState, src: Id, msg, out: Out):
        kind = msg[0]
        if state.is_decided:
            if kind == "get":
                # A server that hasn't decided doesn't know whether a value
                # was decided elsewhere, so it never replies "no value"
                # (reference ``paxos.rs:117-129``).
                _ballot, proposal = state.accepted
                out.send(src, GetOk(msg[1], proposal[2]))
                return state  # reference registers a (possibly no-op) change
            return None

        if kind == "put" and state.proposal is None:
            req_id, value = msg[1], msg[2]
            ballot = (state.ballot[0] + 1, Id(id))
            out.broadcast(self.peer_ids, Internal(("prepare", ballot)))
            return replace(
                state,
                ballot=ballot,
                proposal=(req_id, Id(src), value),
                prepares=((Id(id), state.accepted),),  # self-send Prepared
                accepts=frozenset(),
            )

        if kind != "internal":
            return None
        imsg = msg[1]
        ikind = imsg[0]

        if ikind == "prepare":
            ballot = imsg[1]
            if state.ballot < ballot:
                out.send(src, Internal(("prepared", ballot, state.accepted)))
                return replace(state, ballot=ballot)
            return None

        if ikind == "prepared":
            ballot, last_accepted = imsg[1], imsg[2]
            if ballot != state.ballot:
                return None
            prepares = dict(state.prepares)
            prepares[Id(src)] = last_accepted
            new_prepares = tuple(sorted(prepares.items()))
            new_state = replace(state, prepares=new_prepares)
            quorum = majority(len(self.peer_ids) + 1)
            if len(new_prepares) == quorum:
                # leadership handoff: favor the most recently accepted
                # proposal from the prepare quorum (reference
                # ``paxos.rs:158-179``)
                best = max(
                    (la for _, la in new_prepares), key=_accepted_key
                )
                proposal = best[1] if best is not None else state.proposal
                out.broadcast(
                    self.peer_ids, Internal(("accept", ballot, proposal))
                )
                new_state = replace(
                    new_state,
                    proposal=proposal,
                    accepted=(ballot, proposal),  # self-send Accept
                    accepts=frozenset({Id(id)}),  # self-send Accepted
                )
            return new_state

        if ikind == "accept":
            ballot, proposal = imsg[1], imsg[2]
            if state.ballot <= ballot:
                out.send(src, Internal(("accepted", ballot)))
                return replace(
                    state, ballot=ballot, accepted=(ballot, proposal)
                )
            return None

        if ikind == "accepted":
            ballot = imsg[1]
            if ballot != state.ballot:
                return None
            accepts = state.accepts | {Id(src)}
            new_state = replace(state, accepts=accepts)
            quorum = majority(len(self.peer_ids) + 1)
            if len(accepts) == quorum:
                proposal = state.proposal
                out.broadcast(
                    self.peer_ids, Internal(("decided", ballot, proposal))
                )
                req_id, requester_id, _value = proposal
                out.send(requester_id, PutOk(req_id))
                new_state = replace(new_state, is_decided=True)
            return new_state

        if ikind == "decided":
            ballot, proposal = imsg[1], imsg[2]
            return replace(
                state,
                ballot=ballot,
                accepted=(ballot, proposal),
                is_decided=True,
            )

        return None


class PaxosModel(TensorBackedModel, ActorModel):
    """ActorModel specialization carrying a tensor (device) twin.

    The benchmark configuration — 3 servers, 1..7 clients doing one put
    each, unordered non-duplicating lossless network — uses the hand-tuned
    twin (``paxos_tensor.py``), which covers the reference's ``paxos check
    6`` bench config.  Other configurations (≠3 servers) fall back to the
    mechanical compiler (``parallel/actor_compiler.py``); configurations
    neither supports fall back to structural fingerprints and CPU checking.
    Eligibility is derived from the live builder state."""

    def sweep_family(self, n: int = 8):
        """Default hyper-batched sweep for the STATERIGHT_TPU_SWEEP env
        knob (docs/sweep.md): delegates to the module-level family."""
        return sweep_family(n)

    def tensor_model(self):
        from ..actor.network import UnorderedNonDuplicatingNetwork
        from .paxos_tensor import MAX_CLIENTS, PaxosTensor

        servers = sum(isinstance(a, PaxosServer) for a in self.actors)
        clients = self.actors[servers:]
        if (
            servers == 3
            and 1 <= len(clients) <= MAX_CLIENTS
            and all(
                isinstance(a, RegisterClient) and a.put_count == 1
                for a in clients
            )
            and not self.lossy
            and isinstance(self.init_network, UnorderedNonDuplicatingNetwork)
            # per-channel is a compiled-twin layout: the hand-tuned twin
            # packs its own slot multiset, so the builder flag OR the env
            # knob routes to the mechanical compiler (docs/analysis.md)
            and not self.per_channel_resolved()
        ):
            return PaxosTensor(self, len(clients))
        return self._compiled_tensor(len(clients))

    def _compiled_tensor(self, client_count: int):
        from ..actor.network import (
            OrderedNetwork,
            UnorderedNonDuplicatingNetwork,
        )
        from ..parallel.actor_compiler import CompileError, compile_actor_model

        if not isinstance(
            self.init_network,
            (UnorderedNonDuplicatingNetwork, OrderedNetwork),
        ):
            # the ballot bound below assumes at-most-once delivery; a
            # redelivered put starts extra ballots, exceeding C in real runs
            return None

        C = client_count

        def state_bound(i, s):
            # Each of the C puts starts exactly one new ballot, so ballot
            # rounds never exceed C in a real run; the bound only cuts the
            # closure's over-approximation (SURVEY §7.3: bounded domains).
            return not isinstance(s, PaxosState) or s.ballot[0] <= C

        def env_bound(env):
            m = env.msg
            if m[0] == "internal":
                return m[1][1][0] <= C
            return True

        try:
            return compile_actor_model(
                self, state_bound=state_bound, env_bound=env_bound
            )
        except (CompileError, ValueError):
            return None


def paxos_model(
    client_count: int, server_count: int = 3, network: Optional[Network] = None
) -> ActorModel:
    """Build the checked system (reference ``paxos.rs:231-266``)."""
    if network is None:
        network = Network.new_unordered_nonduplicating()
    m = PaxosModel(
        cfg=None,
        init_history=LinearizabilityTester(Register(NULL_VALUE)),
    )
    for i in range(server_count):
        m.actor(PaxosServer(peer_ids=model_peers(i, server_count)))
    for _ in range(client_count):
        m.actor(RegisterClient(put_count=1, server_count=server_count))
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


def paxos_lossy(client_count: int, server_count: int = 3) -> ActorModel:
    """``paxos_model`` on its default unordered non-duplicating network
    under ``LossyNetwork::Yes`` (reference ``ActorModel::lossy_network``,
    ``src/actor/model.rs:56-57``): upstream's ``paxos check N`` with a Drop
    action for every message in flight, from plain JSON arguments: a
    configuration file cannot call a builder verb.  Its device twin is the
    COMPILED one (``PaxosModel.tensor_model`` hands every lossy
    configuration to ``_compiled_tensor``)."""
    return paxos_model(client_count, server_count).lossy_network(True)


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    c = int(rest[0]) if rest else 2
    return [(f"paxos clients={c} servers=3", paxos_model(c, 3))]


def sweep_family(n: int = 8):
    """The paxos default sweep (docs/sweep.md; ``sweep`` verb +
    ``STATERIGHT_TPU_SWEEP``): ``n`` single-client instances alternating
    network lossiness — the non-lossy members run the hand-tuned twin,
    the lossy ones the compiled per-instance twin, so the sweep spans
    TWO shape cohorts (one engine compile each) and mixed table seeds
    widen the hash-fuzz net; every member must reconcile against its own
    sequential 482/265 (non-lossy) / lossy counts."""
    from ..sweep import SweepInstance, SweepSpec

    insts = []
    for i in range(max(1, int(n))):
        lossy = bool(i % 2)
        m = paxos_model(1, 3)
        if lossy:
            m.lossy_network(True)
        insts.append(SweepInstance(
            f"paxos1-{'lossy-' if lossy else ''}i{i}",
            m,
            params={"clients": 1, "lossy": lossy, "seed": i // 2},
            seed=i // 2,
        ))
    return SweepSpec(insts)


def main(argv=None):
    def check(rest):
        client_count = int(rest[0]) if rest else 2
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        print(f"Model checking Single Decree Paxos with {client_count} clients.")
        paxos_model(client_count, 3, network).checker().threads(
            default_threads()
        ).spawn_dfs().report()

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        client_count = int(rest[0]) if rest else 2
        target = int(rest[1]) if len(rest) > 1 else None
        print(
            f"Model checking Single Decree Paxos with {client_count} clients "
            "on the device wavefront engine"
            + (" (checked mode)." if checked else ".")
        )
        m = apply_encoding(paxos_model(client_count, 3), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check` (CPU)"
            )
        b = apply_perf(m.checker().checked(checked), perf)
        if target:
            b = b.target_states(target)
        spawn_watched(b, watch, lambda b: b.spawn_tpu()).report()

    def check_auto(rest):
        client_count = int(rest[0]) if rest else 2
        print(
            f"Model checking Single Decree Paxos with {client_count} "
            "clients (auto engine selection)."
        )
        paxos_model(client_count, 3).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        client_count = int(rest[0]) if rest else 2
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        print(f"Exploring Paxos state space with {client_count} clients on {addr}.")
        paxos_model(client_count, 3).checker().serve(addr)

    def supervise(rest):
        opts, rest = pop_supervise_opts(rest)
        client_count = int(rest[0]) if rest else 2
        print(
            f"Supervised Paxos check with {client_count} clients "
            "(autosave + retry/backoff; docs/robustness.md)."
        )
        run_supervised(paxos_model(client_count, 3).checker(), opts)

    def spawn_cmd(rest):
        from ..actor import spawn

        ids = [Id.from_addr("127.0.0.1", 3000 + i) for i in range(3)]
        print("  A set of servers that implement Single Decree Paxos.")
        print("  You can monitor and interact using tools such as nc or stateright-cli.")
        for id in ids:
            print(f"  Server listening on {id.to_addr()}")
        actors = [
            (
                id,
                PaxosServer(
                    peer_ids=[p for p in ids if p != id]
                ),
            )
            for id in ids
        ]
        spawn(actors, background=False)

    run_cli(
        "  paxos check [CLIENT_COUNT] [NETWORK]\n"
        "  paxos check-tpu [CLIENT_COUNT] [TARGET_STATES]\n"
        "  paxos check-auto [CLIENT_COUNT]\n"
        "  paxos explore [CLIENT_COUNT] [ADDRESS]\n"
        "  paxos sweep [N_INSTANCES]\n"
        "  paxos spawn",
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
        supervise=supervise,
        sweep=make_sweep_cmd(sweep_family),
        argv=argv,
    )


if __name__ == "__main__":
    main()

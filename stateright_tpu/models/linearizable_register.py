"""ABD linearizable quorum register (reference
``examples/linearizable-register.rs``), after "Sharing Memory Robustly in
Message-Passing Systems" by Attiya, Bar-Noy, and Dolev.

Each request runs two phases: a query phase establishing the latest
(sequencer, value) from a majority, then a record phase driving it (or the
new write, with a bumped sequencer) to a majority.  Sequencers are
``(logical clock, server id)`` pairs, so they are distinct across servers.

Pinned count (reference ``linearizable-register.rs:258,281``): 544 unique
states @ 2 clients / 2 servers on an unordered non-duplicating network.
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
    pop_checked,
    pop_perf,
    pop_watch,
    run_cli,
    spawn_watched,
)


def Query(req_id):
    return ("query", req_id)


def AckQuery(req_id, seq, value):
    return ("ack_query", req_id, seq, value)


def Record(req_id, seq, value):
    return ("record", req_id, seq, value)


def AckRecord(req_id):
    return ("ack_record", req_id)


@dataclass(frozen=True)
class AbdPhase1:
    request_id: int
    requester_id: Id
    write: Optional[str]  # value to write, None for reads
    responses: tuple  # sorted ((server id, (seq, value)), ...)


@dataclass(frozen=True)
class AbdPhase2:
    request_id: int
    requester_id: Id
    read: Optional[str]  # value read in phase 1, None for writes
    acks: frozenset  # server ids


@dataclass(frozen=True)
class AbdState:
    seq: tuple  # (logical clock, server id)
    val: str
    phase: Optional[object]  # AbdPhase1 | AbdPhase2 | None


@dataclass
class AbdServer(Actor):
    """One ABD replica (reference ``linearizable-register.rs:56-186``)."""

    peers: list

    def on_start(self, id: Id, out: Out):
        return AbdState(seq=(0, Id(id)), val=NULL_VALUE, phase=None)

    def _quorum(self) -> int:
        return majority(len(self.peers) + 1)

    def on_msg(self, id: Id, state: AbdState, src: Id, msg, out: Out):
        kind = msg[0]

        if kind in ("put", "get") and state.phase is None:
            req_id = msg[1]
            out.broadcast(self.peers, Internal(Query(req_id)))
            return replace(
                state,
                phase=AbdPhase1(
                    request_id=req_id,
                    requester_id=Id(src),
                    write=msg[2] if kind == "put" else None,
                    responses=((Id(id), (state.seq, state.val)),),
                ),
            )

        if kind != "internal":
            return None
        imsg = msg[1]
        ikind = imsg[0]

        if ikind == "query":
            out.send(src, Internal(AckQuery(imsg[1], state.seq, state.val)))
            return state

        if ikind == "ack_query":
            req_id, seq, val = imsg[1], imsg[2], imsg[3]
            ph = state.phase
            if not (isinstance(ph, AbdPhase1) and ph.request_id == req_id):
                return None
            responses = dict(ph.responses)
            responses[Id(src)] = (seq, val)
            resp_tuple = tuple(sorted(responses.items()))
            if len(resp_tuple) == self._quorum():
                # quorum: pick latest (sequencers are distinct), move to
                # phase 2 (reference ``linearizable-register.rs:107-147``)
                best_seq, best_val = max(
                    responses.values(), key=lambda sv: sv[0]
                )
                if ph.write is not None:
                    new_seq = (best_seq[0] + 1, Id(id))
                    new_val = ph.write
                    read = None
                else:
                    new_seq, new_val = best_seq, best_val
                    read = best_val
                out.broadcast(
                    self.peers, Internal(Record(req_id, new_seq, new_val))
                )
                # self-send Record
                seq2, val2 = state.seq, state.val
                if new_seq > state.seq:
                    seq2, val2 = new_seq, new_val
                return replace(
                    state,
                    seq=seq2,
                    val=val2,
                    phase=AbdPhase2(
                        request_id=req_id,
                        requester_id=ph.requester_id,
                        read=read,
                        acks=frozenset({Id(id)}),
                    ),
                )
            return replace(state, phase=replace(ph, responses=resp_tuple))

        if ikind == "record":
            req_id, seq, val = imsg[1], imsg[2], imsg[3]
            out.send(src, Internal(AckRecord(req_id)))
            if seq > state.seq:
                return replace(state, seq=seq, val=val)
            return state

        if ikind == "ack_record":
            req_id = imsg[1]
            ph = state.phase
            if not (
                isinstance(ph, AbdPhase2)
                and ph.request_id == req_id
                and Id(src) not in ph.acks
            ):
                return None
            acks = ph.acks | {Id(src)}
            if len(acks) == self._quorum():
                if ph.read is not None:
                    out.send(ph.requester_id, GetOk(req_id, ph.read))
                else:
                    out.send(ph.requester_id, PutOk(req_id))
                return replace(state, phase=None)
            return replace(state, phase=replace(ph, acks=acks))

        return None


class AbdModel(TensorBackedModel, ActorModel):
    """ActorModel with a mechanically compiled device twin
    (``parallel/actor_compiler.py``): eligible configurations (unordered
    non-duplicating or ordered network; any uniform ``put_count``) run on
    the TPU wavefront engine with no protocol-specific device code."""

    def tensor_model(self):
        from ..actor.network import (
            OrderedNetwork,
            UnorderedNonDuplicatingNetwork,
        )
        from ..parallel.actor_compiler import CompileError, compile_actor_model

        if not isinstance(
            self.init_network,
            (UnorderedNonDuplicatingNetwork, OrderedNetwork),
        ):
            # the state_bound below assumes each message is delivered at most
            # once; under a duplicating network a redelivered put restarts a
            # write round, the clock exceeds the write total in REAL runs
            # (the space is unbounded), and the bound would poison reachable
            # transitions
            return None

        # total write ops: each bumps the ABD logical clock at most once
        W = sum(
            a.put_count
            for a in self.actors
            if isinstance(a, RegisterClient)
        )

        def state_bound(i, s):
            # ABD sequencers are (logical clock, server id); each of the W
            # writes bumps the clock by at most one, so clock <= W in any
            # real run — the bound only cuts closure over-approximation.
            return not isinstance(s, AbdState) or s.seq[0] <= W

        def env_bound(env):
            m = env.msg
            if m[0] == "internal" and m[1][0] in ("ack_query", "record"):
                return m[1][2][0] <= W
            return True

        try:
            return compile_actor_model(
                self, state_bound=state_bound, env_bound=env_bound
            )
        except (CompileError, ValueError):
            return None


def abd_model(
    client_count: int,
    server_count: int = 2,
    network: Optional[Network] = None,
    put_count: int = 1,
) -> ActorModel:
    """Build the checked system (reference ``linearizable-register.rs:195-230``;
    ``put_count`` as in reference ``register.rs:96,178-186``)."""
    if network is None:
        network = Network.new_unordered_nonduplicating()
    m = AbdModel(
        cfg=None, init_history=LinearizabilityTester(Register(NULL_VALUE))
    )
    for i in range(server_count):
        m.actor(AbdServer(peers=model_peers(i, server_count)))
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


def abd_ordered(client_count: int, server_count: int = 2) -> ActorModel:
    """``abd_model`` on ordered per-pair FIFO links (reference ``bench.sh``'s
    ``check N ordered``), from plain JSON arguments: a configuration file
    cannot hold a ``Network`` object."""
    return abd_model(client_count, server_count, Network.new_ordered())


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    c = int(rest[0]) if rest else 2
    return [(f"linearizable_register clients={c} servers=2", abd_model(c, 2))]


def main(argv=None):
    def check(rest):
        client_count = int(rest[0]) if rest else 2
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        print(f"Model checking a linearizable register with {client_count} clients.")
        abd_model(client_count, 2, network).checker().threads(
            default_threads()
        ).spawn_bfs().report()

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
            f"Model checking a linearizable register with {client_count} "
            "clients on the device wavefront engine."
        )
        m = apply_encoding(abd_model(client_count, 2, network), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                f"the {network.name} network has no device twin here: "
                "redelivery makes ABD clocks unbounded (state_bound); use "
                "`check` (CPU) or a non-duplicating/ordered network"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf), watch,
            lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        client_count = int(rest[0]) if rest else 2
        print(
            f"Model checking a linearizable register with {client_count} "
            "clients (auto engine selection)."
        )
        abd_model(client_count, 2).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        client_count = int(rest[0]) if rest else 2
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        print(f"Exploring ABD state space with {client_count} clients on {addr}.")
        abd_model(client_count, 2).checker().serve(addr)

    def spawn_cmd(rest):
        from ..actor import spawn

        ids = [Id.from_addr("127.0.0.1", 3000 + i) for i in range(2)]
        for id in ids:
            print(f"  Server listening on {id.to_addr()}")
        spawn(
            [
                (id, AbdServer(peers=[p for p in ids if p != id]))
                for id in ids
            ],
            background=False,
        )

    run_cli(
        "  linearizable_register check [CLIENT_COUNT] [NETWORK]\n"
        "  linearizable_register check-tpu [CLIENT_COUNT] [NETWORK]\n"
        "  linearizable_register check-auto [CLIENT_COUNT]\n"
        "  linearizable_register explore [CLIENT_COUNT] [ADDRESS]\n"
        "  linearizable_register spawn",
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

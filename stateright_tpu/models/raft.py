"""Raft leader election, model-checked on host and device.

Beyond the reference's example set (it ships no Raft): this model
demonstrates the actor compiler's *general* fragment — timeout-driven
actors with no auxiliary history, checked against factored properties
(``actor/device_props.py``) — compiling mechanically to a TPU twin with
zero hand-written device code.

The protocol is the election core of Raft (Ongaro & Ousterhout §5.2):
followers time out and become candidates, candidates solicit votes for a
fresh term, a majority elects a leader.  Terms are bounded by
``max_term`` so the space is finite: a server whose election timer fires
at the cap simply stops campaigning (its timer clears and is never
re-armed — the reference's timeout semantics make that a real
transition, not a pruned no-op).

Checked properties:

 - **election safety** (always): at most one leader per term — the
   Figure 3 safety property, as a ``forall_actor_pairs`` predicate;
 - **liveness witness** (sometimes): some execution elects a leader.

CLI: ``python -m stateright_tpu.models.raft check [n] [network]``,
``check-tpu``, ``explore`` — like the reference's example binaries
(``examples/paxos.rs:314-395``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..actor import Actor, ActorModel, Id, Network, Out, majority, model_peers
from ..actor.device_props import exists_actor, forall_actor_pairs
from ..core import Expectation
from ..parallel.tensor_model import TensorBackedModel
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

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2


@dataclass(frozen=True)
class RaftState:
    role: int = FOLLOWER
    term: int = 0
    #: candidate Id this server voted for in `term` (-1: none).  Stored as
    #: Id (not int) so symmetry reduction rewrites them under actor
    #: permutations, on host and in the compiled twin's tables alike
    voted_for: int = -1
    #: granter Ids (candidates only); a frozenset rather than a bitmask so
    #: runtime sockaddr ids (~2^47) work as well as dense model ids
    votes: frozenset = frozenset()


class RaftServer(Actor):
    """Election-only Raft server.

    Messages: ``("req_vote", term)`` solicits, ``("grant", term)``
    grants.  A server votes at most once per term; a candidate counting a
    majority becomes leader and stops campaigning.
    """

    def __init__(
        self,
        peers: list[Id],
        cluster: int,
        max_term: int,
        timer_range=(0.0, 0.0),
    ):
        self.peers = peers
        self.cluster = cluster
        self.max_term = max_term
        # model checking ignores durations (any set timer may fire); a real
        # deployment passes Raft's randomized election timeout here
        self.timer_range = timer_range

    def on_start(self, id: Id, out: Out):
        out.set_timer(self.timer_range)  # election timer
        return RaftState()

    def on_timeout(self, id: Id, state: RaftState, out: Out):
        if state.role == LEADER or state.term >= self.max_term:
            return None  # stop campaigning (timer stays cleared)
        term = state.term + 1
        out.broadcast(self.peers, ("req_vote", term))
        out.set_timer(self.timer_range)  # elections may time out and retry
        return RaftState(
            role=CANDIDATE,
            term=term,
            voted_for=Id(id),
            votes=frozenset((Id(id),)),
        )

    def on_msg(self, id: Id, state: RaftState, src: Id, msg, out: Out):
        kind, term = msg
        if kind == "req_vote":
            if term > state.term:
                # newer term: step down and grant
                out.send(src, ("grant", term))
                return RaftState(term=term, voted_for=Id(src))
            if (
                term == state.term
                and state.role == FOLLOWER
                and state.voted_for in (-1, int(src))
            ):
                out.send(src, ("grant", term))
                if state.voted_for == int(src):
                    return None  # duplicate request, vote already recorded
                return RaftState(term=term, voted_for=Id(src))
            return None  # stale or already voted: ignore
        if kind == "grant":
            if state.role != CANDIDATE or term != state.term:
                return None  # stale grant
            if int(src) in state.votes:
                return None  # duplicate grant
            votes = state.votes | {Id(src)}
            role = (
                LEADER
                if len(votes) >= majority(self.cluster)
                else CANDIDATE
            )
            return RaftState(
                role=role,
                term=state.term,
                voted_for=state.voted_for,
                votes=votes,
            )
        return None


class RaftModel(TensorBackedModel, ActorModel):
    """ActorModel with a mechanically compiled device twin (general
    fragment: timers + factored properties, no history)."""

    max_term = 2

    def tensor_model(self):
        from ..parallel.actor_compiler import CompileError, compile_actor_model

        try:
            return compile_actor_model(
                self,
                # cut the closure's over-approximation at the term cap
                # (reachable states never cross it; poison pins that)
                state_bound=lambda i, s: s.term <= self.max_term,
                env_bound=lambda e: e.msg[1] <= self.max_term,
            )
        except (CompileError, ValueError):
            return None


def raft_model(
    server_count: int = 3,
    max_term: int = 2,
    network: Optional[Network] = None,
) -> ActorModel:
    """Election-safety model: ``server_count`` servers, terms bounded by
    ``max_term``."""
    if network is None:
        network = Network.new_unordered_nonduplicating()
    m = RaftModel(cfg=None, init_history=None)
    m.max_term = max_term
    for i in range(server_count):
        m.actor(
            RaftServer(
                peers=model_peers(i, server_count),
                cluster=server_count,
                max_term=max_term,
            )
        )
    m.init_network_(network)
    m.property(
        Expectation.ALWAYS,
        "election safety",
        forall_actor_pairs(
            lambda i, si, j, sj: not (
                si.role == LEADER and sj.role == LEADER and si.term == sj.term
            )
        ),
    )
    m.property(
        Expectation.SOMETIMES,
        "a leader is elected",
        exists_actor(lambda i, s: s.role == LEADER),
    )
    return m


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    n = int(rest[0]) if rest else 3
    return [(f"raft servers={n} max_term=2", raft_model(n))]


def main(argv=None) -> None:
    def parse(rest):
        n = int(rest[0]) if rest else 3
        network = (
            Network.from_name(rest[1])
            if len(rest) > 1
            else Network.new_unordered_nonduplicating()
        )
        return n, network

    def check(rest):
        n, network = parse(rest)
        print(f"Model checking Raft leader election with {n} servers.")
        raft_model(n, network=network).checker().threads(
            default_threads()
        ).spawn_bfs().report()

    def check_sym(rest):
        n, network = parse(rest)
        print(
            f"Model checking Raft leader election with {n} servers "
            "(symmetry-reduced DFS)."
        )
        raft_model(n, network=network).checker().symmetry().threads(
            default_threads()
        ).spawn_dfs().report()

    def check_sym_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        n, network = parse(rest)
        print(
            f"Model checking Raft leader election with {n} servers on the "
            "device wavefront engine (mechanical symmetry reduction)."
        )
        m = apply_encoding(raft_model(n, network=network), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check-sym`"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked).symmetry(), perf),
            watch, lambda b: b.spawn_tpu(),
        ).report()

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        n, network = parse(rest)
        print(
            f"Model checking Raft leader election with {n} servers on the "
            "device wavefront engine."
        )
        m = apply_encoding(raft_model(n, network=network), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check` (CPU)"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf), watch,
            lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        n, network = parse(rest)
        print(
            f"Model checking Raft leader election with {n} servers "
            "(auto engine selection)."
        )
        raft_model(n, network=network).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        n = int(rest[0]) if rest else 3
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        raft_model(n).checker().serve(addr)

    def spawn_cmd(rest):
        from ..actor.spawn import spawn

        n = int(rest[0]) if rest else 3
        base = int(rest[1]) if len(rest) > 1 else 3000
        ids = [Id.from_addr("127.0.0.1", base + i) for i in range(n)]
        print(f"Spawning a {n}-server Raft cluster on 127.0.0.1:"
              f"{base}..{base + n - 1} (ctrl-c to stop)")
        spawn(
            [
                (
                    ids[i],
                    RaftServer(
                        peers=[x for x in ids if x != ids[i]],
                        cluster=n,
                        max_term=1 << 20,
                        timer_range=(0.15, 0.5),
                    ),
                )
                for i in range(n)
            ],
            background=False,
        )

    run_cli(
        "raft [SERVER_COUNT] [NETWORK]",
        check,
        check_sym=check_sym,
        check_tpu=check_tpu,
        check_sym_tpu=check_sym_tpu,
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

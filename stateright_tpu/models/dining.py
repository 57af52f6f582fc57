"""Dining philosophers — deadlock detection on the device engines.

Beyond the reference's example set: the classic circular-wait deadlock,
found by the checker as an ``eventually``-property counterexample whose
trace ends in the deadlocked terminal state (every philosopher holding
their left fork, each waiting on the right).  Philosophers and forks are
plain Python actors; the general compiler fragment gives them a device
twin, so the deadlock hunt runs on the TPU wavefront engines too.

System: ``n`` philosophers (actors ``0..n-1``) and ``n`` forks (actors
``n..2n-1``).  Philosopher ``i`` uses forks ``n+i`` (left) and
``n+(i+1)%n`` (right), acquires left-then-right, eats once, releases
both.  Forks grant FIFO-free (lowest pending id first) — determinism the
checker needs, not fairness the protocol needs.

CLI: ``python -m stateright_tpu.models.dining check [n]``, ``check-tpu``,
``explore``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..actor import Actor, ActorModel, Id, Network, Out
from ..actor.device_props import exists_actor, forall_actors
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

HUNGRY, HAS_LEFT, DONE = 0, 1, 2


@dataclass(frozen=True)
class PhilosopherState:
    phase: int = HUNGRY


@dataclass(frozen=True)
class ForkState:
    #: Id of the current holder, or -1
    holder: int = -1
    #: Ids waiting for the fork
    pending: frozenset = frozenset()


class Philosopher(Actor):
    def __init__(self, left: Id, right: Id):
        self.left = left
        self.right = right

    def on_start(self, id: Id, out: Out):
        out.send(self.left, ("acquire",))
        return PhilosopherState(HUNGRY)

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        if msg[0] != "granted":
            return None
        if state.phase == HUNGRY:
            out.send(self.right, ("acquire",))
            return PhilosopherState(HAS_LEFT)
        if state.phase == HAS_LEFT:
            # both forks held: eat, then release both
            out.send(self.left, ("release",))
            out.send(self.right, ("release",))
            return PhilosopherState(DONE)
        return None


class Fork(Actor):
    def on_start(self, id: Id, out: Out):
        return ForkState()

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        if msg[0] == "acquire":
            if state.holder == -1:
                out.send(src, ("granted",))
                return ForkState(holder=Id(src), pending=state.pending)
            return ForkState(
                holder=state.holder, pending=state.pending | {Id(src)}
            )
        if msg[0] == "release":
            if state.pending:
                nxt = Id(min(state.pending))
                out.send(nxt, ("granted",))
                return ForkState(
                    holder=nxt, pending=state.pending - {nxt}
                )
            return ForkState()
        return None


def dining_model(n: int = 3, network: Optional[Network] = None) -> ActorModel:
    """``n`` philosophers, ``n`` forks; the famous deadlock is reachable
    (and discovered) for every ``n >= 2``."""
    if network is None:
        network = Network.new_unordered_nonduplicating()

    class DiningModel(TensorBackedModel, ActorModel):
        def tensor_model(self):
            from ..parallel.actor_compiler import (
                CompileError,
                compile_actor_model,
            )

            try:
                return compile_actor_model(self)
            except (CompileError, ValueError):
                return None

    m = DiningModel(cfg=None, init_history=None)
    for i in range(n):
        m.actor(Philosopher(left=Id(n + i), right=Id(n + (i + 1) % n)))
    for _ in range(n):
        m.actor(Fork())
    m.init_network_(network)
    phil = lambda i: i < n  # noqa: E731 - actors 0..n-1 are philosophers
    m.property(
        Expectation.EVENTUALLY,
        "everyone eats",
        forall_actors(lambda i, s: not phil(i) or s.phase == DONE),
    )
    m.property(
        Expectation.SOMETIMES,
        "someone eats",
        exists_actor(lambda i, s: phil(i) and s.phase == DONE),
    )
    return m


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    n = int(rest[0]) if rest else 3
    return [(f"dining n={n}", dining_model(n))]


def main(argv=None) -> None:
    def parse(rest):
        return int(rest[0]) if rest else 3

    def check(rest):
        n = parse(rest)
        print(f"Model checking {n} dining philosophers.")
        c = (
            dining_model(n)
            .checker()
            .threads(default_threads())
            .spawn_bfs()
            .report()
        )
        trace = c.discovery("everyone eats")
        if trace is not None:
            print(f"deadlock after {len(trace.actions())} steps:")
            print(trace)

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        n = parse(rest)
        print(
            f"Model checking {n} dining philosophers on the device "
            "wavefront engine."
        )
        m = apply_encoding(dining_model(n), perf)
        if m.tensor_model() is None:
            raise SystemExit(
                "this configuration has no device twin; use `check` (CPU)"
            )
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf), watch,
            lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        n = parse(rest)
        print(f"Model checking {n} dining philosophers (auto engine).")
        dining_model(n).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        n = parse(rest)
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        dining_model(n).checker().serve(addr)

    run_cli(
        "dining [PHILOSOPHER_COUNT]",
        check,
        check_tpu=check_tpu,
        check_auto=check_auto,
        explore=explore,
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

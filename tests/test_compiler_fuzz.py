"""Compiler fuzz: random bounded actor systems, host=device across engines.

The fixed examples (2pc, paxos, raft, registers) pin exact counts but
share a handful of structural shapes.  This fuzzer generates seeded
random actor systems inside the general compiled fragment — random
per-actor monotone FSMs exchanging messages from a small alphabet, with
factored properties — and requires, for every seed:

 - the mechanical compiler accepts the system (its closure terminates:
   actor states only advance, so total sends are bounded);
 - per-state equivalence over the FULL space (encode/decode round-trip,
   fingerprint agreement, successor-set equality, property-mask
   agreement) via the same crawl used for the examples;
 - unique-count and discovery parity across spawn_bfs / spawn_dfs /
   spawn_mp_bfs / spawn_tpu / the 8-device mesh engine.

Seeds are fixed, so failures reproduce exactly.
"""

import random

import pytest

from stateright_tpu.actor import Actor, ActorModel, Id, Network, Out
from stateright_tpu.actor.device_props import exists_actor, forall_actors
from stateright_tpu.core import Expectation
from stateright_tpu.parallel.actor_compiler import compile_actor_model
from stateright_tpu.parallel.tensor_model import TensorBackedModel

from test_paxos_tensor import crawl_and_check

N_STATES = 4  # per-actor FSM size; states only advance -> bounded space
ALPHABET = 3  # message kinds


class FuzzActor(Actor):
    """Monotone random FSM: on a delivery, either ignore it or advance
    one state and (maybe) send one random message to a random peer.  The
    tables are drawn once from the seed, so the actor is deterministic."""

    def __init__(self, rng: random.Random, me: int, n_actors: int):
        self.me = me
        # start[k]: message kind sent at boot to a random peer (or None)
        self.boot = None
        if rng.random() < 0.8:
            self.boot = (rng.randrange(n_actors), rng.randrange(ALPHABET))
        # advance[state][kind] -> None (ignore) | (dst, kind) | (None,)
        self.table = {}
        for s in range(N_STATES - 1):
            for k in range(ALPHABET):
                roll = rng.random()
                if roll < 0.35:
                    self.table[s, k] = None  # ignore: no-op transition
                elif roll < 0.75:
                    self.table[s, k] = (
                        rng.randrange(n_actors), rng.randrange(ALPHABET)
                    )
                else:
                    self.table[s, k] = (None,)  # advance silently

    def on_start(self, id: Id, out: Out):
        if self.boot is not None:
            dst, kind = self.boot
            if dst != self.me:
                out.send(Id(dst), ("m", kind))
        return 0

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        if state >= N_STATES - 1:
            return None
        eff = self.table[state, msg[1]]
        if eff is None:
            return None
        if len(eff) == 2:
            dst, kind = eff
            if dst != self.me:
                out.send(Id(dst), ("m", kind))
        return state + 1


class FuzzModel(TensorBackedModel, ActorModel):
    def tensor_model(self):
        return compile_actor_model(self)


def _fuzz_model(
    seed: int, n_actors: int, network, actor_cls=FuzzActor
) -> FuzzModel:
    rng = random.Random(seed)
    m = FuzzModel(None, None)
    for i in range(n_actors):
        m.actor(actor_cls(rng, i, n_actors))
    m.init_network_(network)
    m.property(
        Expectation.SOMETIMES,
        "someone finishes",
        exists_actor(lambda i, s: s == N_STATES - 1),
    )
    # never-violated ALWAYS: forces full exploration so engine counts
    # compare at the complete space, not at early-exit granularity
    m.property(
        Expectation.ALWAYS,
        "states in range",
        forall_actors(lambda i, s: 0 <= s < N_STATES),
    )
    return m


NETWORKS = {
    "nondup": Network.new_unordered_nonduplicating,
    "dup": Network.new_unordered_duplicating,
    "ordered": Network.new_ordered,
}


class FuzzTimerActor(FuzzActor):
    """FuzzActor plus a timer axis: boot may arm the timer; a timeout at
    a non-final state may advance (and maybe send) and maybe re-arm —
    still monotone, so still bounded."""

    def __init__(self, rng: random.Random, me: int, n_actors: int):
        super().__init__(rng, me, n_actors)
        self.boot_timer = rng.random() < 0.7
        # ttable[state] -> None (clear only) | (advance?, send | None, rearm?)
        self.ttable = {}
        for s in range(N_STATES - 1):
            if rng.random() < 0.3:
                self.ttable[s] = None
            else:
                send = None
                if rng.random() < 0.5:
                    send = (rng.randrange(n_actors), rng.randrange(ALPHABET))
                advance = rng.random() < 0.8
                # re-arming must imply advancing: a timeout that re-arms
                # without changing state fires forever, adding one more
                # envelope copy per firing — an infinite space
                self.ttable[s] = (
                    advance, send, advance and rng.random() < 0.6
                )

    def on_start(self, id: Id, out: Out):
        state = super().on_start(id, out)
        if self.boot_timer:
            out.set_timer((1.0, 2.0))
        return state

    def on_timeout(self, id: Id, state, out: Out):
        eff = self.ttable.get(state)
        if eff is None:
            return None  # the timeout still clears the timer bit
        advance, send, rearm = eff
        if send is not None and send[0] != self.me:
            out.send(Id(send[0]), ("m", send[1]))
        if rearm and state < N_STATES - 2:
            out.set_timer((1.0, 2.0))
        return state + 1 if advance and state < N_STATES - 1 else None


def _assert_engine_parity(m, seed, net):
    tm = m.tensor_model()
    seen = crawl_and_check(m, tm)  # full-space per-state equivalence
    h = m.checker().spawn_bfs().join()
    assert h.unique_state_count() == len(seen)
    for build in (
        lambda: m.checker().spawn_dfs().join(),
        lambda: m.checker().spawn_mp_bfs(processes=2).join(),
        lambda: m.checker().spawn_tpu(sync=True, capacity=1 << 12),
        lambda: m.checker().spawn_tpu(
            sync=True, devices=8, capacity=1 << 12,
            frontier_capacity=1 << 7,
        ),
    ):
        c = build()
        assert c.unique_state_count() == h.unique_state_count(), (seed, net)
        assert sorted(c.discoveries()) == sorted(h.discoveries()), (seed, net)


# fast tier runs two seeds (0 = a typical chatty system; 4 = the empty
# envelope universe that crashed device gathers); the rest join the daily
# medium tier per the repo's tiering convention
_FAST_SEEDS = (0, 4)
_SEEDS = [
    s if s in _FAST_SEEDS else pytest.param(s, marks=pytest.mark.medium)
    for s in range(6)
]


@pytest.mark.parametrize("seed", _SEEDS)
@pytest.mark.parametrize("net", sorted(NETWORKS))
# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_fuzzed_system_host_equals_device(seed, net):
    m = _fuzz_model(seed, n_actors=2 + seed % 2, network=NETWORKS[net]())
    _assert_engine_parity(m, seed, net)


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
@pytest.mark.parametrize("seed", _SEEDS)
def test_fuzzed_timer_system_host_equals_device(seed):
    """The timer axis of the general fragment under fuzz: boot-armed
    timers, timeout-driven advances/sends, re-arming — every engine
    agrees with the host on the full space."""
    m = _fuzz_model(
        1000 + seed,
        n_actors=2 + seed % 2,
        network=Network.new_unordered_nonduplicating(),
        actor_cls=FuzzTimerActor,
    )
    _assert_engine_parity(m, seed, "timer")


@pytest.mark.parametrize("seed", _SEEDS)
# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_fuzzed_lossy_system_host_equals_device(seed):
    """Drop actions under fuzz: a lossy duplicating network adds a Drop
    per deliverable envelope; engines must agree on the enlarged space."""
    m = _fuzz_model(
        seed, n_actors=2, network=Network.new_unordered_duplicating()
    )
    m.lossy_network(True)
    _assert_engine_parity(m, seed, "lossy-dup")

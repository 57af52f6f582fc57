"""The unreplicated single-copy register on the default UNORDERED network,
through the COMPILED actor twin on the device engine, against the plain
reference (a BFS over the host object model: the actors' own ``on_msg``,
the multiset network, ``LinearizabilityTester``;
``benchmarks/srbench/reference.py``) — and the device's linearizability
verdict against the object tester's ``is_consistent()``, state by state.

The configurations are the small siblings of the benchmark's
``singlecopy4`` (``single_copy_model(4, 1)``, ``bench.sh:29``'s
``single-copy-register check 4``: 400,233 unique, pinned in its
configuration file, checked on the chip) and the two-server variants
whose violation is the example's purpose upstream.
"""

import contextlib
import hashlib
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.models.single_copy_register import single_copy_model
from stateright_tpu.telemetry import spans

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks"))

from srbench.reference import reference_bfs, successors  # noqa: E402


def _replays_on_the_host_model(model, path) -> None:
    """The device's parent chain, replayed on the host model step by step."""
    states, actions = path.states(), path.actions()
    assert states[0] in model.init_states()
    for before, action, after in zip(states, actions, states[1:]):
        assert model.next_state(before, action) == after


@pytest.fixture(scope="module")
def three_clients():
    """``single_copy_model(3, 1)`` searched by the plain reference, every
    state kept."""
    model = single_copy_model(3, 1)
    kept: list = []
    return model, reference_bfs(model, kept=kept), kept


def test_compiled_twin_on_the_engine_equals_the_plain_reference(three_clients):
    model, want, kept = three_clients
    assert (want["unique"], want["generated"], want["max_depth"]) == (4243, 6778, 12)
    checker = model.checker().spawn_tpu(sync=True, capacity=1 << 15, batch=256)
    checker.join()  # a poisoned row (a compile-time bound crossed) raises here
    assert checker.unique_state_count() == want["unique"]
    assert checker.state_count() == want["generated"]
    assert checker.max_depth() == want["max_depth"]
    found = checker.discoveries()
    assert sorted(found) == want["discoveries"] == ["value chosen"]
    path = checker.discovery("value chosen")
    assert model.property_by_name("value chosen").condition(model, path.last_state())
    _replays_on_the_host_model(model, path)
    # at most one message a client is ever in flight: the other slots of
    # the twin's 16 (n_slots = max(16, 4 * n_actors)) are always empty
    assert max(len(s.network) for s in kept) == 3
    assert model.tensor_model().n_slots == 16


@pytest.mark.parametrize("clients, unique, generated", [(3, 79, 109), (4, 193, 293)])
def test_two_servers_are_not_linearizable_and_the_path_replays(
        clients, unique, generated):
    """The example's purpose upstream: two unreplicated servers.  Both
    properties are discovered (the search stops there, the reference at
    ``unique`` / ``generated`` states), and the ``linearizable`` path ends
    in a state whose object tester finds no serialization."""
    model = single_copy_model(clients, 2)
    want = reference_bfs(model)
    assert (want["unique"], want["generated"], want["max_depth"]) == (unique, generated, 4)
    assert want["discoveries"] == ["linearizable", "value chosen"]
    checker = model.checker().spawn_tpu(sync=True, capacity=1 << 13, batch=256)
    checker.join()
    assert sorted(checker.discoveries()) == want["discoveries"]
    path = checker.discovery("linearizable")
    _replays_on_the_host_model(model, path)
    assert not path.last_state().history.is_consistent()
    assert len(path.states()) - 1 <= want["max_depth"]
    chosen = checker.discovery("value chosen")
    _replays_on_the_host_model(model, chosen)
    assert model.property_by_name("value chosen").condition(model, chosen.last_state())


# -- the device verdict against the object tester, state by state ---------------


def _device_linearizable(model, states) -> np.ndarray:
    twin = model.tensor_model()
    rows = np.asarray([twin.encode_state(s) for s in states], dtype=np.uint64)
    names = [p.name for p in model.properties()]
    masks = jax.jit(twin.property_masks)(jnp.asarray(rows))
    return np.asarray(masks)[:, names.index("linearizable")]


def _whole_space(model) -> list:
    """Every reachable state (no property stops this search)."""
    seen = set(model.init_states())
    frontier = list(seen)
    while frontier:
        nxt = []
        for s in frontier:
            for n in successors(model, s):
                if n not in seen:
                    seen.add(n)
                    nxt.append(n)
        frontier = nxt
    return list(seen)


def test_device_verdict_is_the_object_testers_on_every_state_one_server(three_clients):
    model, _, kept = three_clients
    want = np.asarray([s.history.is_consistent() for s in kept])
    assert want.all() and len(kept) == 4243  # one server: linearizable everywhere
    assert (_device_linearizable(model, kept) == want).all()


def test_device_verdict_is_the_object_testers_on_every_state_two_servers():
    model = single_copy_model(3, 2)
    states = _whole_space(model)
    want = np.asarray([s.history.is_consistent() for s in states])
    assert len(states) == 2519 and int((~want).sum()) == 1271
    assert (_device_linearizable(model, states) == want).all()


@pytest.mark.parametrize("servers, seed", [(1, 2147483659), (2, 2147483693)])
def test_device_verdict_at_four_threads_on_a_seeded_sample(servers, seed):
    """C = 4 is where the benchmark's cell runs (and the table strategy's
    last size): >= 500 distinct states drawn by seeded random walks, each
    encoded to a row by the twin's ``encode_state``."""
    model = single_copy_model(4, servers)
    rng = random.Random(seed)
    (init,) = model.init_states()
    states: dict = {}
    while len(states) < 600:
        s = init
        for _ in range(24):
            nxt = successors(model, s)
            if not nxt:
                break
            s = rng.choice(nxt)
            states[s] = None
    states = list(states)
    want = np.asarray([s.history.is_consistent() for s in states])
    if servers == 1:
        assert want.all()
    else:  # both verdicts are in the sample
        assert 100 < int((~want).sum()) < len(states) - 100
    assert (_device_linearizable(model, states) == want).all()
    assert model.tensor_model().hist.C == 4


# -- what the twin says of its history, and the scope around the verdict ---------


@pytest.mark.parametrize("clients", [1, 2, 3, 4])
def test_compile_attrs_name_the_history_codec(clients):
    twin = single_copy_model(clients, 1).tensor_model()
    attrs = twin.compile_attrs()
    assert attrs["hist_strategy"] == "closure" == twin.hist.strategy
    assert attrs["hist_threads"] == clients
    # per thread: phase 2 + a 2-bit snapshot of each other thread + rval 3
    assert attrs["hist_bits"] == clients * (2 + 2 * (clients - 1) + 3)
    assert attrs["n_slots"] == max(16, 4 * (clients + 1))


def test_a_model_without_a_history_says_none():
    from stateright_tpu.models.raft import raft_model

    attrs = raft_model(2).tensor_model().compile_attrs()
    assert (attrs["hist_strategy"], attrs["hist_threads"], attrs["hist_bits"]) == (
        "none", 0, 0)


def _props_text(twin) -> str:
    rows = jnp.asarray(np.asarray(twin.init_rows(), dtype=np.uint64))
    return jax.jit(twin.property_masks).lower(rows).as_text(debug_info=True)


def test_the_lowered_props_kernel_carries_the_scope():
    assert not spans.PROPS_LIN.startswith("sr.")  # never a stage of its own
    assert f"{spans.PROPS_LIN}/" in _props_text(single_copy_model(2, 1).tensor_model())
    from stateright_tpu.models.paxos import paxos_model

    assert f"{spans.PROPS_LIN}/" in _props_text(paxos_model(1).tensor_model())


def _without_props_lin(monkeypatch) -> None:
    named = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name == spans.PROPS_LIN else named(name))


def _step_program(model, **kw):
    """(sha of the lowered run program without debug info, its text with)."""
    c = model.checker().spawn_tpu(sync=True, **kw)
    c.join()
    init_fn, run_fn = c._build(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    low = run_fn.lower(carry)
    return (hashlib.sha256(low.as_text().encode()).hexdigest(),
            low.as_text(debug_info=True))


@pytest.mark.parametrize("which", ["twopc3", "abd2x2o"])
def test_the_scope_is_metadata_the_step_program_hashes_equal_without_it(
        which, monkeypatch):
    from stateright_tpu.models.linearizable_register import abd_ordered
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    make, kw = {
        "twopc3": (lambda: TwoPhaseSys(3), dict(capacity=1 << 12, batch=64)),
        "abd2x2o": (lambda: abd_ordered(2, 2), dict(capacity=1 << 13, batch=256)),
    }[which]
    sha, text = _step_program(make(), **kw)
    lin = f"/{spans.STAGE_PROPS}/{spans.PROPS_LIN}/"
    # a twin with a history names the verdict inside sr.props; 2pc has none
    assert (lin in text) == (which == "abd2x2o")
    _without_props_lin(monkeypatch)
    bare_sha, bare_text = _step_program(make(), **kw)
    assert spans.PROPS_LIN not in bare_text
    assert bare_sha == sha

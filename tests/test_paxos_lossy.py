"""Single-decree Paxos under ``LossyNetwork::Yes`` (``paxos_lossy``: the
default unordered non-duplicating network plus a Drop action for every
message in flight), through the COMPILED actor twin on the device engine,
against the plain reference (a BFS over the host object model, whose
``model.actions`` enumerates the Drops itself;
``benchmarks/srbench/reference.py``) and against the host ``spawn_bfs`` —
and the twin's Deliver and Drop columns against the host model's Deliver
and Drop actions, state by state.

The configuration is the small sibling of the benchmark's ``paxos2lossy``
(``paxos_lossy(2, 3)``: 954,508 unique / 5,060,177 generated, pinned in its
configuration file, checked on the chip).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.actor.model import Deliver, Drop
from stateright_tpu.fingerprint import hash_words
from stateright_tpu.models.paxos import paxos_lossy, paxos_model
from stateright_tpu.telemetry import spans

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks"))

from srbench.reference import reference_bfs  # noqa: E402

PINS = {"unique": 2378, "generated": 8197, "max_depth": 14,
        "discoveries": ["value chosen"]}


@pytest.fixture(scope="module")
def one_client():
    """``paxos_lossy(1)`` searched by the plain reference, every state kept."""
    model = paxos_lossy(1)
    kept: list = []
    return model, reference_bfs(model, kept=kept), kept


def test_the_factory_is_paxos_model_on_its_default_network_made_lossy():
    model = paxos_lossy(2)
    assert model.lossy and len(model.actors) == 5
    assert model.init_network.name == "unordered_nonduplicating"
    assert [p.name for p in model.properties()] == ["linearizable", "value chosen"]
    assert not paxos_model(2).lossy
    assert len(paxos_lossy(1, 5).actors) == 6


def test_the_plain_reference_counts_every_drop(one_client):
    model, got, kept = one_client
    assert got == PINS  # ``linearizable`` has no counterexample: absent
    assert len(kept) == PINS["unique"]
    # the lossless space is 265 states: the rest exist only because a
    # message was lost
    assert paxos_model(1).checker().spawn_bfs().join().unique_state_count() == 265


def test_the_host_bfs_agrees_with_the_plain_reference():
    checker = paxos_lossy(1).checker().spawn_bfs().join()
    assert checker.unique_state_count() == PINS["unique"]
    assert checker.state_count() == PINS["generated"]
    assert sorted(checker.discoveries()) == PINS["discoveries"]


@pytest.fixture(scope="module")
def device_check(one_client):
    model = one_client[0]
    checker = model.checker().spawn_tpu(sync=True, capacity=1 << 15, batch=256)
    checker.join()  # a poisoned row (a compile-time bound crossed) raises here
    return model, checker


def test_the_compiled_twin_on_the_engine_equals_the_plain_reference(device_check):
    _, checker = device_check
    assert checker.unique_state_count() == PINS["unique"]
    assert checker.state_count() == PINS["generated"]
    assert checker.max_depth() == PINS["max_depth"]
    assert sorted(checker.discoveries()) == PINS["discoveries"]
    assert "linearizable" not in checker.discoveries()


def test_the_discovery_path_replays_on_the_host_model(device_check):
    model, checker = device_check
    path = checker.discovery("value chosen")
    states, actions = path.states(), path.actions()
    assert states[0] in model.init_states()
    for before, action, after in zip(states, actions, states[1:]):
        assert model.next_state(before, action) == after
    assert model.property_by_name("value chosen").condition(model, path.last_state())
    assert len(actions) <= PINS["max_depth"]


@pytest.fixture(scope="module")
def successors_of_every_state(one_client):
    """For every state of the space: the twin's valid successor rows'
    fingerprints by action column, and the kept states themselves."""
    model, _, kept = one_client
    twin = model.tensor_model()
    rows = np.asarray([twin.encode_state(s) for s in kept], dtype=np.uint64)
    succ, valid = jax.jit(twin.step_rows)(jnp.asarray(rows))
    return model, twin, kept, np.asarray(succ), np.asarray(valid)


@pytest.mark.parametrize("kind, action_type", [("deliver", Deliver), ("drop", Drop)])
def test_the_twins_columns_are_the_host_models_actions_state_by_state(
        successors_of_every_state, kind, action_type):
    """The SET of valid successor fingerprints in the twin's 16 Deliver
    columns equals the host model's Deliver successors, and the 16 Drop
    columns' equals its Drop successors, on every one of the 2,378 states: a
    Drop kernel that loses or invents a successor fails here by name."""
    model, twin, kept, succ, valid = successors_of_every_state
    ns = twin.n_slots
    assert (ns, twin.max_actions) == (16, 32)
    cols = range(0, ns) if kind == "deliver" else range(ns, 2 * ns)
    total = 0
    for i, state in enumerate(kept):
        want = set()
        for action in model.actions(state):
            if isinstance(action, action_type):
                nxt = model.next_state(state, action)
                if nxt is not None:
                    want.add(model.fingerprint_state(nxt))
        got = {hash_words(int(w) for w in succ[i, a]) for a in cols if valid[i, a]}
        assert got == want, (kind, i, state)
        total += sum(bool(valid[i, a]) for a in cols)
    assert total > 0
    if kind == "drop":
        # a Drop a message in flight, on every state: what the pinned
        # ``generated`` counts beside the deliveries
        assert total == sum(len(s.network) for s in kept)
        deliveries = int(valid[:, :ns].sum())
        assert 1 + deliveries + total == PINS["generated"]


def test_compile_attrs_say_lossy_and_count_the_drop_columns():
    attrs = paxos_lossy(2).tensor_model().compile_attrs()
    assert attrs["lossy"] is True
    assert (attrs["max_actions"], attrs["n_slots"], attrs["row_width"]) == (40, 20, 21)
    assert (attrs["actor_states"], attrs["envelopes"]) == ("1194,1153,28,3,3", 82)
    assert attrs["table_bytes"] == 3320508
    assert (attrs["hist_strategy"], attrs["hist_threads"], attrs["hist_bits"]) == (
        "closure", 2, 14)


def test_a_lossless_twin_says_so():
    attrs = paxos_model(2, 3).lossy_network(False)._compiled_tensor(2).compile_attrs()
    assert attrs["lossy"] is False
    assert (attrs["max_actions"], attrs["n_slots"]) == (20, 20)


def test_the_three_client_closure_is_still_refused():
    """ROADMAP Queue 2 A4: the next paxos size under loss has no device
    twin (the closure estimate refuses the compile), so a check of it stays
    on the host engines."""
    assert paxos_lossy(3).tensor_model() is None


# -- the Drop block's scope (tests/test_stage_tracing.py's idiom) -----------------


def _lowered_step(twin) -> str:
    rows = jnp.asarray(np.asarray(twin.init_rows(), dtype=np.uint64))
    return jax.jit(twin.step_rows).lower(rows).as_text(debug_info=True)


def test_twin_drop_is_a_scope_of_its_own_and_no_member_of_twin_scopes():
    assert spans.TWIN_DROP == "twin.drop"
    assert spans.TWIN_DROP not in spans.TWIN_SCOPES  # only a lossy twin opens it
    assert not spans.TWIN_DROP.startswith("sr.")  # a part, never a stage


def test_a_lossy_twins_run_program_names_its_drop_block_inside_expand(device_check):
    _, c = device_check
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    text = run_fn.lower(carry).as_text(debug_info=True)
    assert f"/{spans.STAGE_EXPAND}/{spans.TWIN_DROP}/" in text
    # the canonicalising sort the Drop block calls is charged to the Drop
    # block: ``twin.drop`` comes first on its scope path
    assert f"/{spans.TWIN_DROP}/{spans.TWIN_NET}/" in text
    assert f"/{spans.TWIN_NET}/{spans.TWIN_DROP}/" not in text
    for scope in spans.TWIN_SCOPES:  # and the Deliver block keeps its three
        assert f"/{spans.STAGE_EXPAND}/{scope}/" in text, scope


def test_a_lossless_twins_step_holds_no_drop_scope():
    twin = paxos_model(1, 3)._compiled_tensor(1)
    text = _lowered_step(twin)
    assert spans.TWIN_DROP not in text
    assert f"/{spans.TWIN_NET}/" in text and f"/{spans.TWIN_TABLE}/" in text


def test_the_per_channel_layouts_drop_block_opens_the_same_scope():
    from stateright_tpu.models.single_copy_register import single_copy_model

    lossy = single_copy_model(1, 1).lossy_network(True).per_channel_()
    twin = lossy.tensor_model()
    assert twin.per_channel and twin.compile_attrs()["lossy"] is True
    assert f"/{spans.TWIN_DROP}/" in _lowered_step(twin)
    plain = single_copy_model(1, 1).per_channel_().tensor_model()
    assert plain.per_channel and spans.TWIN_DROP not in _lowered_step(plain)

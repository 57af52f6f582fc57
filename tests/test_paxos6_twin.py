"""The hand paxos twin at C = 6 (``bench.sh``'s own ``paxos check 6``: 64 x
u64 rows, 60 action columns) against the host object model, past the two
levels the slow tier holds it to - and the loud network-slot overflow that
a run of that size leans on: no host search reaches the whole paxos-6
space, so a send that found no free slot must END the run (status
``poison``), never shorten it quietly.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.paxos_tensor import PaxosTensor
from stateright_tpu.telemetry import spans

WALKS = 64
LANES = 256  # rows a call of the jitted twin: one compile each


def reference_level_sizes(model, max_level: int) -> list:
    """Sizes of the BFS levels 0..max_level of the host object model: a
    ``set`` of the state objects, no twin, no fingerprint."""
    seen = set(model.init_states())
    frontier = list(seen)
    sizes = [len(frontier)]
    for _ in range(max_level):
        nxt = []
        for s in frontier:
            for t in model.next_states(s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
        sizes.append(len(frontier))
    return sizes


def walk_states(model, seed: int, walks: int) -> dict:
    """Every distinct state on ``walks`` seeded random walks of the HOST
    model, each run to its terminal state, by fingerprint."""
    rng = random.Random(seed)
    inits = list(model.init_states())
    seen = {}
    for _ in range(walks):
        s = rng.choice(inits)
        while True:
            seen.setdefault(model.fingerprint_state(s), s)
            nxt = list(model.next_states(s))
            if not nxt:
                break
            s = rng.choice(nxt)
    return seen


@pytest.fixture(scope="module")
def paxos6():
    m = paxos_model(6, 3)
    return m, m.tensor_model()


def test_paxos6_is_the_hand_twin_at_64_words_and_60_actions(paxos6):
    _, tm = paxos6
    assert isinstance(tm, PaxosTensor)
    assert (tm.width, tm.max_actions, tm.n_slots) == (64, 60, 60)


def test_paxos6_twin_is_the_host_model_on_every_state_of_seeded_walks(paxos6):
    """Successor sets and both property masks, twin against host model, on
    every distinct state of 64 walks to their terminal states (depth ~20:
    all six puts decided and read back), not just the first two levels."""
    m, tm = paxos6
    states = list(walk_states(m, 46, WALKS).values())
    assert len(states) > 500
    assert max(len(s.network._counts) for s in states) <= tm.n_slots
    step = jax.jit(tm.step_rows)
    masks_of = jax.jit(tm.property_masks)
    props = list(m.properties())
    deepest_actions = 0
    for at in range(0, len(states), LANES):
        chunk = states[at:at + LANES]
        rows = np.asarray([tm.encode_state(s) for s in chunk], np.uint64)
        pad = np.repeat(rows[:1], LANES - len(chunk), axis=0)
        block = jnp.asarray(np.concatenate([rows, pad]))
        succ, valid = step(block)
        succ, valid = np.asarray(succ), np.asarray(valid)
        masks = np.asarray(masks_of(block))
        assert not np.asarray(tm.poison_rows(jnp.asarray(succ[valid]))).any()
        for i, s in enumerate(chunk):
            want = sorted(tuple(tm.encode_state(t)) for t in m.next_states(s))
            got = sorted(tuple(int(w) for w in succ[i, a])
                         for a in np.flatnonzero(valid[i]))
            assert got == want, s
            deepest_actions = max(deepest_actions, len(got))
            for p, prop in enumerate(props):
                assert bool(masks[i, p]) == bool(prop.condition(m, s)), (
                    prop.name, s)
    assert 0 < deepest_actions <= tm.max_actions


def test_paxos6_device_prefix_holds_the_references_first_levels(paxos6):
    """A ``target_states`` run of the device engine on paxos-6: the queue's
    rows by depth label are the host search's level sizes 1 / 6 / 24 / 86 /
    276 (paxos is graded: a label is the level)."""
    m, _ = paxos6
    sizes = reference_level_sizes(m, 4)
    assert sizes == [1, 6, 24, 86, 276]
    c = m.checker().target_states(1500).spawn_tpu(
        sync=True, capacity=1 << 14, queue_capacity=1 << 13, batch=64)
    c.join()
    assert c.unique_state_count() >= 1500
    assert not c.growth_events and not c.discoveries()
    snap = c.checkpoint()
    head, tail = int(snap["head"]), int(snap["tail"])
    labels = np.asarray(snap["q_depth"])[:tail]
    assert tail == c.unique_state_count()
    # every row of levels 0..3 was popped, so level 4 is whole
    assert head >= sum(sizes[:4]) and labels[head:].min() >= 4
    assert np.bincount(labels)[:5].tolist() == sizes


# -- the loud overflow ----------------------------------------------------------


def _with_twin(model, twin):
    model.tensor_model = lambda: twin
    return model


def test_too_few_slots_raise_the_poison_error_instead_of_a_count():
    m = paxos_model(2, 3)
    m = _with_twin(m, PaxosTensor(m, 2, n_slots=4))
    with pytest.raises(RuntimeError, match="poisoned rows.*n_slots"):
        m.checker().spawn_tpu(
            sync=True, capacity=1 << 16, queue_capacity=1 << 15, batch=256)


def test_the_terminal_step_record_says_poison():
    from stateright_tpu.parallel.wavefront import _STATUS_TELEMETRY_NAMES

    m = paxos_model(2, 3)
    m = _with_twin(m, PaxosTensor(m, 2, n_slots=4))
    # not ``sync``: the checker is handed out before ``join()`` raises
    c = m.checker().telemetry().spawn_tpu(
        sync=False, capacity=1 << 16, queue_capacity=1 << 15, batch=256)
    with pytest.raises(RuntimeError, match="poisoned rows"):
        c.join()
    # a step record holds the engine's status WORD; its name is the engine's
    named = [_STATUS_TELEMETRY_NAMES[r["status"]]
             for r in c.flight_recorder.records() if r["kind"] == "step"]
    assert named and named[-1] == "poison" and "poison" not in named[:-1]


def test_poison_rows_reads_the_overflow_bit():
    m = paxos_model(2, 3)
    tm = m.tensor_model()
    row = np.asarray(tm.encode_state(next(iter(m.init_states()))), np.uint64)
    rows = jnp.asarray(np.stack([row, row]))
    rows = tm.pk.set(rows, "overflow", jnp.asarray([0, 1], jnp.uint64))
    assert np.asarray(tm.poison_rows(rows)).tolist() == [False, True]
    with pytest.raises(RuntimeError, match="slot overflow"):
        tm.decode_state(np.asarray(rows[1]))


def test_default_slots_keep_the_paxos2_pins():
    c = paxos_model(2, 3).checker().spawn_tpu(sync=True)
    c.join()
    assert c.unique_state_count() == 16668
    assert c.discoveries().keys() == {"value chosen"}


def _run_program(model, **spawn):
    c = model.checker().spawn_tpu(sync=True, **spawn)
    c.join()
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    return c, run_fn.trace(carry).jaxpr, run_fn.lower(carry).as_text(
        debug_info=True)


def _equations(jaxpr) -> int:
    from stateright_tpu.analysis.jaxpr_audit import _iter_eqns

    return sum(1 for _ in _iter_eqns(jaxpr))


SMALL = dict(capacity=1 << 12, queue_capacity=1 << 10, batch=64)


def test_the_poison_test_is_a_named_part_of_bookkeep_and_costs_a_bit_test():
    """The hand twin's step gains the engine's existing poison branch - a
    ``[batch]`` bit test under ``sr.bookkeep/twin.poison``, a named stage -
    and a twin WITHOUT ``poison_rows`` keeps the step it had."""
    m = paxos_model(1, 3)
    c, with_jaxpr, with_text = _run_program(m, **SMALL)
    assert f"/{spans.STAGE_BOOKKEEP}/{spans.TWIN_POISON}/" in with_text

    bare = paxos_model(1, 3)
    twin = bare.tensor_model()
    twin.poison_rows = None  # what ``getattr(tensor, "poison_rows", None)`` reads
    bare = _with_twin(bare, twin)
    c2, bare_jaxpr, bare_text = _run_program(bare, **SMALL)
    assert spans.TWIN_POISON not in bare_text
    assert c.unique_state_count() == c2.unique_state_count() == 265
    assert 0 < _equations(with_jaxpr) - _equations(bare_jaxpr) <= 12


def test_a_twin_without_a_poison_bit_has_no_poison_scope():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(3)
    assert not hasattr(m.tensor_model(), "poison_rows")
    _, _, text = _run_program(m, **SMALL)
    assert spans.TWIN_POISON not in text
    assert f"/{spans.STAGE_BOOKKEEP}/" in text

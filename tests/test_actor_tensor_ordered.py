"""The ordered slot kernel ``slot_send_ordered`` and its two callers.

The kernel CARRIES each slot's flow id (directed ``(src, dst)`` pair) beside
the slot word instead of gathering it from the slot's code on every call.
Held here: the kernel against a plain per-row FIFO reference that knows
nothing of carried ids; the contract ``slot_pair == where(occupied,
env_pair[codes], -1)`` on every output; no ``[batch, slots, slots]`` gather
left in a compiled twin's step; and both callers (``_step_rows_multiset``,
``_append_timeouts``) against the host object model.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.actor import Network
from stateright_tpu.analysis.jaxpr_audit import _iter_eqns
from stateright_tpu.models.linearizable_register import abd_ordered
from stateright_tpu.models.raft import raft_model
from stateright_tpu.parallel.actor_tensor import (
    COUNT_BITS,
    COUNT_MASK,
    SLOT_EMPTY,
    slot_send_ordered,
)

# twelve envelope codes over four flows; the LAST code's flow is what a
# ``code = -1`` lane reads (a wrapped index), so make it a live one
ENV_PAIR = np.array([0, 1, 2, 3, 0, 1, 2, 3, 3, 2, 1, 0], np.int32)
SAME_FLOW = np.array([4, 5, 6, 7, 11, 10, 9, 8, 3, 2, 1, 0])  # another code of it
EMPTY = int(SLOT_EMPTY)


def word(code: int, rank: int) -> int:
    return (code << COUNT_BITS) | rank


def derive_pair(slots: np.ndarray) -> np.ndarray:
    """The contract's right-hand side, from the slot words alone."""
    occ = slots != np.uint64(EMPTY)
    codes = np.where(occ, slots >> np.uint64(COUNT_BITS), 0).astype(np.int64)
    return np.where(occ, ENV_PAIR[codes], -1).astype(np.int32)


def fifo_send(row, code: int, enable: bool):
    """Plain reference for ONE row: depth of the code's flow, first free
    slot, rank = depth + 1.  Returns ``(row, overflow)``."""
    row = [int(w) for w in row]
    if not enable:
        return row, False
    flow = ENV_PAIR[code]
    depth = sum(
        1 for w in row if w != EMPTY and ENV_PAIR[w >> COUNT_BITS] == flow
    )
    free = [i for i, w in enumerate(row) if w == EMPTY]
    if not free or depth >= COUNT_MASK:
        return row, True
    row[free[0]] = word(code, depth + 1)
    return row, False


def random_rows(rng, n_rows: int, n_slots: int, max_fill: int) -> np.ndarray:
    """Valid FIFO networks in NO canonical order (a deliver leaves holes):
    per flow a random depth, ranks 1..depth, random codes of that flow."""
    rows = np.full((n_rows, n_slots), EMPTY, np.uint64)
    for r in range(n_rows):
        words = []
        for flow in range(4):
            depth = int(rng.integers(0, 4))
            codes = np.flatnonzero(ENV_PAIR == flow)
            for rank in range(1, depth + 1):
                words.append(word(int(rng.choice(codes)), rank))
        words = words[: int(rng.integers(0, max_fill + 1))]
        where = rng.permutation(n_slots)[: len(words)]
        rows[r, where] = np.asarray(words, np.uint64)
    return rows


def case_random(rng, n):
    return random_rows(rng, n, 8, 8), [
        (rng.integers(0, 12, n), rng.random(n) < 0.8)
    ]


def case_disabled(rng, n):
    return random_rows(rng, n, 8, 6), [
        (rng.integers(0, 12, n), np.zeros(n, bool))
    ]


def case_no_free_slot(rng, n):
    rows = np.tile(
        np.asarray([word(c, 1) for c in (0, 1, 2, 3)], np.uint64), (n, 1)
    )
    return rows, [(rng.integers(0, 12, n), np.ones(n, bool))]


def case_flow_count_mask_deep(rng, n):
    # flow 0 holds COUNT_MASK envelopes; one slot is free, so only the
    # depth can refuse the send: rank COUNT_MASK + 1 would carry into the code
    row = np.full(COUNT_MASK + 1, EMPTY, np.uint64)
    row[:COUNT_MASK] = [word(4, rank) for rank in range(1, COUNT_MASK + 1)]
    rows = np.tile(rng.permutation(row), (n, 1))
    codes = np.where(np.arange(n) % 2 == 0, 0, 1)  # flow 0 (full) / flow 1
    return rows, [(codes, np.ones(n, bool))]


def case_two_sends_same_flow(rng, n):
    codes = rng.integers(0, 12, n)
    on = np.ones(n, bool)
    return random_rows(rng, n, 10, 7), [(codes, on), (SAME_FLOW[codes], on)]


def case_two_sends_different_flows(rng, n):
    codes = rng.integers(0, 12, n)
    other = (ENV_PAIR[codes] + 1) % 4  # codes 0..3 are flows 0..3
    on = np.ones(n, bool)
    return random_rows(rng, n, 10, 7), [(codes, on), (other, rng.random(n) < 0.7)]


def case_code_minus_one_disabled(rng, n):
    codes = np.where(np.arange(n) % 2 == 0, -1, rng.integers(0, 12, n))
    return random_rows(rng, n, 8, 6), [(codes, codes >= 0)]


CASES = [
    case_random,
    case_disabled,
    case_no_free_slot,
    case_flow_count_mask_deep,
    case_two_sends_same_flow,
    case_two_sends_different_flows,
    case_code_minus_one_disabled,
]


@pytest.mark.parametrize("lead", [(24,), (6, 4)], ids=["B_NS", "B_A_NS"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_kernel_equals_the_plain_fifo_reference(case, lead):
    n = int(np.prod(lead))
    rows, sends = case(np.random.default_rng(29), n)
    ns = rows.shape[-1]
    env_pair = jnp.asarray(ENV_PAIR)

    slots = jnp.asarray(rows).reshape(*lead, ns)
    pair = jnp.asarray(derive_pair(rows)).reshape(*lead, ns)
    want = [list(int(w) for w in r) for r in rows]
    for codes, enable in sends:
        codes = np.asarray(codes, np.int32)
        sk = jnp.asarray(codes).reshape(lead)
        before = np.asarray(slots).reshape(n, ns)
        slots, pair, overflow = slot_send_ordered(
            slots, pair, sk.astype(jnp.uint64), env_pair[sk],
            jnp.asarray(enable).reshape(lead),
        )
        assert slots.shape == pair.shape == (*lead, ns)
        assert overflow.shape == lead and pair.dtype == jnp.int32
        got = np.asarray(slots).reshape(n, ns)
        overflow = np.asarray(overflow).reshape(n)
        for r in range(n):
            want[r], of = fifo_send(want[r], int(codes[r]), bool(enable[r]))
            assert [int(w) for w in got[r]] == want[r], (case.__name__, r)
            assert bool(overflow[r]) == of
            if of or not enable[r]:
                assert np.array_equal(got[r], before[r])
        # the carried ids ARE the looked-up ids, on every output
        assert np.array_equal(
            np.asarray(pair).reshape(n, ns), derive_pair(got)
        )
    for row in want:  # no rank ever carried into the code bits
        live = [w for w in row if w != EMPTY]
        assert all(1 <= (w & COUNT_MASK) and (w >> COUNT_BITS) < 12 for w in live)


def test_the_deep_flow_case_refuses_only_the_full_flow():
    rows, [(codes, _)] = case_flow_count_mask_deep(np.random.default_rng(1), 2)
    for row, code, full in zip(rows, codes, (True, False)):
        assert fifo_send(row, int(code), True)[1] is full


# -- the callers ---------------------------------------------------------------


def gather_shapes(fn, *args) -> collections.Counter:
    """Result shapes of every gather in ``fn``'s jaxpr, sub-jaxprs included.

    Counts DISTINCT sub-jaxprs, not call sites: ``_walk_jaxprs`` visits each
    jaxpr object once, and ``jnp.take_along_axis`` is a cached jit, so ten
    calls of it at one shape read as 1.  The pins below are plain ``x[idx]``
    look-ups (one gather equation each) and stand as they are; to count what
    a program RUNS use ``tests/test_paxos_tensor.py:gather_call_sites``."""
    return collections.Counter(
        tuple(eqn.outvars[0].aval.shape)
        for eqn in _iter_eqns(jax.make_jaxpr(fn)(*args))
        if eqn.primitive.name == "gather"
    )


@pytest.mark.parametrize(
    "make, per_slot_gathers",
    [
        (lambda: abd_ordered(2, 2), 1),
        (lambda: raft_model(2, network=Network.new_ordered()), 1),
    ],
    ids=["abd_ordered_2x2", "raft2_ordered_timers"],
)
def test_step_rows_gathers_no_flow_id_per_successor_slot(make, per_slot_gathers):
    """Before the ids were carried, every ``slot_send_ordered`` call of the
    deliver block gathered ``env_pair`` at ``[B, NS, NS]`` lanes (K of them a
    step) and every one of ``_append_timeouts`` at ``[B, actors, NS]``.  The
    ``[B, NS]`` look-ups were ``3 * n_actors + 2 + K + 3`` (14 and 9 here)
    until the deliver block looked a delivered envelope up ONCE: the
    envelope record is a compare-and-max over the universe, no gather, and
    the one gather left is the ``[B, NS, words]`` row of the transition
    record overlaid over the actors - what ``step_gathers`` on the
    ``twin_compile`` span says."""
    tm = make().tensor_model()
    tm.init_rows()  # device constants outside any trace
    assert tm.ordered
    B, NS = 7, tm.n_slots
    words = tm.compile_attrs()["record_words"]
    cst = tm._consts()
    # the per-actor tables and the per-envelope columns are not on the device
    # at all (env_pair only for the Timeout block's sends, at [B] lanes)
    assert not set(cst) & {
        "trans", "sends", "poison", "teff", "env_dst", "env_kind", "env_val"
    }
    assert ("env_pair" in cst) == bool(tm._has_timers and tm.Kt)
    for step in (tm.step_rows, tm.step_rows_coalesced):
        rows = jnp.zeros((B, tm.width), jnp.uint64)
        shapes = gather_shapes(step, rows)
        assert not [s for s in shapes if int(np.prod(s)) >= B * NS * NS], shapes
        assert not [s for s in shapes if len(s) == 3 and s[-1] == NS], shapes
        at_slot_lanes = shapes[(B, NS)] + shapes[(B, NS, words)]
        assert at_slot_lanes == per_slot_gathers == tm.compile_attrs()["step_gathers"], shapes
        # ... and the one gather at slot lanes reads the overlaid record
        closed = jax.make_jaxpr(step)(rows)
        const_of = dict(zip(closed.jaxpr.constvars, closed.consts))
        read = [
            const_of.get(eqn.invars[0])
            for eqn in _iter_eqns(closed)
            if eqn.primitive.name == "gather"
            and tuple(eqn.outvars[0].aval.shape)[:2] == (B, NS)
        ]
        assert len(read) == 1 and read[0] is cst["trans_rec"], read


def flows_are_well_formed(tm, slot_words) -> bool:
    """Every flow of a successor's network region, re-derived from its own
    codes (``where(occ, env_pair[codes], -1)``), holds ranks 1..depth."""
    ranks = collections.defaultdict(list)
    for w in (int(w) for w in slot_words):
        if w != EMPTY:
            ranks[int(tm._env_pair[w >> COUNT_BITS])].append(w & COUNT_MASK)
    return all(sorted(r) == list(range(1, len(r) + 1)) for r in ranks.values())


def crawl_against_the_host_model(model, levels: int) -> int:
    """BFS the host model ``levels`` deep; per state the twin's valid
    successors (plain and coalesced) equal, as a multiset of rows,
    ``encode_state`` of the host's ``next_state`` over its enabled actions."""
    tm = model.tensor_model()
    tm.init_rows()
    plain, coalesced = jax.jit(tm.step_rows), jax.jit(tm.step_rows_coalesced)
    frontier = list(model.init_states())
    seen = set(frontier)
    checked = 0
    for _ in range(levels):
        rows = np.asarray([tm.encode_state(s) for s in frontier], np.uint64)
        succ, valid = (np.asarray(x) for x in plain(jnp.asarray(rows)))
        succ_c, valid_c = (np.asarray(x) for x in coalesced(jnp.asarray(rows)))
        assert np.array_equal(valid, valid_c)
        assert np.array_equal(succ[valid], succ_c[valid])
        nxt = []
        for i, state in enumerate(frontier):
            host = model.next_states(state)  # next_state over the enabled actions
            want = sorted(tuple(int(w) for w in tm.encode_state(t)) for t in host)
            got = sorted(tuple(int(w) for w in r) for r in succ[i][valid[i]])
            assert got == want, (state, len(got), len(want))
            for row in succ[i][valid[i]]:
                assert flows_are_well_formed(tm, row[tm.pw:]), row
            checked += len(got)
            for t in host:
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return checked


def test_deliver_block_carries_the_ids_abd_ordered_2x3():
    """``_step_rows_multiset``'s ordered branch: the benchmark cell's model,
    its first BFS levels from the init row (K = 2 sends a delivery)."""
    assert crawl_against_the_host_model(abd_ordered(2, 3), levels=7) > 300


def test_timeout_block_carries_the_ids_raft2_ordered():
    """``_append_timeouts`` on an ordered network (the fixture of
    ``tests/test_raft.py``'s network-semantics parity): a timeout's
    ``RequestVote`` broadcast goes through ``slot_send_ordered`` too."""
    model = raft_model(2, network=Network.new_ordered())
    tm = model.tensor_model()
    assert tm.ordered and tm._has_timers and tm.Kt >= 1
    assert crawl_against_the_host_model(model, levels=6) > 40

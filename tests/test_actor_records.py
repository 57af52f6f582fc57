"""The compiled twin's two record tables (``actor_compiler._freeze_records``).

The deliver block looks a delivered envelope up once: one envelope record
by envelope code and one transition record, overlaid over the actors, by
``(state code of the envelope's destination) * ne + envelope code``.  These
tests hold the packed records to the host's per-actor tables entry for
entry, the overlay's invariant to its ``CompileError``, and a record of
more than one word to the successors of the one-word reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.actor import Network
from stateright_tpu.models.linearizable_register import abd_ordered
from stateright_tpu.models.paxos import paxos_lossy
from stateright_tpu.models.raft import raft_model
from stateright_tpu.models.single_copy_register import single_copy_model
from stateright_tpu.parallel import actor_compiler as ac

MODELS = {
    "abd_ordered_2x2": lambda: abd_ordered(2, 2),
    "abd_ordered_2x3": lambda: abd_ordered(2, 3),
    "single_copy_3": lambda: single_copy_model(3, 1),
    "paxos_lossy_1": lambda: paxos_lossy(1, 3),
    "raft2_timers": lambda: raft_model(2),
    "raft2_ordered_timers": lambda: raft_model(2, network=Network.new_ordered()),
}


def planes(rec):
    return [rec[:, w] for w in range(rec.shape[1])]


@pytest.mark.parametrize("name", list(MODELS))
def test_the_records_unpack_to_the_per_actor_tables(name):
    tm = MODELS[name]().tensor_model()
    n, nep, K = tm.n_actors, tm._ne_padded, tm.K
    el, tl = tm._env_layout, tm._trans_layout
    env, rec = planes(tm._env_rec_np), planes(tm._trans_rec_np)
    max_s = max(len(s) for s in tm._states)
    assert tm._trans_rec_np.shape == (max_s * nep, tl.words)
    assert tm._env_rec_np.shape == (nep, el.words)
    assert tl.words == -(-tl.bits // 32) and el.words == -(-el.bits // 32)

    # everything a bare envelope code decides
    dst = el.get(env, "dst")
    assert (dst == np.minimum(tm._env_dst, n)).all()
    if tm.ordered:
        assert (el.get(env, "pair") == tm._env_pair).all()
    if tm.C:
        assert (el.get(env, "kind") == tm._env_kind).all()
        assert (el.get(env, "val") == tm._env_val).all()
        client_of = np.append(tm._client_of, -1)  # dst == n: no actor's
        assert (el.get(env, "ci") - 1 == client_of[dst]).all()

    # every (state of the envelope's destination, envelope) entry
    grid = {f: tl.get(rec, f).reshape(max_s, nep) for f in tl.layout}
    seen_timers = False
    for e in range(nep):
        i = int(tm._env_dst[e])
        if i >= n:  # addressed to no actor: no entry at all
            assert not tm._trans_rec_np.reshape(max_s, nep, -1)[:, e].any()
            continue
        si = len(tm._states[i])
        nc = tm._trans_np[i][:, e]
        assert (grid["valid"][:si, e] == (nc >= 0)).all()
        assert (grid["next"][:si, e] == np.where(nc >= 0, nc, 0)).all()
        assert (grid["poison"][:si, e] == tm._poison_np[i][:, e]).all()
        assert not tm._trans_rec_np.reshape(max_s, nep, -1)[si:, e].any()
        for k in range(K):
            code = tm._sends_np[i][:, e, k]
            assert (grid[f"has{k}"][:si, e] == (code >= 0)).all()
            assert (grid[f"send{k}"][:si, e] == np.where(code >= 0, code, 0)).all()
            if tm.ordered:
                # the send's flow id: its source is the deliverer
                flow = i * n + grid[f"sdst{k}"][:si, e]
                has = code >= 0
                assert (flow[has] == tm._env_pair[code[has]]).all()
        if tm._has_timers:
            seen_timers = True
            assert (grid["teff"][:si, e] - 1 == tm._teff_np[i][:, e]).all()
    assert seen_timers == tm._has_timers


def test_record_layout_round_trips_fields_that_straddle_words():
    rng = np.random.default_rng(3)
    fields = [("a", 1000), ("b", 1), ("c", (1 << 31) - 1), ("d", 70000), ("e", 5), ("f", 1 << 20)]
    lay = ac.RecordLayout(fields)
    assert lay.bits == 10 + 1 + 31 + 17 + 3 + 21 and lay.words == 3
    cols = {nm: rng.integers(0, hi + 1, size=257) for nm, hi in fields}
    rec = lay.pack(**cols)
    assert rec.dtype == np.uint32 and rec.shape == (257, 3)
    for nm, _ in fields:
        assert (lay.get(planes(rec), nm) == cols[nm]).all(), nm
        traced = jax.jit(lambda r, nm=nm: lay.get([r[:, w] for w in range(3)], nm))(rec)
        assert (np.asarray(traced) == cols[nm]).all(), nm
    with pytest.raises(ac.CompileError, match="32|bits"):
        ac.RecordLayout([("wide", 1 << 31)])


def test_the_freeze_raises_when_two_actors_claim_one_entry():
    tm = abd_ordered(2, 2).tensor_model()
    e = int(np.flatnonzero(tm._env_dst == 0)[0])
    sc = int(np.flatnonzero(tm._trans_np[0][:, e] >= 0)[0])
    assert tm._trans_np[1][sc, e] == -1
    tm._trans_np[1][sc, e] = 0  # actor 1 claims actor 0's entry
    with pytest.raises(ac.CompileError, match="both claim"):
        tm._freeze_records()


def test_the_freeze_raises_on_an_entry_whose_actor_is_not_the_destination():
    tm = abd_ordered(2, 2).tensor_model()
    small, big = sorted((0, 1), key=lambda i: len(tm._states[i]))
    e = int(np.flatnonzero(tm._env_dst == small)[0])
    # a state code only the larger universe has, so nobody else claims the entry
    sc = len(tm._states[small])
    assert sc < len(tm._states[big])
    tm._trans_np[big][sc, e] = 0
    with pytest.raises(ac.CompileError, match="destination"):
        tm._freeze_records()


def reachable_rows(tm, levels=5, cap=300):
    step = jax.jit(tm.step_rows)
    frontier = tm.init_rows()
    seen = {tuple(r) for r in frontier.tolist()}
    out = [frontier]
    for _ in range(levels):
        if not len(frontier):
            break
        succ, valid = step(jnp.asarray(frontier))
        fresh = []
        for r in np.asarray(succ)[np.asarray(valid)].tolist():
            if tuple(r) not in seen:
                seen.add(tuple(r))
                fresh.append(r)
        frontier = np.asarray(fresh[:cap], np.uint64).reshape(-1, tm.width)
        out.append(frontier)
    return np.concatenate(out)


@pytest.mark.parametrize("widen", ["transition", "envelope"])
@pytest.mark.parametrize("name", ["abd_ordered_2x2", "raft2_ordered_timers", "paxos_lossy_1"])
def test_a_record_of_two_words_steps_to_the_one_word_successors(monkeypatch, name, widen):
    """A universe doctored to need another word (every field of one record
    15 bits wider, so fields straddle the words) gets it - same successors,
    same ``valid``, through both step forms."""
    ref = MODELS[name]().tensor_model()
    assert ref._trans_layout.words == 1 and ref._env_layout.words == 1

    class Wide(ac.RecordLayout):
        def __init__(self, fields):
            which = "transition" if fields[0][0] == "next" else "envelope"
            if which == widen:
                fields = [(nm, min(hi << 15 | 1, (1 << 31) - 1)) for nm, hi in fields]
            super().__init__(fields)

    monkeypatch.setattr(ac, "RecordLayout", Wide)
    wide = MODELS[name]().tensor_model()
    assert wide is not ref
    words = (wide._trans_layout.words, wide._env_layout.words)
    assert words == ((2, 1) if widen == "transition" else (1, 2)) or max(words) > 2
    attrs = wide.compile_attrs()
    assert attrs["record_words"] == wide._trans_layout.words
    assert attrs["table_bytes"] == ref.compile_attrs()["table_bytes"]
    assert attrs["device_table_bytes"] > ref.compile_attrs()["device_table_bytes"]
    wide.init_rows()  # device constants outside any trace
    rows = jnp.asarray(reachable_rows(ref))
    for form in ("step_rows", "step_rows_coalesced"):
        s_ref, v_ref = jax.jit(getattr(ref, form))(rows)
        s_new, v_new = jax.jit(getattr(wide, form))(rows)
        assert np.asarray(v_ref).sum() > len(rows) // 2
        assert (np.asarray(v_ref) == np.asarray(v_new)).all()
        assert (np.asarray(s_ref) == np.asarray(s_new)).all()


@pytest.mark.parametrize("name", ["abd_ordered_2x2", "paxos_lossy_1"])
def test_a_universe_past_the_select_bound_gathers_its_envelope_record(monkeypatch, name):
    """Past ``_ENV_SELECT_MAX`` envelopes the compare-and-max's work would
    outgrow a gather's: the envelope record is gathered instead - two
    gathers at slot lanes, counted as such, same successors."""
    ref = MODELS[name]().tensor_model()
    assert ref.compile_attrs()["step_gathers"] == 1
    rows = jnp.asarray(reachable_rows(ref))
    forms = ("step_rows", "step_rows_coalesced")
    # the reference's successors are traced BEFORE the bound moves
    expected = {form: jax.jit(getattr(ref, form))(rows) for form in forms}
    monkeypatch.setattr(ac, "_ENV_SELECT_MAX", 3)
    big = MODELS[name]().tensor_model()
    big.init_rows()
    assert big.compile_attrs()["step_gathers"] == 2
    B, NS = rows.shape[0], big.n_slots
    for form in forms:
        closed = jax.make_jaxpr(getattr(big, form))(rows)
        at_slot_lanes = [
            eqn for eqn in closed.jaxpr.eqns
            if eqn.primitive.name == "gather"
            and tuple(eqn.outvars[0].aval.shape)[:2] == (B, NS)
        ]
        assert len(at_slot_lanes) == 2
        s_ref, v_ref = expected[form]
        s_new, v_new = jax.jit(getattr(big, form))(rows)
        assert (np.asarray(v_ref) == np.asarray(v_new)).all()
        assert (np.asarray(s_ref) == np.asarray(s_new)).all()

"""The queue append writes what is novel: ``wavefront.append_novel`` is ONE
body for every engine, the mesh's included - ``qchunk``-row chunks while
``k * qchunk < n_new`` - where its predecessor wrote one ``cand``-wide
window a step.

Held here:

 - the function against the parent's window (kept verbatim below as
   ``ref_append_novel``, as ``tests/test_buckets.py`` keeps
   ``ref_bucket_insert``): every row up to the last chunk's end is the
   window's row, every row past it is untouched, for a ``cand`` that the
   chunk divides, does not divide and that is smaller than ``batch``, at
   ``n_new`` = 0, 1, ``qchunk``, ``qchunk + 1`` and ``eff_cand``, for
   one-word rows (gathered in the loop, with the columns) and wider ones
   (gathered before it);
 - whole engines built on the one and on the other: the queue's live rows
   ``[0, tail)`` of all four buffers, the counts, the discoveries and their
   paths equal - the same three ``cand`` shapes, an overflowed batch
   replayed after growth, ``.symmetry()``, POR's two appends a step, and
   the four-virtual-device mesh (whose window is ``ref_place_append``'s)
   against the one-device engine too: one-word rows and wide ones, a
   ``cand`` the chunk does not divide, ``.symmetry()``, POR;
 - ``append_chunks`` (the ``step`` records' counter) against a host replay
   of the same search, and the mesh's against the one-device engine's;
 - the one-device step program's compiled text: no gather and no update of
   the append wider than ``qchunk`` rows, but a wide payload's one gather.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hlo_stage import stage_movers

from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.hashing import row_hash
from stateright_tpu.parallel import wavefront
from stateright_tpu.parallel.carry import QUEUE_FIELDS, carry_avals
from stateright_tpu.parallel.wavefront import append_novel


# -- the parent's append, verbatim ------------------------------------------------


def ref_place_append(place, q, rows, tail):
    """``StepPlacement.append`` as the window's reference keeps it: the
    payload rows by row index, a narrow column by update slice."""
    rows = place.whole(rows)
    if q.ndim == 1:
        return jax.lax.dynamic_update_slice(q, rows, (tail,))
    at = tail + jax.lax.iota(tail.dtype, rows.shape[0])
    return q.at[at].set(
        rows, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds",
    )


def ref_append_novel(bufs, tail0, sel, n_new, cands, qchunk, place=None):
    """The append this file's subject replaced (``wavefront.py:330-348`` at
    commit fa77ed2): one candidate-stack-wide window a buffer, whatever
    ``n_new`` is."""
    qrows, qfp, qebits, qdepth = bufs
    crows, cfp, cebt, cdep = cands
    if place is not None:
        return tuple(
            ref_place_append(place, q, c[sel], tail0)
            for q, c in ((qrows, crows), (qfp, cfp), (qebits, cebt),
                         (qdepth, cdep))
        ), jnp.int32(0)
    qrows = jax.lax.dynamic_update_slice(
        qrows, crows[sel], (tail0, jnp.int32(0))
    )
    qfp = jax.lax.dynamic_update_slice(qfp, cfp[sel], (tail0,))
    qebits = jax.lax.dynamic_update_slice(qebits, cebt[sel], (tail0,))
    qdepth = jax.lax.dynamic_update_slice(qdepth, cdep[sel], (tail0,))
    return (qrows, qfp, qebits, qdepth), jnp.int32(0)


# -- the function -------------------------------------------------------------------

# (batch, eff_cand): the chunk divides / does not divide the stack / is the
# whole of a stack smaller than the batch
SHAPES = {"divides": (32, 128), "odd": (64, 100), "under_batch": (128, 96)}
M, QALLOC, TAIL0 = 160, 512, 37


def _operands(eff_cand, width, seed=0):
    rng = np.random.default_rng(seed)
    bufs = (
        rng.integers(1, 1 << 60, (QALLOC, width)).astype(np.uint64),
        rng.integers(1, 1 << 60, QALLOC).astype(np.uint64),
        rng.integers(0, 1 << 30, QALLOC).astype(np.uint32),
        rng.integers(0, 1 << 30, QALLOC).astype(np.uint32),
    )
    cands = (
        rng.integers(1, 1 << 60, (M, width)).astype(np.uint64),
        rng.integers(1, 1 << 60, M).astype(np.uint64),
        rng.integers(0, 1 << 30, M).astype(np.uint32),
        rng.integers(0, 1 << 30, M).astype(np.uint32),
    )
    sel = rng.permutation(M)[:eff_cand].astype(np.int32)
    return bufs, cands, sel


@pytest.mark.parametrize("n_new", ["0", "1", "qchunk", "qchunk+1", "eff_cand"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("width", [1, 3], ids=["one_word", "wide"])
def test_every_row_up_to_the_last_chunk_is_the_windows_and_none_past_it(
        width, shape, n_new):
    batch, eff_cand = SHAPES[shape]
    qchunk = min(batch, eff_cand)
    n = min(eff_cand, {"0": 0, "1": 1, "qchunk": qchunk, "qchunk+1": qchunk + 1,
                       "eff_cand": eff_cand}[n_new])
    bufs, cands, sel = _operands(eff_cand, width)

    def run(fn):
        out, chunks = jax.jit(
            lambda b, t, s, k, c: fn(b, t, s, k, c, qchunk)
        )(bufs, jnp.int32(TAIL0), sel, jnp.int32(n), cands)
        return [np.asarray(x) for x in out], int(chunks)

    new, chunks = run(append_novel)
    ref, _ = run(ref_append_novel)
    assert chunks == -(-n // qchunk)
    end = TAIL0 + min(chunks * qchunk, eff_cand)  # the last chunk's end
    assert TAIL0 + n <= end <= TAIL0 + eff_cand
    for got, want, before in zip(new, ref, bufs):
        assert np.array_equal(got[:TAIL0 + n], want[:TAIL0 + n])  # the live rows
        assert np.array_equal(got[:end], want[:end])  # and the garbage, as it was
        assert np.array_equal(got[end:], before[end:])  # nothing past the chunks
    if n == 0:  # nothing novel (an overflowed batch too): nothing written
        assert end == TAIL0


# -- whole engines --------------------------------------------------------------------


def _paths(c):
    return {name: [str(s) for s in path.states()]
            for name, path in c.discoveries().items()}


def _live_queue(c):
    carry = c._final_carry
    tail = int(carry.tail)
    return tail, [np.asarray(getattr(carry, k))[:tail] for k in QUEUE_FIELDS]


def _same_search(a, b):
    assert (a.state_count(), a.unique_state_count(), a.max_depth()) == (
        b.state_count(), b.unique_state_count(), b.max_depth())
    (ta, qa), (tb, qb) = _live_queue(a), _live_queue(b)
    assert ta == tb > 0
    for name, x, y in zip(QUEUE_FIELDS, qa, qb):
        assert np.array_equal(x, y), name
    assert _paths(a) == _paths(b) and a.discoveries()


def _pc_paxos1():
    m = paxos_model(1, 3)
    m.per_channel_()
    return m


ENGINES = {
    # the three cand shapes of the ONE body: a chunk divides ``cand``, does
    # not (the last chunk restarts at ``cand - qchunk``), is ``cand`` itself
    "cand_divides": (lambda: TwoPhaseSys(3).checker(),
                     dict(capacity=1 << 12, batch=64, cand=128,
                          queue_capacity=1 << 12), 288),
    "cand_odd": (lambda: TwoPhaseSys(3).checker(),
                 dict(capacity=1 << 12, batch=64, cand=100,
                      queue_capacity=1 << 12), 288),
    "cand_under_batch": (lambda: TwoPhaseSys(3).checker(),
                         dict(capacity=1 << 12, batch=128, cand=96,
                              queue_capacity=1 << 12), 288),
    # a table that overflows and a cand budget that is passed: the batch
    # wrote nothing durable and is replayed after growth
    "replay_after_growth": (lambda: TwoPhaseSys(4).checker(),
                            dict(capacity=1 << 8, batch=32, cand=64,
                                 queue_capacity=1 << 12), 1568),
    "symmetry": (lambda: TwoPhaseSys(3).checker().symmetry(),
                 dict(capacity=1 << 12, batch=64), None),
    # POR's cycle proviso appends twice a step, the second at tail + n_new
    "por": (lambda: _pc_paxos1().checker().por(),
            dict(capacity=1 << 15, batch=256), 250),
}

# the four-virtual-device mesh engine: (the ONE-device builder, spawn, unique)
MESH4 = {
    "mesh4": (lambda: TwoPhaseSys(3).checker(),
              dict(capacity=1 << 12, batch=64, cand=256,
                   queue_capacity=1 << 12), 288),
    # rows wider than a word: the payload gathered before the loop, on
    # every chip, and a chunk of it sliced a trip
    "mesh4_wide": (lambda: paxos_model(1, 3).checker(),
                   dict(capacity=1 << 12, batch=64, cand=256,
                        queue_capacity=1 << 12), None),
    # the last chunk restarts at ``cand - qchunk``: rows rewritten by index
    "mesh4_cand_odd": (lambda: TwoPhaseSys(3).checker(),
                       dict(capacity=1 << 12, batch=64, cand=100,
                            queue_capacity=1 << 12), 288),
    "mesh4_symmetry": (lambda: TwoPhaseSys(3).checker().symmetry(),
                       dict(capacity=1 << 12, batch=64), None),
    "mesh4_por": (lambda: _pc_paxos1().checker().por(),
                  dict(capacity=1 << 15, batch=256), 250),
}
ENGINES.update({
    case: (lambda one=one: one().mesh(devices=4), spawn, unique)
    for case, (one, spawn, unique) in MESH4.items()
})


def _chunks(c):
    return sum(r["append_chunks"] for r in c.flight_recorder.records("step"))


@pytest.mark.parametrize("case", sorted(ENGINES))
def test_an_engine_queues_what_the_window_queued(case, monkeypatch):
    builder, spawn, unique = ENGINES[case]
    new = builder().telemetry().spawn_tpu(sync=True, **spawn)
    assert unique is None or new.unique_state_count() == unique
    with monkeypatch.context() as mp:
        mp.setattr(wavefront, "append_novel", ref_append_novel)
        ref = builder().telemetry().spawn_tpu(sync=True, **spawn)
    _same_search(new, ref)
    if case == "replay_after_growth":
        kinds = {status for status, _ in new.growth_events}
        assert {wavefront._STATUS_TABLE_FULL, wavefront._STATUS_CAND_FULL} <= kinds
        assert new.growth_events == ref.growth_events
    assert _chunks(ref) == 0 < _chunks(new)  # the window counts none
    if case in MESH4:  # and the one-device engine's, rows and all
        assert new.n_devices == 4
        one = MESH4[case][0]().telemetry().spawn_tpu(sync=True, **spawn)
        _same_search(new, one)
        # the trips follow ``n_new`` on the mesh as on one device
        assert _chunks(new) == _chunks(one)
        assert new.device_steps() == one.device_steps()


# -- the counter ------------------------------------------------------------------------


def test_append_chunks_is_what_a_host_replay_of_the_search_counts():
    """``append_chunks`` summed over the ``step`` records equals
    ``sum(ceil(n_new_i / qchunk))`` where the host pops the final queue's
    rows a batch at a time, expands them with the twin's kernel outside
    any engine and counts the fingerprints it had not seen."""
    batch = 128
    c = TwoPhaseSys(5).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 16, batch=batch, steps_per_call=4)
    assert c.unique_state_count() == 8832 and not c.growth_events
    steps = [r for r in c.flight_recorder.records("step")]
    assert steps[0]["dsteps"] == steps[0]["append_chunks"] == 0  # the init call
    tensor = c.tensor
    qchunk = min(batch, c._cand, batch * tensor.max_actions)
    tail, (rows, fps, _, _) = _live_queue(c)
    expand = jax.jit(lambda r: (lambda s, v: (row_hash(s), v))(*tensor.step_rows(r)))
    n_init = np.asarray(tensor.init_rows()).shape[0]
    seen, head, t, chunks, dsteps = set(fps[:n_init].tolist()), 0, n_init, 0, 0
    while head < t:
        n = min(batch, t - head)
        block = np.concatenate([rows[head:head + n],
                                np.repeat(rows[:1], batch - n, axis=0)])
        sfp, valid = (np.asarray(x) for x in expand(jnp.asarray(block)))
        novel = set(sfp[:n][valid[:n]].tolist()) - seen
        assert novel == set(fps[t:t + len(novel)].tolist())  # this step's rows
        chunks += -(-len(novel) // qchunk)
        seen |= novel
        head, t, dsteps = head + n, t + len(novel), dsteps + 1
    assert t == tail == 8832
    assert sum(r["dsteps"] for r in steps) == dsteps == c.device_steps()
    assert sum(r["append_chunks"] for r in steps) == chunks
    assert dsteps < chunks < 3 * dsteps  # more than one trip a step, on 2pc-5


# -- the compiled step ---------------------------------------------------------------------


@pytest.mark.parametrize("rows", ["one_word", "wide"])
def test_no_gather_or_update_of_the_compiled_append_is_wider_than_a_chunk(rows):
    """XLA:CPU's module of the one-device step at batch 16 / cand 64: the
    append is four gathers and four update slices of ``qchunk`` = 16 rows,
    and nothing of the 64-lane window the reference gathers and writes -
    but, where a row is wider than a word (paxos-1's), the payload's one
    gather before the loop.  (The TPU compiler's modules, one chip and the
    described 2x2 mesh, are held to the same in tests/test_table_layout.py,
    which owns the topology.)"""
    model = TwoPhaseSys(3) if rows == "one_word" else paxos_model(1, 3)
    tensor, props = model.tensor_model(), list(model.properties())
    cap, qcap, batch, cand = 1 << 10, 1 << 8, 16, 64
    assert batch < cand < batch * tensor.max_actions
    assert (tensor.width > 1) == (rows == "wide")

    def movers(patched):
        with pytest.MonkeyPatch.context() as mp:
            if patched:
                mp.setattr(wavefront, "append_novel", ref_append_novel)
            _, run_fn = wavefront._build_engine(
                tensor, props, cap, qcap, batch, 4, None, cand=cand)
            avals = carry_avals(tensor, len(props), cap, qcap, batch, checked=False)
            text = run_fn.lower(avals).compile().as_text()
        return sorted(stage_movers(text, "sr.append"))

    payload = cand if rows == "wide" else batch
    assert movers(False) == (
        [("dynamic-update-slice", batch)] * 4
        + [("gather", batch)] * 3 + [("gather", payload)])
    # the helper does see a window where there is one: the parent's append
    assert movers(True) == (
        [("dynamic-update-slice", cand)] * 4 + [("gather", cand)] * 4)

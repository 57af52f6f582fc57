"""The wavefront carry is one pytree with named fields (PR 49).

``parallel/carry.py`` is the one place that knows the buffers' names and
order, which tails a build has, their shapes, the snapshot keys and the
packed stats vector.  Held here:

 - for every ``(checked, por, spill, cartography)`` combination the carry
   ``init_fn`` builds has exactly the module's names, leaf for leaf in the
   order, shapes and dtypes of ``carry_avals`` (the drift fence the five
   hand copies of the layout used to need, as a property of the type);
 - the memory ledger's specs and the mesh engine's shardings name the same
   buffers in the same order as the carry;
 - a snapshot holds the thirteen base buffers under the names the parent
   tree wrote, and one written by the parent (its keys spelled out here)
   resumes with every tail seeded anew;
 - a growth event, on the device and on the host, leaves the tails the
   objects they were and arms ``por.boost``.
"""

import itertools

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel import wavefront as wf
from stateright_tpu.parallel.carry import (
    SNAPSHOT_KEYS,
    Carry,
    carry_avals,
    leaf_names,
    ledger_name,
)
from stateright_tpu.parallel.partition import MESH_AXES
from stateright_tpu.telemetry.memory import ENV_DEVICE_BYTES

TPC3_UNIQUE, TPC5_UNIQUE = 288, 8832  # examples/2pc.rs:128,133
SMALL = dict(sync=True, capacity=1 << 10, batch=16)

# what the parent tree's ``_SNAPSHOT_KEYS`` spelled: the on-disk names
PARENT_SNAPSHOT_KEYS = (
    "table_fp", "table_parent", "q_rows", "q_fp", "q_ebits", "q_depth",
    "head", "tail", "unique", "scount", "disc", "maxdepth", "status",
)
MANIFEST = {"cap", "qcap", "batch", "cand", "width", "engine", "model_sig",
            "run_id", "footprint_bytes", "cart_depth_base"}


def _builder(n, checked=False, por=False, spill=False, cartography=False):
    b = TwoPhaseSys(n).checker()
    if checked:
        b = b.checked()
    if por:
        b = b.por()
    if spill:
        b = b.spill()
    if cartography:
        b = b.telemetry(cartography=True)
    return b


@pytest.fixture
def spill_budget(monkeypatch):
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(1 << 30))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")


def _avals(checked=False, por=False, spill=False, cartography=False):
    """The module's carry for these flags, at any capacities."""
    return carry_avals(TwoPhaseSys(3).tensor_model(), 2, 1 << 10, 1 << 9, 16,
                       checked, cartography, por,
                       (1 << 10, 64) if spill else None)


@pytest.mark.parametrize(
    "checked,por,spill,cartography",
    list(itertools.product([False, True], repeat=4)),
)
def test_init_builds_the_modules_carry(checked, por, spill, cartography,
                                       spill_budget):
    flags = dict(checked=checked, por=por, spill=spill,
                 cartography=cartography)
    names = leaf_names(_avals(**flags))
    assert names[:13] == SNAPSHOT_KEYS == PARENT_SNAPSHOT_KEYS
    assert len(names) == 13 + checked + 2 * por + 9 * spill + 3 * cartography
    tails = [n.split("_")[0] for n in names[13:]]
    assert tails == sorted(tails, key=["err", "por", "spill", "cart"].index)
    if por and spill:
        # no engine is built for the pair
        with pytest.raises(NotImplementedError, match="spill mode"):
            _builder(3, **flags).spawn_tpu(**SMALL)
        return
    c = _builder(3, **flags).spawn_tpu(**SMALL)
    assert c.unique_state_count() == TPC3_UNIQUE or por
    init_fn, _ = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    assert isinstance(carry, Carry)
    assert [t is not None for t in
            (carry.err, carry.por, carry.spill, carry.cart)] == [
        checked, por, spill, cartography]
    assert leaf_names(carry) == names
    avals = c._avals(c._cap, c._qcap, c._batch)
    assert jax.tree.structure(avals) == jax.tree.structure(carry)
    for name, got, want in zip(
        names, jax.tree.leaves(carry), jax.tree.leaves(avals), strict=True
    ):
        assert (got.shape, got.dtype) == (want.shape, want.dtype), name
    # the run program takes the carry init built and hands the same one back
    assert jax.tree.structure(c._final_carry) == jax.tree.structure(carry)


@pytest.mark.parametrize("flags", [
    {},
    dict(checked=True, por=True, cartography=True),
    dict(spill=True, cartography=True),
], ids=["plain", "checked-por-cart", "spill-cart"])
def test_the_memory_ledger_names_the_carrys_buffers_in_its_order(
        flags, spill_budget):
    c = _builder(3, **flags).spawn_tpu(**SMALL)
    specs = c._memory_spec_fn()(c._memory_caps())
    names = leaf_names(c._final_carry)
    assert names == leaf_names(_avals(**flags))
    assert [s.name for s in specs] == [ledger_name(n) for n in names]
    for s, leaf in zip(specs, jax.tree.leaves(c._final_carry), strict=True):
        assert (s.shape, s.dtype) == (leaf.shape, leaf.dtype), s.name
    # the spellings the ledger's records and the planners' tables keep
    if flags.get("checked"):
        assert "checked_err" in [s.name for s in specs]
    if flags.get("spill"):
        assert {"pend_fp", "pend_count", "spill_bloom"} <= {
            s.name for s in specs}


@pytest.mark.parametrize("flags", [
    {}, dict(checked=True, por=True, cartography=True),
], ids=["plain", "checked-por-cart"])
def test_the_mesh_engine_places_the_carrys_buffers_by_their_names(flags):
    c = _builder(3, **flags).spawn_tpu(devices=2, **SMALL)
    avals = c._avals(c._cap, c._qcap, c._batch)
    placed = c._place(avals)
    assert jax.tree.structure(placed) == jax.tree.structure(c._final_carry)
    for name, sh, leaf in zip(
        leaf_names(_avals(**flags)), jax.tree.leaves(placed),
        jax.tree.leaves(c._final_carry), strict=True,
    ):
        sharded = name.startswith(("table_", "q_"))
        assert sh.spec == (P(MESH_AXES) if sharded else P()), name
        assert leaf.sharding == sh, name
    # the ledger reads the bytes one device holds off the same placement
    specs = c._memory_spec_fn()(c._memory_caps())
    assert [s.per_device_nbytes * 2 == s.nbytes for s in specs[:6]] == [True] * 6
    assert all(s.per_device_nbytes == s.nbytes for s in specs[6:])


def test_a_parent_written_snapshot_resumes_with_every_tail_seeded_anew():
    flags = dict(checked=True, cartography=True)
    first = _builder(5, **flags).target_states(3000).spawn_tpu(
        sync=True, batch=64, steps_per_call=2)
    snap = first._carry_to_snapshot(first._final_carry, first._cap, first._qcap)
    # no tail rides a snapshot: the thirteen buffers and the manifest
    assert set(snap) - MANIFEST == set(PARENT_SNAPSHOT_KEYS)
    assert 0 < int(snap["unique"]) < TPC5_UNIQUE
    assert int(snap["head"]) < int(snap["tail"])
    # a snapshot as the parent tree wrote it: the thirteen buffers zipped
    # against ITS key list, and the manifest
    written = {k: np.asarray(v)
               for k, v in zip(PARENT_SNAPSHOT_KEYS, first._final_carry.base())}
    written.update({k: snap[k] for k in MANIFEST & set(snap)})
    # what the first run's tails held must not come back
    assert int(first._final_carry.cart.action_hist.sum()) > 0
    resumed = _builder(5, **flags).spawn_tpu(sync=True, resume=written)
    assert resumed.unique_state_count() == TPC5_UNIQUE
    resumed.assert_properties()
    cap, qcap, seeded = resumed._snapshot_to_carry(written)
    assert (cap, qcap) == (int(snap["cap"]), int(snap["qcap"]))
    assert not bool(seeded.err)
    assert all(int(np.asarray(c).sum()) == 0
               for c in jax.tree.leaves(seeded.cart))
    for k in PARENT_SNAPSHOT_KEYS[6:]:
        np.testing.assert_array_equal(getattr(seeded, k), written[k], err_msg=k)
    # the totals keep counting across the resume; the tallies restarted
    total = resumed.cartography()
    assert sum(total["depth_hist"]) == TPC5_UNIQUE
    assert sum(total["action_hist"]) < resumed.state_count()


@pytest.mark.parametrize("spill", [False, True], ids=["device", "host"])
def test_a_growth_event_leaves_the_tails_where_they_are(spill, spill_budget):
    flags = dict(checked=True, cartography=True, por=not spill, spill=spill)
    c = _builder(3, **flags).spawn_tpu(**SMALL)
    carry = c._final_carry
    assert c._grows_on_device(carry) is (not spill)
    before = carry.cart
    counters = [np.asarray(x).copy() for x in jax.tree.leaves(before)]
    grown, cap, qcap, cand, _ = c._grow(
        carry, wf._STATUS_TABLE_FULL, c._cap, c._qcap, c._batch, c._cand
    )
    assert cap == 2 * c._cap and grown.table_fp.shape == (cap,)
    assert isinstance(grown.table_fp, jax.Array)
    assert int(grown.status) == wf._STATUS_OK and int(grown.head) == 0
    # nothing stripped and re-attached: the same objects
    assert grown.err is carry.err and grown.cart is before
    for got, want in zip(jax.tree.leaves(grown.cart), counters, strict=True):
        np.testing.assert_array_equal(got, want)
    if spill:
        assert grown.spill is carry.spill
    else:
        # growth is a boundary: one fully expanded batch
        assert int(carry.por.boost) == 0 and int(grown.por.boost) == 1
        assert grown.por.stats is carry.por.stats

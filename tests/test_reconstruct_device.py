"""A discovery's path is read off the device (``ops/buckets.parent_chains``).

``TpuChecker.discoveries`` resolves the parent links of every discovered
fingerprint against the final carry's table where it lies, in one dispatch a
run, and only the chains cross to the host.  Held here, on XLA:CPU:

 - the device chains equal ``_walk(_parents_from_table(*_table_np()))``, the
   host path they replaced, for every discovery and for 256 drawn table
   entries: 2pc-5 plain and under ``.symmetry()``, a compiled actor twin, a
   table that grew;
 - a chain that leaves the table, or outgrows its bound, raises: never a
   silently shorter path;
 - who keeps the host path: a spill-tier run whose store holds the roots
   returns the paths it returned and says ``path="host"``; a ``devices=4``
   mesh run walks its sharded table where it lies (``shards`` 4);
 - one program a table capacity: a second equal model object compiles
   nothing.

(That the compiled program holds no table-sized operation of its own is
``tests/test_table_layout.py``'s, with the other compiles for a described
chip.)
"""

import numpy as np
import pytest

from stateright_tpu.models.linearizable_register import abd_ordered
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.buckets import (
    CHAIN_BOUND,
    CHAIN_MISS,
    CHAIN_ROOT,
    parent_chains,
)
from stateright_tpu.ops.hashing import EMPTY
from stateright_tpu.telemetry.memory import ENV_DEVICE_BYTES

DRAWN = 256


def _twopc5(builder):
    return builder.spawn_tpu(sync=True, capacity=1 << 16, batch=128)


CHECKS = {
    "twopc5": lambda: _twopc5(TwoPhaseSys(5).checker().telemetry()),
    "twopc5-symmetry": lambda: _twopc5(
        TwoPhaseSys(5).checker().symmetry().telemetry()
    ),
    "compiled-twin": lambda: abd_ordered(2, 2).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 13, batch=256
    ),
    # the defaults of a small table: the run grows it on the way
    "grown-table": lambda: TwoPhaseSys(5).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=128
    ),
}


@pytest.fixture(scope="module", params=sorted(CHECKS))
def check(request):
    """One finished check, its pulled table and the host's parent map."""
    c = CHECKS[request.param]()
    c.join()
    tfp, tpl = c._table_np()
    return {"name": request.param, "checker": c, "table": (tfp, tpl),
            "parents": c._parents_from_table(tfp, tpl)}


def _spans(checker, name):
    return [r for r in checker.flight_recorder.records("span") if r["name"] == name]


def test_every_discovery_is_the_host_walk(check):
    c = check["checker"]
    if check["name"] == "grown-table":
        assert len(c.growth_events) >= 1
    found = c.discoveries()
    disc = [int(fp) for fp in c._results["disc"] if int(fp)]
    assert len(found) == len(disc) >= 1
    for fp in disc:
        walked = c._walk(check["parents"], fp)
        assert c._trace(fp) == walked and walked[-1] == fp
        assert check["parents"][walked[0]] == 0  # an init state
    (parents,), (pull,) = (_spans(c, "reconstruct.parents"),
                           _spans(c, "reconstruct.pull"))
    assert parents["path"] == "device"
    assert parents["lookups"] == sum(len(p) for p in found.values())
    assert 0 < pull["bytes"] < 64 << 10
    for name, path in found.items():
        assert c.discovery(name) == path
    # the second calls dispatched nothing and pulled nothing
    assert len(_spans(c, "reconstruct.parents")) == 1
    assert len(_spans(c, "reconstruct.pull")) == 1
    assert len(_spans(c, "reconstruct")) == 1 + len(found)


def test_drawn_table_entries_are_the_host_walk(check):
    """``DRAWN`` occupied slots, their chains in one call against the carry's
    own arrays."""
    c = check["checker"]
    tfp, _ = check["table"]
    held = tfp[tfp != EMPTY]
    assert len(held) == c.unique_state_count()
    starts = np.random.default_rng(5).choice(held, DRAWN, replace=False)
    bound = 1 << c.max_depth().bit_length()
    chains, lens, ends = (
        np.asarray(x) for x in parent_chains(*c._device_table(), starts, bound=bound)
    )
    assert (ends == CHAIN_ROOT).all()
    for k, fp in enumerate(starts):
        walked = c._walk(check["parents"], int(fp))
        assert chains[k, :lens[k]][::-1].tolist() == walked
        assert not chains[k, lens[k]:].any()
    assert lens.max() <= c.max_depth() + 1


@pytest.fixture()
def fresh():
    c = _twopc5(TwoPhaseSys(5).checker())
    c.join()
    return c


def test_a_start_of_zero_is_an_empty_chain_and_a_stranger_misses(fresh):
    tfp, tpl = fresh._device_table()
    held = set(np.asarray(tfp).tolist())
    stranger = next(fp for fp in range(7, 99) if fp not in held)
    deep = int(fresh._results["disc"].max())
    starts = np.array([0, stranger, deep], np.uint64)
    chains, lens, ends = (
        np.asarray(x) for x in parent_chains(tfp, tpl, starts, bound=4)
    )
    assert ends.tolist() == [CHAIN_ROOT, CHAIN_MISS, CHAIN_BOUND]
    assert lens.tolist() == [0, 0, 4] and chains[2, 0] == deep
    # the fingerprint that missed stands past the chain's end, uncounted
    assert not chains[0].any() and chains[1].tolist() == [stranger, 0, 0, 0]


def test_a_chain_that_leaves_the_table_raises(fresh):
    """No spill store: the table holds the whole search, so a fingerprint
    that is not in its bucket is a bug, not a shorter path."""
    tfp, _ = fresh._table_np()
    held = set(tfp.tolist())
    disc = fresh._results["disc"].copy()
    disc[0] = next(fp for fp in range(7, 99) if fp not in held)
    fresh._results["disc"] = disc
    with pytest.raises(RuntimeError, match="leaves the visited table after 0"):
        fresh.discoveries()


def test_a_bound_that_is_too_small_raises(fresh):
    """2pc-5's deepest discovery is 17 states from an init state; a search
    that claims to have been 3 deep gets the smallest bound, 16."""
    assert max(len(p) for p in fresh.discoveries().values()) == 17
    again = _twopc5(TwoPhaseSys(5).checker())
    again.join()
    again._results["depth"] = 3
    with pytest.raises(RuntimeError, match="longer than its bound of 16"):
        again.discoveries()
    assert again._chain_map is None  # nothing kept of a failed resolve


def test_a_second_model_object_finds_the_program_compiled():
    first = _twopc5(TwoPhaseSys(5).checker())
    want = first.discoveries()
    programs = parent_chains._cache_size()
    second = _twopc5(TwoPhaseSys(5).checker())  # a fresh model, twin, checker
    assert second.discoveries() == want
    assert parent_chains._cache_size() == programs


def _states(paths):
    return {name: [str(s) for s in p.states()] for name, p in paths.items()}


def test_a_spilled_run_reconstructs_through_the_host(monkeypatch):
    """An eviction clears the whole hot table, the init states with it:
    every chain leaves it, so the run merges the tiers on the host as it
    did (``tests/test_spill.py`` holds the tiers themselves)."""
    from test_spill import BATCH, _budget_for, _spawn_spill

    c = _spawn_spill(5, _budget_for(5, 1 << 13), monkeypatch,
                     telemetry={"capacity": 1 << 14})
    assert c.spill_status()["evictions"] >= 1 and c._device_table() is None
    found = c.discoveries()
    monkeypatch.delenv(ENV_DEVICE_BYTES)
    base = TwoPhaseSys(5).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=BATCH
    )
    assert set(found) == set(base.discoveries())
    for name, path in found.items():
        prop = c.model.property_by_name(name)
        assert prop.condition(c.model, path.last_state())
    (parents,), (pull,) = (_spans(c, "reconstruct.parents"),
                           _spans(c, "reconstruct.pull"))
    assert parents["path"] == "host"
    assert parents["lookups"] == c.unique_state_count()
    assert pull["bytes"] == 2 * 8 * c._cap


def test_a_mesh_run_reconstructs_on_the_device_too():
    """The table is sharded by bucket range over four (virtual) devices:
    read off the array's sharding, no knob - and walked where it lies
    (``tests/test_mesh_reconstruct.py`` holds the sharded walk itself)."""
    mesh = TwoPhaseSys(3).checker().telemetry().spawn_tpu(
        sync=True, devices=4, capacity=1 << 12, batch=64
    )
    solo = TwoPhaseSys(3).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert len(mesh._device_table()[0].sharding.device_set) == 4
    assert len(solo._device_table()[0].sharding.device_set) == 1
    assert _states(mesh.discoveries()) == _states(solo.discoveries())
    (parents,), (pull,) = (_spans(mesh, "reconstruct.parents"),
                           _spans(mesh, "reconstruct.pull"))
    assert (parents["path"], parents["shards"]) == ("device", 4)
    assert 0 < pull["bytes"] < 64 << 10
    (alone,) = _spans(solo, "reconstruct.parents")
    assert (alone["path"], alone["shards"]) == ("device", 1)

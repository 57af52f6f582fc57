"""The mesh engine reads its sharded table where it lies, and keeps its
carry placed as the partition rules place it.

Held here, on XLA:CPU's virtual devices (the suite forces eight):

 - on 2 and 4 devices the sharded ``parent_chains`` (``ops/buckets.
   sharded_parent_chains``: the table's own ``NamedSharding`` in, the chains
   replicated out) gives the ONE-device chains for every discovery of
   2pc-3, paxos-1 and a ``target_states`` prefix of paxos-2, and for drawn
   table entries; ``reconstruct.parents`` says ``path: device`` and how
   many shards, ``reconstruct.pull`` stays in the hundreds of bytes, and
   nothing pulls the table (``_table_np`` is never called inside a check);
 - ``mesh_stats()`` counts ``shard_load`` and the route matrix on the
   device (``mesh._shard_traffic``) and equals the host's count over the
   pulled table, on a divided table and on one the mesh does not divide;
 - a spilled run still takes the host path;
 - **the memory bound**: at the shapes of the benchmark's four-chip cell
   (``paxos6x4-bounded``) ``MeshTpuChecker._place`` shards all six
   ``table_*`` / ``q_*`` buffers on dimension 0 and a chip holds
   1,304,166,400 B of them - a replicated queue column is live bytes the
   allocator's peak sees (PR 50 was refused for 98 MB of them);
 - the compiled mesh step at a small paxos size holds the collectives its
   ``mesh.program`` record says, none of them the size of the queue, and
   the step's placement leaves the one-device program alone.
"""

import json
import os

import numpy as np
import pytest

import jax

from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.buckets import (
    CHAIN_ROOT,
    SLOTS,
    bucket_of,
    parent_chains,
    sharded_parent_chains,
)
from stateright_tpu.ops.hashing import EMPTY
from stateright_tpu.parallel import wavefront
from stateright_tpu.parallel.carry import carry_avals, leaf_names
from stateright_tpu.parallel.mesh import MeshTpuChecker
from stateright_tpu.parallel.partition import StepPlacement, build_mesh
from stateright_tpu.telemetry.collectives import (
    COLLECTIVE_KINDS,
    hlo_collectives,
    shape_bytes,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODELS = {
    "twopc3": (lambda: TwoPhaseSys(3), {"capacity": 1 << 12, "batch": 64}, None),
    "paxos1": (lambda: paxos_model(1), {"capacity": 1 << 12, "batch": 64}, None),
    "paxos2-prefix": (
        lambda: paxos_model(2),
        {"capacity": 1 << 16, "batch": 256, "queue_capacity": 1 << 14}, 6000,
    ),
}


def _spawn(name, devices, telemetry=True):
    factory, spawn, target = MODELS[name]
    b = factory().checker()
    if devices > 1:
        b = b.mesh(devices=devices)
    if target is not None:
        b = b.target_states(target)
    if telemetry:
        b = b.telemetry()
    c = b.spawn_tpu(sync=True, **spawn)
    c.join()
    return c


@pytest.fixture(scope="module", params=sorted(MODELS))
def solo(request):
    return request.param, _spawn(request.param, 1)


def _spans(checker, name):
    return [r for r in checker.flight_recorder.records("span") if r["name"] == name]


@pytest.mark.parametrize("devices", [2, 4])
def test_the_sharded_walk_gives_the_one_device_chains(solo, devices, monkeypatch):
    name, one = solo
    mesh = _spawn(name, devices)
    assert isinstance(mesh, MeshTpuChecker) and mesh.n_devices == devices
    assert mesh.unique_state_count() == one.unique_state_count()
    assert mesh.state_count() == one.state_count()
    # nothing below may pull the table: a discovery's path is walked on it
    monkeypatch.setattr(
        MeshTpuChecker, "_table_np",
        lambda self: pytest.fail("the visited table crossed to the host"),
    )
    table = mesh._device_table()
    assert table is not None and len(table[0].sharding.device_set) == devices
    found, want = mesh.discoveries(), one.discoveries()
    assert sorted(found) == sorted(want) and found
    disc = [int(fp) for fp in one._results["disc"] if int(fp)]
    assert disc == [int(fp) for fp in mesh._results["disc"] if int(fp)]
    for fp in disc:
        assert mesh._trace(fp) == one._trace(fp)
    for prop, path in want.items():
        assert [str(s) for s in found[prop].states()] == [
            str(s) for s in path.states()]
    (parents,), (pull,) = (_spans(mesh, "reconstruct.parents"),
                           _spans(mesh, "reconstruct.pull"))
    assert (parents["path"], parents["shards"]) == ("device", devices)
    assert parents["lookups"] == sum(len(p) for p in found.values())
    assert 0 < pull["bytes"] < 4096
    (alone,) = _spans(one, "reconstruct.parents")
    assert (alone["path"], alone["shards"], alone["lookups"]) == (
        "device", 1, parents["lookups"])


@pytest.mark.parametrize("devices", [2, 4])
def test_drawn_entries_of_a_sharded_table_walk_as_on_one_device(solo, devices):
    name, one = solo
    mesh = _spawn(name, devices, telemetry=False)
    tfp = np.asarray(one._final_carry.table_fp)
    held = tfp[tfp != EMPTY]
    starts = np.random.default_rng(11).choice(held, min(64, len(held)), replace=False)
    bound = 1 << one.max_depth().bit_length()
    want = [np.asarray(x) for x in
            parent_chains(*one._device_table(), starts, bound=bound)]
    table = mesh._device_table()
    walk = sharded_parent_chains(table[0].sharding)
    got = walk(*table, starts, bound)
    # every output replicated: each chip holds the chains
    assert all(x.sharding.is_fully_replicated for x in got)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)
    assert (want[2] == CHAIN_ROOT).all()
    # one program a sharding: a second call compiles nothing
    assert sharded_parent_chains(table[0].sharding) is walk


@pytest.mark.parametrize("devices", [2, 3, 4, 8])
def test_mesh_stats_is_counted_on_the_device_and_equals_the_hosts(devices, monkeypatch):
    c = TwoPhaseSys(4).checker().mesh(devices=devices).spawn_tpu(
        sync=True, capacity=1 << 13, batch=64)
    tfp, tpl = c._table_np()  # the host's count, as mesh_stats made it before
    cap, d = tfp.shape[0], devices
    rows = cap // d if cap % d == 0 else cap
    shard_of = np.arange(cap) // rows
    occupied = tfp != EMPTY
    load = np.bincount(shard_of[occupied], minlength=d)[:d]
    routed = occupied & (tpl != 0)
    parent = bucket_of(tpl[routed], cap // SLOTS) * SLOTS // rows
    route = np.zeros((d, d), np.int64)
    np.add.at(route, (parent, shard_of[np.nonzero(routed)[0]]), 1)
    monkeypatch.setattr(
        MeshTpuChecker, "_table_np",
        lambda self: pytest.fail("mesh_stats pulled the table"),
    )
    c._mesh_stats_cache = None
    got = c.mesh_stats()
    assert got["shard_load"] == load.tolist()
    assert got["route_matrix"] == route.tolist()
    assert got["routed_states"] == int(route.sum()) == c.unique_state_count() - 1
    assert sum(got["shard_load"]) == c.unique_state_count() == 1568
    assert c._results["mesh"] == got
    if cap % d:  # a table the mesh does not divide lies replicated: one owner
        assert got["shard_load"][1:] == [0] * (d - 1)


def test_a_spilled_run_still_takes_the_host_path(monkeypatch):
    from test_spill import _budget_for, _spawn_spill

    c = _spawn_spill(5, _budget_for(5, 1 << 13), monkeypatch,
                     telemetry={"capacity": 1 << 14})
    assert c.spill_status()["evictions"] >= 1 and c._device_table() is None
    assert c.discoveries()
    (parents,) = _spans(c, "reconstruct.parents")
    assert parents["path"] == "host" and "shards" not in parents


# -- the memory bound ------------------------------------------------------------


@pytest.fixture(scope="module")
def cell():
    wl = json.load(open(os.path.join(
        ROOT, "benchmarks", "workloads", "paxos6x4-bounded.json")))
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "paxos6x4.json")))
    return wl, cfg


def test_the_cells_carry_is_sharded_column_by_column(cell):
    """``carry_avals`` at the cell's shapes under ``_place`` on four
    devices: all six ``table_*`` / ``q_*`` buffers split on dimension 0,
    1,304,166,400 B a chip; everything else a scalar or a few words."""
    wl, cfg = cell
    spawn = wl["spawn"]
    tensor = paxos_model(cfg["clients"]).tensor_model()
    assert (tensor.width, tensor.max_actions) == (
        cfg["row"]["width_u64"], cfg["row"]["max_actions"]) == (64, 60)
    avals = carry_avals(tensor, 2, spawn["capacity"], spawn["queue_capacity"],
                        spawn["batch"], checked=False)
    placer = MeshTpuChecker.__new__(MeshTpuChecker)
    placer._mesh = build_mesh(4)
    placed = placer._place(avals)
    per_chip, sharded = {}, []
    for name, aval, sh in zip(leaf_names(avals), jax.tree.leaves(avals),
                              jax.tree.leaves(placed)):
        shard = sh.shard_shape(aval.shape)
        per_chip[name] = int(np.prod(shard, dtype=np.int64)) * aval.dtype.itemsize
        if shard != aval.shape:
            sharded.append(name)
            assert shard == (aval.shape[0] // 4,) + aval.shape[1:]
    assert sharded == ["table_fp", "table_parent", "q_rows", "q_fp",
                       "q_ebits", "q_depth"]
    qalloc = spawn["queue_capacity"] + spawn["batch"] * 60
    assert qalloc == 9_371_648 and avals.q_rows.shape == (qalloc, 64)
    assert {k: per_chip[k] for k in sharded} == {
        "table_fp": 33_554_432, "table_parent": 33_554_432,
        "q_rows": 1_199_570_944, "q_fp": 18_743_296,
        "q_ebits": 9_371_648, "q_depth": 9_371_648,
    }
    assert sum(per_chip[k] for k in sharded) == 1_304_166_400
    # what is replicated is the scalars and the discovery fingerprints
    assert sum(v for k, v in per_chip.items() if k not in sharded) < 128
    # the configuration says the same bytes
    assert "1,304,166,400 B of carry a chip" in cfg["deployment"]["chips"]


SMALL = dict(cap=1 << 16, qcap=1 << 14, batch=256, cand=4096)


@pytest.fixture(scope="module")
def small_step():
    """The mesh step of paxos-2 at a small size, compiled for four virtual
    devices, with the record the engine writes for it."""
    c = (paxos_model(2).checker().mesh(devices=4).target_states(6000)
         .telemetry().spawn_tpu(sync=True, capacity=SMALL["cap"],
                                queue_capacity=SMALL["qcap"],
                                batch=SMALL["batch"], cand=SMALL["cand"]))
    c.join()
    (record,) = c.flight_recorder.records("mesh.program")
    eng = c._engine_cache()[c._engine_key(*SMALL.values())]
    return c, record, eng[1]


def test_the_mesh_program_record_is_what_the_compile_reads(small_step):
    c, record, exe = small_step
    assert isinstance(exe, jax.stages.Compiled)
    text = exe.as_text()
    counted = hlo_collectives(text)
    assert record["collectives"] == counted["collectives"]
    assert set(record["collectives"]) == set(COLLECTIVE_KINDS)
    assert record["collective_count"] == sum(record["collectives"].values()) > 0
    for kind, n in record["collectives"].items():
        assert n == text.count(f" {kind}(") + text.count(f" {kind}-start(")
    assert record["largest"] == counted["largest"]
    assert (record["cap"], record["qcap"], record["batch"], record["cand"],
            record["devices"]) == (*SMALL.values(), 4)
    mem = exe.memory_analysis()
    assert record["argument_bytes"] == mem.argument_size_in_bytes
    assert record["temp_bytes"] == mem.temp_size_in_bytes
    assert record["code_bytes"] == mem.generated_code_size_in_bytes


def test_no_collective_of_the_step_is_the_size_of_the_queue(small_step):
    """The pop and the append move a window's rows between the chips, never
    the queue: the largest collective is the novel rows' all-reduce, and
    every collective keeps the ``sr.*`` stage of the operation it serves
    or is a combined one of a few words."""
    c, record, exe = small_step
    tensor = c.tensor
    qalloc = SMALL["qcap"] + SMALL["batch"] * tensor.max_actions
    queue_bytes = qalloc * tensor.width * 8
    window = SMALL["cand"] * tensor.width * 8
    assert record["largest"]["bytes"] <= window < queue_bytes // 4
    assert "sr.append" in record["largest"]["op"]
    # a chip is handed a quarter of the carry, nothing replicated but words
    avals = c._avals(SMALL["cap"], SMALL["qcap"], SMALL["batch"])
    whole = sum(int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
                for a in jax.tree.leaves(avals))
    assert whole // 4 <= record["argument_bytes"] <= whole // 4 + 256


def test_the_placement_leaves_no_equation_in_the_one_device_step():
    """``place=None`` builds the parent's program: no sharding constraint,
    the pop a dynamic slice and the append a dynamic update slice; with a
    placement the same step pops the queue, and appends to all four of its
    buffers, by row index - inside the same loop over chunks."""
    tensor = TwoPhaseSys(3).tensor_model()
    props = list(TwoPhaseSys(3).properties())
    avals = carry_avals(tensor, len(props), 1 << 10, 1 << 8, 16, checked=False)

    def primitives(place):
        _, run_fn = wavefront._build_engine(
            tensor, props, 1 << 10, 1 << 8, 16, 4, None, place=place)
        names = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                names.append(eqn.primitive.name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(run_fn)(avals).jaxpr)
        return names

    alone = primitives(None)
    assert "sharding_constraint" not in alone
    assert alone.count("dynamic_slice") >= 4
    placed = primitives(StepPlacement(build_mesh(4)))
    assert placed.count("sharding_constraint") >= 10
    assert placed.count("while") == alone.count("while")  # the one body
    # the pop's four windows by index; the chunk of ``sel`` sliced a trip
    # stays (wavefront.append_novel)
    assert placed.count("dynamic_slice") == alone.count("dynamic_slice") - 4
    # all four buffers go in by index, a chunk a trip: no update slice of a
    # sharded buffer is left (StepPlacement.append)
    assert (placed.count("dynamic_update_slice")
            == alone.count("dynamic_update_slice") - 4)
    assert placed.count("scatter") == alone.count("scatter") + 4


@pytest.mark.parametrize("shape,want", [
    ("u32[262144,64]", 262144 * 64 * 4),
    ("(u32[16384], u32[4], pred[8])", 16384 * 4 + 16 + 8),
    ("u64[]", 8),
    ("token[]", 0),
])
def test_shape_bytes_by_hand(shape, want):
    assert shape_bytes(shape) == want


def test_hlo_collectives_by_hand():
    text = "\n".join([
        '  %ag = u32[8,4]{1,0} all-gather(u32[2,4]{1,0} %p), dimensions={0}, '
        'metadata={op_name="jit(f)/sr.pop/gather"}',
        '  %ars = (u32[16]{0}, u32[4]{0}) all-reduce-start(%a, %b), to_apply=%add',
        '  %ard = (u32[16]{0}, u32[4]{0}) all-reduce-done(%ars)',
        '  %f = u32[8]{0} fusion(%x), kind=kLoop, calls=%all-gather.clone',
    ])
    got = hlo_collectives(text)
    assert got["collectives"] == {
        "all-gather": 1, "all-reduce": 1, "all-to-all": 0,
        "collective-permute": 0, "reduce-scatter": 0}
    assert got["collective_count"] == 2
    assert got["largest"] == {"kind": "all-gather", "shape": "u32[8,4]",
                              "bytes": 128, "op": "jit(f)/sr.pop/gather"}
    assert hlo_collectives("")["largest"] is None

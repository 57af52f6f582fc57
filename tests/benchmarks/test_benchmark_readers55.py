"""Tests of the seven per-layer readers PR 55 appended - records the program
has emitted since PRs 47, 48, 51 and 53 and no reader read: CPU-only,
unit-cheap.

 - each reader on a synthetic ``ctx`` of hand-made records gives the
   hand-computed number, gives ``None`` - never 0.0, never a raise - on the
   records of a program that lacks its key (the parent side of a comparison
   prints the line it printed before), and repeats its manifest entry;
 - ``collective_s`` on a hand-made operation table: ``xstages.reduce_stages``
   gains a key from the pass it makes and changes none;
 - each of the seven entries is in the manifest once, listed for the cells
   whose program has something for it to read.

The mesh engine's two counters are read off a real run in
``test_benchmark_paxos6x4.py``'s four-device rehearsal; every other
rehearsal under ``tests/benchmarks/`` prints ``append_trips`` and
``reconstruct_pull_bytes`` (metrics of every cell).
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from srbench import stats, xplane, xstages  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

FOUR_CHIPS, GROWS = ["paxos6x4-bounded"], ["paxos3-defaults", "linreg2x3o-cold"]
# name: (unit, source, layer, the cells it is listed for - None: every cell)
SEVEN = {
    "collective_s": ("s", "device_trace", "GSPMD collectives", FOUR_CHIPS),
    "collective_count": ("count", "program_counter", "GSPMD collectives", FOUR_CHIPS),
    "shard_imbalance": ("%", "program_counter", "GSPMD collectives", FOUR_CHIPS),
    "append_trips": ("count", "program_counter", "kernels", None),
    "reconstruct_pull_bytes": ("bytes", "program_counter",
                               "host trace reconstruction", None),
    "stage_grow_s": ("s", "device_trace", "kernels", GROWS),
    "grow_bytes": ("bytes", "program_counter", "host run loop", GROWS),
}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- the operation table: a collective is its opcode, whatever it is called -------


@pytest.mark.parametrize("name, code, crosses", [
    ("%all-reduce.44 = u32[262144,64]{1,0:T(8,128)} all-reduce(u32[262144,64]{1,0} "
     "%fusion.7), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_1",
     "all-reduce", True),
    ("%all-gather-start.2 = (u32[8]{0}, u32[32]{0}) all-gather-start(u32[8]{0} %p), "
     "dimensions={0}", "all-gather-start", True),
    ("%all-gather-done.2 = u32[32]{0} all-gather-done((u32[8]{0}, u32[32]{0}) "
     "%all-gather-start.2)", "all-gather-done", True),
    ("%collective-permute.5 = u32[64]{0} collective-permute(u32[64]{0} %x), "
     "source_target_pairs={{0,1}}", "collective-permute", True),
    ("%reduce-scatter.1 = u32[16]{0} reduce-scatter(u32[64]{0} %x)", "reduce-scatter", True),
    ("%all-to-all.9 = u32[64]{0} all-to-all(u32[64]{0} %x)", "all-to-all", True),
    # XLA names a fusion after what it holds: still a fusion
    ("%all_reduce_like = u32[8]{0} fusion(u32[8]{0} %a), kind=kLoop", "fusion", False),
    ("%all-reduce-scatter.3 = u32[8]{0} fusion(u32[8]{0} %a), kind=kCustom", "fusion", False),
    # an operand called after a collective does not make the consumer one
    ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %all-reduce.1), calls=%f", "fusion", False),
    ("%reduce.4 = u32[] reduce(u32[8]{0} %x, u32[] %c), to_apply=%sum", "reduce", False),
    # a bare instruction name (XLA:CPU's thunks): the name without its number
    ("all-reduce.3", "all-reduce", True), ("all-gather-done.1", "all-gather-done", True),
    ("all_reduce_like", "all_reduce_like", False), ("copy.3", "copy", False),
])
def test_a_collective_is_told_by_its_opcode(name, code, crosses):
    assert xplane.opcode(name) == code
    assert xstages.is_collective(name) is crosses


def _op(name, scope):
    return {"name": name, "scope": scope, "source": "", "bytes": 0}


# two chips, the step's while over: an append fusion, an asynchronous
# all-reduce whose halves lie 40 ns apart with the scatter between them, a
# combined all-reduce that lost its op_name, and a fusion named like one
TABLE = {
    "w": _op("%while.1 = (s32[]) while(%t)", "jit(run)/while:"),
    "f": _op("%fusion.1 = u32[8] fusion(%a)", "jit(run)/while/body/sr.append/scatter:"),
    "s": _op("%all-reduce-start.1 = (u32[8], u32[8]) all-reduce-start(%f)",
             "jit(run)/while/body/sr.append/psum:"),
    "d": _op("%all-reduce-done.1 = u32[8] all-reduce-done(%s)",
             "jit(run)/while/body/sr.append/psum:"),
    "c": _op("%all-reduce.2 = (u32[8], u32[4]) all-reduce(%x, %y)", ""),
    "l": _op("%all_reduce_like = u32[8] fusion(%a)", "jit(run)/while/body/sr.hash/xor:"),
}
CHIP0 = [("w", 0.0, 200.0), ("s", 10.0, 5.0), ("f", 15.0, 40.0), ("d", 55.0, 15.0),
         ("c", 80.0, 30.0), ("l", 120.0, 60.0)]
CHIP1 = [("w", 0.0, 200.0), ("s", 10.0, 5.0), ("f", 15.0, 40.0), ("d", 55.0, 5.0),
         ("c", 80.0, 10.0), ("l", 120.0, 60.0)]
REDUCED = xstages.reduce_stages({"/device:TPU:0": CHIP0, "/device:TPU:1": CHIP1},
                                TABLE, window=(0.0, 200.0))


def test_collective_seconds_by_hand():
    # the pair counted once: its halves' own 5 + 15 (5 + 5) ns, NOT the 60 ns
    # from the start's start to the done's end; the combined one under
    # ``unnamed`` counted; the fusion named like one not; two chips averaged
    assert REDUCED["collective_stages"] == pytest.approx(
        {"sr.append": (20 + 10) / 2 * 1e-9, "unnamed": (30 + 10) / 2 * 1e-9})
    assert REDUCED["collective_s"] == pytest.approx((50 + 20) / 2 * 1e-9)
    # a part OF the stages, which read as they did without the key
    assert REDUCED["stages"] == pytest.approx({
        "sr.append": (60 + 50) / 2 * 1e-9, "unnamed": (30 + 10) / 2 * 1e-9,
        "sr.hash": 60e-9})
    assert sum(REDUCED["stages"].values()) == pytest.approx(REDUCED["busy_s"])
    assert REDUCED["collective_s"] <= REDUCED["stages"]["sr.append"] + REDUCED["stages"]["unnamed"]
    # the stage table's one line for them: the total and its split by stage
    assert "xstages: collectives" in xstages.report(
        {**REDUCED, "span_s": {}, "idle": {}, "windowed": True})


def test_one_chip_has_no_collective_and_the_report_no_line_for_it():
    one = xstages.reduce_stages(
        {"/device:TPU:0": [("w", 0.0, 100.0), ("f", 10.0, 40.0), ("l", 50.0, 20.0)]},
        TABLE, window=(0.0, 100.0))
    assert one["collective_s"] == 0.0 and one["collective_stages"] == {}
    assert "collectives" not in xstages.report(
        {**one, "span_s": {}, "idle": {}, "windowed": True})


# -- each reader by hand ------------------------------------------------------------


def _step(dsteps, chunks=None, **more):
    r = {"kind": "step", "dsteps": dsteps, "batch": 64, **more}
    return r if chunks is None else {**r, "append_chunks": chunks}


def _pull(nbytes=None):
    r = {"kind": "span", "name": "reconstruct.pull", "dur": 0.001}
    return r if nbytes is None else {**r, "bytes": nbytes}


def _growth(d2h=None, h2d=None):
    r = {"kind": "growth", "status": "table_full", "path": "device"}
    return r if d2h is None else {**r, "d2h_bytes": d2h, "h2d_bytes": h2d}


def _program(count=None, **more):
    r = {"kind": "mesh.program", "devices": 4, **more}
    return r if count is None else {**r, "collective_count": count}


def _ctx(checks, warmup=(), profiled=None, trace=None):
    ctx = {"checks": [{"records": list(r)} for r in checks], "median": stats.median,
           "warmup_records": list(warmup), "cell": {"name": "no-such-cell"},
           "_trace": trace}
    if profiled is not None:
        ctx["profiled"] = {"records": list(profiled)}
    return ctx


OTHER = [{"kind": "compile", "duration": 1.0}, _step(0, 0),
         {"kind": "span", "name": "reconstruct", "dur": 0.01}]
GROWN = {**REDUCED, "stages": {**REDUCED["stages"], "sr.grow": 0.0095}}

# name: (a ctx and the number it reads by hand, a ctx of a program without
# the key).  The mesh's loads: max 1010 over a mean of 1000 = 1.0, 2.5, 0.5
BY_HAND = {
    "collective_s": (_ctx([OTHER], trace=REDUCED), 35e-9,
                     _ctx([OTHER], trace={})),
    "collective_count": (
        _ctx([OTHER + [_program(18)], OTHER], warmup=[_program(20), _program(15)],
             profiled=OTHER), 18.0,
        _ctx([OTHER], warmup=[_program()], profiled=OTHER)),
    "shard_imbalance": (
        _ctx([OTHER + [{"kind": "mesh", "shard_load": load}]
              for load in ([1010, 990, 1000, 1000], [1025, 975, 1000, 1000],
                           [1005, 1000, 1000, 995])]), 1.0,
        _ctx([OTHER + [{"kind": "mesh", "devices": 4}], OTHER])),
    # 8 chunks in 4 steps, 4 in 4, 9 in 4: the median check's 2.0
    "append_trips": (
        _ctx([[_step(0, 0), _step(3, 7), _step(1, 1)], [_step(4, 4)], [_step(4, 9)]]),
        2.0, _ctx([[_step(0), _step(3)]])),
    # two pulls in one check add up; a check without a discovery has none
    "reconstruct_pull_bytes": (
        _ctx([OTHER + [_pull(528), _pull(264)], OTHER + [_pull(528)],
              OTHER + [_pull(1040)], OTHER]), 792.0,
        _ctx([OTHER + [_pull()]])),
    "stage_grow_s": (_ctx([OTHER], trace=GROWN), 0.0095, _ctx([OTHER], trace={})),
    # 5 events of 24 B, none, one of 120 B
    "grow_bytes": (
        _ctx([OTHER + [_growth(16, 8)] * 5, OTHER, OTHER + [_growth(80, 40)]]), 120.0,
        _ctx([OTHER + [_growth()]])),
}


@pytest.fixture
def read(manifest, monkeypatch):
    """``read(name, ctx)``: the reader's own ``read``, its trace the one the
    ctx was made with (``_trace``) in place of a file under ``.bench_trace``."""
    monkeypatch.setattr(xstages, "trace_of", lambda ctx, reader_file: ctx["_trace"] or {})
    return lambda name, ctx: manifest.reader_module(name).read(ctx)


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_the_reader_reads_the_hand_computed_number(read, name):
    ctx, want, _ = BY_HAND[name]
    got = read(name, ctx)
    assert isinstance(got, float) and got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_the_reader_reads_nothing_where_the_program_lacks_its_key(read, name):
    """A parent-era record set: ``None``, not 0.0 and not a raise - and the
    same of a run that recorded nothing at all."""
    assert read(name, BY_HAND[name][2]) is None
    plain = {"checks": [{"check_s": 1.0}], "median": stats.median,
             "cell": {"name": "no-such-cell"}, "_trace": None}
    if name not in ("stage_grow_s", "collective_s"):  # a trace is not a record
        assert read(name, plain) is None


def test_a_check_that_did_not_grow_moved_nothing(read):
    """0, a count: the cells that list the growth readers grow in every check,
    and a tiny cell of the tests that joins their lists may not."""
    assert read("grow_bytes", _ctx([OTHER, OTHER])) == 0.0


def test_the_collective_count_is_the_latest_programs(read):
    # the profiled check's, else the latest window check's, else the warm-up's
    last = _ctx([OTHER + [_program(18)], OTHER + [_program(17)]],
                warmup=[_program(20)], profiled=OTHER + [_program(16)])
    assert read("collective_count", last) == 16.0
    last["profiled"] = {"records": OTHER}
    assert read("collective_count", last) == 17.0
    # a closed loop: the engines are resident from the warm-up, its last rung
    assert read("collective_count", _ctx([OTHER], warmup=[_program(20), _program(15)],
                                         profiled=OTHER)) == 15.0


def test_a_trace_reader_without_a_trace_reads_nothing(manifest):
    ctx = _ctx([OTHER])  # no file under .bench_trace/no-such-cell
    for name in ("collective_s", "stage_grow_s"):
        assert manifest.reader_module(name).read(ctx) is None


# -- the manifest --------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_the_manifest_entry_repeats_the_readers_file(manifest, name):
    unit, source, layer, _ = SEVEN[name]
    reader = manifest.reader_module(name)
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        unit, layer, "check_s", source)
    assert entry["better"] == "lower"
    # none is a share of a roofline or of a peak: a CPU rehearsal prints them
    assert not name.endswith("_roofline") and "mfu" not in name
    # a reader imports nothing of the program
    text = open(manifest.reader_path(name)).read()
    assert "stateright_tpu" not in text.split('"""')[2]


def test_each_of_the_seven_is_listed_for_the_cells_with_something_to_read(manifest):
    """Where the entries lie is ``test_benchmark_room.py``'s to say (they were
    appended after the 46 it holds); here: each is there once, for its cells."""
    names = [m["name"] for m in manifest.doc["per_layer"]]
    assert all(names.count(name) == 1 for name in SEVEN)
    cells = {w["name"] for w in manifest.doc["workloads"]}
    for name, (_, _, _, listed) in SEVEN.items():
        entry = manifest.doc["per_layer"][names.index(name)]
        if listed is None:
            assert "workloads" not in entry  # every cell, a later one too
        else:
            # the committed cells it is listed for; a later cell joins the list
            assert set(listed) <= set(entry["workloads"]) and set(listed) <= cells

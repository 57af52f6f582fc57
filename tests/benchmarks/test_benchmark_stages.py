"""Tests of the per-stage / per-span trace reduction (``srbench/xstages.py``)
and of the readers built on it: CPU-only, unit-cheap.

The arithmetic by hand on synthetic events, the wire-format reader against
``jax.profiler.ProfileData`` on the two traces recorded on a TPU v5e, the
stage sums against a brute-force timeline, and ``run.py`` end to end in
rehearsal mode printing every per-layer metric of the manifest on the tiny
cell.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)

from srbench import stats, xplane, xstages  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

UNNAMED_V5E = os.path.join(DATA, "twopc4_v5e.xplane.pb")  # PR 23: no scopes
NAMED_V5E = os.path.join(DATA, "twopc4_v5e_named.xplane.pb")  # PR 24: named
NOTE = "srbench_traced_check"

NEW_METRICS = (
    "stage_pop_s", "stage_props_s", "stage_expand_s", "stage_hash_s",
    "stage_insert_s", "stage_append_s", "stage_unnamed_pct", "device_steps",
    "batch_fill_pct", "grow_pull_s", "grow_rehash_s", "grow_push_s",
    "reconstruct_pull_s", "reconstruct_parents_s", "reconstruct_replay_s",
    "idle_unspanned_s", "grow_queue_s",
)


# -- names ----------------------------------------------------------------------


def test_the_yardsticks_stage_names_are_the_programs():
    from stateright_tpu.telemetry import spans

    assert xstages.STAGES == spans.STAGES
    assert xstages.SPAN_PREFIX == spans.ANNOTATION_PREFIX
    assert all(s.startswith(xstages.STAGE_PREFIX) for s in xstages.STAGES)


@pytest.mark.parametrize("scope, want", [
    ("jit(wavefront_run)/while/body/sr.insert/while/body/scatter:", "sr.insert"),
    ("jit(wavefront_run)/while/body/sr.hash/jit(_where)/select_n:", "sr.hash"),
    # the FIRST sr. component names the stage
    ("jit(wavefront_run)/while/body/sr.append/sr.insert/gather:", "sr.append"),
    ("jit(wavefront_run)/sr.stats/concatenate:", "sr.stats"),
    ("jit(wavefront_run)/while:", "unnamed"),
    ("jit(_run_impl)/while/body/reduce_and:", "unnamed"),
    ("user.sr.insert/x:", "unnamed"),
    ("", "unnamed"),
])
def test_stage_of_a_scope_path(scope, want):
    assert xstages.stage_of(scope) == want


# -- the arithmetic, by hand ----------------------------------------------------

# one chip: a while spanning an insert fusion and a hash fusion, then a copy
# XLA made itself (no scope), then the insert fusion again
OPS = {
    "w": {"name": "%while.1 = (s32[]) while(%t)", "scope": "jit(wavefront_run)/while:",
          "source": "", "bytes": 7},
    "i": {"name": "%fusion.1 = s32[8] fusion(%a)", "source": "buckets.py:188", "bytes": 100,
          "scope": "jit(wavefront_run)/while/body/sr.insert/scatter:"},
    "h": {"name": "%fusion.2 = u32[8] fusion(%b)", "source": "hashing.py:41", "bytes": 10,
          "scope": "jit(wavefront_run)/while/body/sr.hash/xor:"},
    "c": {"name": "%copy.3 = u32[8] copy(%c)", "scope": "", "source": "", "bytes": 1},
}
EVENTS = [("w", 0.0, 100.0), ("i", 10.0, 20.0), ("h", 40.0, 30.0),
          ("c", 200.0, 50.0), ("i", 260.0, 10.0)]


def test_reduce_stages_by_hand():
    r = xstages.reduce_stages({"/device:TPU:0": EVENTS}, OPS, window=(0.0, 300.0))
    # the while is a container: its 50 ns of self time are not work
    assert r["stages"] == pytest.approx(
        {"sr.insert": 30e-9, "sr.hash": 30e-9, "unnamed": 50e-9}
    )
    assert r["self_s"] == pytest.approx(110e-9) == pytest.approx(r["busy_s"])
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["unnamed_pct"] == pytest.approx(100.0 * 50 / 110)
    # XLA's estimate, once per EXECUTED operation
    assert r["stage_bytes"] == {"sr.insert": 200, "sr.hash": 10, "unnamed": 1}
    assert r["stage_ops"]["sr.insert"] == [
        ["fusion.1 fusion->s32[8]", "buckets.py:188", pytest.approx(30e-9)]
    ]
    assert r["gaps"] == [(0.0, 10.0), (30.0, 40.0), (70.0, 200.0),
                         (250.0, 260.0), (270.0, 300.0)]
    # agrees with the reduction the existing metrics use
    old = xplane.reduce_events(
        {"/device:TPU:0": [(OPS[k]["name"], s, d) for k, s, d in EVENTS]},
        window=(0.0, 300.0),
    )
    assert old["busy_s"] == pytest.approx(r["busy_s"])
    assert xstages.reduce_stages({}, OPS) == {}


def test_reduce_stages_averages_over_chips_and_clips_to_the_window():
    two = {"/device:TPU:0": [("i", 0.0, 100.0)], "/device:TPU:1": [("h", 50.0, 100.0)]}
    r = xstages.reduce_stages(two, OPS, window=(0.0, 100.0))
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((100e-9 + 50e-9) / 2)
    # self time is the operation's own (not clipped), as in xplane.reduce_events
    assert r["stages"] == pytest.approx({"sr.insert": 50e-9, "sr.hash": 50e-9})
    outside = xstages.reduce_stages(two, OPS, window=(200.0, 300.0))
    assert outside["stages"] == {} and outside["busy_s"] == 0.0


SPANS = [
    ("sr/device_call", 0.0, 100.0), ("sr/dispatch", 5.0, 20.0),
    ("sr/wait", 20.0, 95.0), ("sr/grow", 120.0, 180.0),
    ("sr/grow.pull", 130.0, 150.0), ("sr/reconstruct", 300.0, 400.0),
]


def test_innermost_segments_by_hand():
    assert xstages.innermost_segments(SPANS) == [
        (0.0, 5.0, "sr/device_call"), (5.0, 20.0, "sr/dispatch"),
        (20.0, 95.0, "sr/wait"), (95.0, 100.0, "sr/device_call"),
        (120.0, 130.0, "sr/grow"), (130.0, 150.0, "sr/grow.pull"),
        (150.0, 180.0, "sr/grow"), (300.0, 400.0, "sr/reconstruct"),
    ]
    # a span that outlives the one it started in is cut at that one's end
    assert xstages.innermost_segments([("a", 0.0, 10.0), ("b", 5.0, 15.0)]) == [
        (0.0, 5.0, "a"), (5.0, 10.0, "b"),
    ]
    assert xstages.innermost_segments([]) == []


def test_split_gaps_by_hand():
    gaps = [(0.0, 10.0),      # 5 under device_call, 5 under dispatch
            (90.0, 125.0),    # 5 wait, 5 device_call, 20 no span, 5 grow
            (140.0, 160.0),   # 10 grow.pull, 10 grow
            (200.0, 250.0),   # no span at all
            (390.0, 420.0)]   # 10 reconstruct, 20 after it
    got = xstages.split_gaps(gaps, SPANS)
    assert got == pytest.approx({
        "sr/device_call": 10e-9, "sr/dispatch": 5e-9, "sr/wait": 5e-9,
        "sr/grow": 15e-9, "sr/grow.pull": 10e-9, "sr/reconstruct": 10e-9,
        "unspanned": 90e-9,
    })
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in gaps) / 1e9)
    assert xstages.split_gaps(gaps, []) == pytest.approx({"unspanned": 145e-9})


# -- the wire-format reader against ProfileData ---------------------------------


@pytest.mark.parametrize("path", [UNNAMED_V5E, NAMED_V5E], ids=["pr23", "pr24"])
def test_wire_reader_agrees_with_profiledata(path):
    """Same operations, same clock: busy time and the annotation window as
    the existing reduction reads them through ``jax.profiler.ProfileData``."""
    old = xplane.load_trace(path, NOTE)
    new = xstages.load(path, NOTE)
    assert list(new["devices"]) == list(old["devices"]) == ["/device:TPU:0"]
    ours, theirs = new["devices"]["/device:TPU:0"], old["devices"]["/device:TPU:0"]
    assert len(ours) == len(theirs) > 100
    assert [new["ops"][i]["name"] for i, _, _ in ours] == [n for n, _, _ in theirs]
    assert [s for _, s, _ in ours] == pytest.approx([s for _, s, _ in theirs], abs=1.0)
    _, n0, ndur = old["annotation"]
    assert new["annotation"] == pytest.approx((n0, n0 + ndur), abs=1.0)
    a = xstages.analyse(path, NOTE)
    r = xplane.reduce_events(old["devices"], window=(n0, n0 + ndur))
    # ProfileData hands out whole nanoseconds; the file holds picoseconds:
    # half a nanosecond an operation, thousands of operations
    assert a["busy_s"] == pytest.approx(r["busy_s"], rel=5e-4)
    assert a["window_s"] == pytest.approx(r["window_s"], rel=1e-6)


def test_a_trace_without_scopes_reads_all_unnamed():
    """PR 23's trace (the program had no scopes, no host spans): every stage
    reads 0, unnamed 100%, all idle time unspanned — loud, not missing."""
    a = xstages.analyse(UNNAMED_V5E, NOTE)
    assert set(a["stages"]) == {"unnamed"}
    assert a["unnamed_pct"] == pytest.approx(100.0)
    assert a["span_s"] == {} and set(a["idle"]) == {"unspanned"}
    assert a["idle"]["unspanned"] == pytest.approx(a["window_s"] - a["busy_s"])


@pytest.fixture(scope="module")
def named():
    """A whole 2pc-4 check recorded on one TPU v5e with the stage scopes
    and the host spans (PR 24)."""
    return xstages.analyse(NAMED_V5E, NOTE)


def test_named_trace_holds_every_stage_and_host_seam(named):
    assert set(xstages.STAGES) <= set(named["stages"])
    assert {"sr/engine_acquire", "sr/device_call", "sr/dispatch", "sr/wait",
            "sr/reconstruct", "sr/reconstruct.pull", "sr/reconstruct.parents",
            "sr/reconstruct.walk", "sr/reconstruct.replay"} <= set(named["span_s"])
    assert named["windowed"] and named["chips"] == 1
    # the readings taken when the trace was recorded (my chip run, PR 24)
    assert named["stages"]["sr.insert"] == pytest.approx(0.031849373, rel=1e-6)
    assert named["stages"]["sr.append"] == pytest.approx(0.004975853, rel=1e-6)
    assert named["unnamed_pct"] == pytest.approx(1.6221444, rel=1e-6)
    assert named["idle"]["unspanned"] == pytest.approx(0.016302280, rel=1e-6)
    # XLA's bytes estimate is printed, per stage
    assert named["stage_bytes"]["sr.insert"] == 244_358_500
    # the top operation of a stage comes with its source line
    label, source, secs = named["stage_ops"]["sr.insert"][0]
    assert label == "fusion.206 fusion->s32[8192]"
    assert source.endswith("stateright_tpu/ops/buckets.py:188") and secs > 0.01


def test_named_trace_stages_match_a_brute_force_timeline(named):
    """Stages + unnamed against an independent method: paint every leaf
    operation's stage onto a nanosecond grid, later (nested) events over
    earlier ones, and count the cells of each colour."""
    import numpy as np

    t = xstages.load(NAMED_V5E, NOTE)
    n0, n1 = t["annotation"]
    names = sorted(set(xstages.STAGES) | {xstages.UNNAMED})
    grid = np.zeros(int(n1 - n0) + 1, dtype=np.int8)  # 0 = idle
    events = sorted(t["devices"]["/device:TPU:0"], key=lambda e: (e[1], -e[2]))
    for op_id, s, d in events:
        op = t["ops"][op_id]
        a = int(round(max(s, n0) - n0))
        b = int(round(min(s + d, n1) - n0))
        if b <= a:
            continue
        colour = 0 if xplane.is_container(op["name"]) else (
            1 + names.index(xstages.stage_of(op["scope"]))
        )
        grid[a:b] = colour  # a child paints over its parent
    for i, name in enumerate(names):
        painted = float((grid == i + 1).sum())
        assert named["stages"].get(name, 0.0) * 1e9 == pytest.approx(
            painted, rel=5e-3, abs=2000.0
        ), name
    busy = float((grid > 0).sum())
    assert named["self_s"] * 1e9 == pytest.approx(busy, rel=1e-3)
    # the stages and unnamed ARE the busy time
    assert sum(named["stages"].values()) == pytest.approx(named["busy_s"], rel=1e-6)
    # and the idle split is the rest of the window
    assert sum(named["idle"].values()) == pytest.approx(
        named["window_s"] - named["busy_s"], rel=1e-6
    )


def test_report_and_the_command_line(named, capsys):
    text = xstages.report(named)
    assert "sr.insert" in text and "XLA's bytes_accessed estimate" in text
    assert "idle seconds by innermost covering span" in text
    assert xstages.main([NAMED_V5E, NOTE]) == 0
    assert capsys.readouterr().out.strip() == text
    assert xstages.main([]) == 2


# -- the readers on a synthetic context -----------------------------------------


def _reader(name):
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH).reader_module(name)


def _span(name, dur):
    return {"kind": "span", "name": name, "dur": dur}


def _ctx(checks, unique=1000):
    return {"checks": [{"records": r} for r in checks], "median": stats.median,
            "pins": {"unique": unique}, "cell": {"name": "no-such-cell"}}


def test_span_readers_sum_a_checks_spans_and_take_the_median():
    grown = [_span("device_call", 1.0), _span("grow.pull", 0.25),
             _span("grow.pull", 0.5), _span("grow.rehash", 2.0),
             _span("grow.queue", 1.0), _span("grow.push", 0.125),
             _span("reconstruct", 3.0),
             _span("reconstruct.pull", 0.5), _span("reconstruct.parents", 1.5),
             _span("reconstruct.replay", 0.25), _span("reconstruct.replay", 0.25)]
    ctx = _ctx([grown, grown, [_span("device_call", 1.0), _span("reconstruct", 1.0)]])
    want = {"grow_pull_s": 0.75, "grow_rehash_s": 2.0, "grow_push_s": 0.125,
            "grow_queue_s": 1.0,
            "reconstruct_pull_s": 0.5, "reconstruct_parents_s": 1.5,
            "reconstruct_replay_s": 0.5}
    for metric, value in want.items():
        assert _reader(metric).read(ctx) == pytest.approx(value), metric
    # a presized cell: the seams are there, no growth happened -> 0, not None
    presized = _ctx([[_span("device_call", 1.0), _span("reconstruct", 1.0)]])
    assert _reader("grow_rehash_s").read(presized) == 0.0
    assert _reader("reconstruct_pull_s").read(presized) == 0.0
    # a program without the seams (the parent commit): nothing to read
    parent = _ctx([[{"kind": "step", "engine": "wavefront"},
                    {"kind": "span", "name": "engine_run", "dur": 1.0}]])
    for metric in want:
        assert _reader(metric).read(parent) is None, metric


def test_device_steps_and_batch_fill_by_hand():
    def step(dsteps, batch=64):
        return {"kind": "step", "dsteps": dsteps, "batch": batch}

    ctx = _ctx([[step(0), step(10), step(6)], [step(0), step(10), step(6)]],
               unique=768)
    assert _reader("device_steps").read(ctx) == 16.0
    assert _reader("batch_fill_pct").read(ctx) == pytest.approx(100.0 * 768 / (16 * 64))
    # a growth ladder may change the batch between records
    mixed = _ctx([[step(0), step(10, 32), step(5, 64)]], unique=320)
    assert _reader("batch_fill_pct").read(mixed) == pytest.approx(100.0 * 320 / 640)
    parent = _ctx([[{"kind": "step", "engine": "wavefront"}]])
    assert _reader("device_steps").read(parent) is None
    assert _reader("batch_fill_pct").read(parent) is None


def test_trace_readers_without_a_trace_read_nothing():
    ctx = _ctx([[]])
    for metric in ("stage_insert_s", "stage_unnamed_pct", "idle_unspanned_s"):
        assert _reader(metric).read(ctx) is None, metric


# -- run.py end to end (rehearsal): every new metric on the tiny cell -----------


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The manifest as it is plus the tests' tiny cell, in a directory of
    its own (the README's recipe, as ``test_benchmark_own.py`` does it)."""
    root = tmp_path_factory.mktemp("bench_stages")
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(DATA, "twopc3.json"), bench / "configs")
    shutil.copy(os.path.join(DATA, "twopc3-tiny.json"), bench / "workloads")
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    doc["configs"].append({
        "name": "twopc3", "source": "stateright examples/2pc.rs",
        "file": "benchmarks/configs/twopc3.json", "reduced": ["rm_count"],
        "why": "tiny",
    })
    doc["workloads"].append({
        "name": "twopc3-tiny", "config": "twopc3", "traffic": "tiny",
        "chips": 1, "why": "rehearsal of the harness on the CPU",
    })
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("twopc3-tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(str(root / "BENCHMARK.json"), str(bench)).problems() == []
    return root, doc


@pytest.fixture(scope="module")
def rehearsal(tiny_benchmark):
    """One traced rehearsal from an EMPTY compile cache: XLA:CPU keeps the
    scope paths only in an executable it compiled itself."""
    root, doc = tiny_benchmark
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / "jax_cache_stages")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "twopc3-tiny", "--seed", "2147483747", "--seconds", "0.5", "--trace", "1",
         "--manifest", str(root / "BENCHMARK.json"),
         "--bench-dir", str(root / "benchmarks"), "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(root),
    )
    assert p.returncode == 2, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    out = json.loads(last.split("(no result line): ", 1)[1])
    assert out["correct"] is True
    return p, out, doc


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_rehearsal_prints_every_new_metric_on_the_tiny_cell(rehearsal, metric):
    _, out, doc = rehearsal
    entry = next(m for m in doc["per_layer"] if m["name"] == metric)
    assert "workloads" not in entry  # every cell, the tiny one included
    got = out["metrics"][metric]
    assert got["unit"] == entry["unit"] and got["value"] >= 0.0


def test_rehearsal_numbers_hang_together(rehearsal):
    p, out, _ = rehearsal
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # 2pc-3 at batch 64: 288 unique states, each popped once
    assert m["device_steps"] >= 288 / 64 and m["device_steps"] == int(m["device_steps"])
    assert m["batch_fill_pct"] == pytest.approx(100.0 * 288 / (m["device_steps"] * 64))
    assert m["grow_pull_s"] == m["grow_rehash_s"] == m["grow_push_s"] == 0.0
    assert m["grow_queue_s"] == 0.0
    assert m["reconstruct_parents_s"] > 0 and m["reconstruct_replay_s"] > 0
    phases = (m["reconstruct_pull_s"] + m["reconstruct_parents_s"]
              + m["reconstruct_replay_s"])
    assert phases <= m["reconstruct_s"]  # the harness's span holds them all
    # the stage table went to stderr (stdout stays the labelled lines)
    assert "xstages:   sr.insert" in p.stderr and "xstages:" not in p.stdout
    stages = sum(m[f"stage_{s}_s"] for s in
                 ("pop", "props", "expand", "hash", "insert", "append"))
    assert stages > 0 and m["stage_insert_s"] > 0
    assert 0.0 <= m["stage_unnamed_pct"] < 100.0
    assert m["idle_unspanned_s"] < out["device"]["window_s"]

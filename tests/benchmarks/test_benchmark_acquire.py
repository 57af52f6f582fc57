"""The readers of the engine-acquisition seam (``srbench/xacquire.py`` and
the six ``acquire_*`` / ``programs_loaded_check`` files under
``layer_metrics/``): self time by ``parent_id`` on a canned context,
nothing where the seam is absent, the enqueues and zeros where the engines
were resident, the manifest entries, and the same arithmetic on the records of a real tiny check.
CPU-only, unit-cheap.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from srbench import stats, xacquire  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

WARMUP = ("acquire_trace_s", "acquire_lower_s")
CHECK = ("acquire_trace_check_s", "acquire_lower_check_s",
         "acquire_retrieval_check_s", "programs_loaded_check")
COLD = "linreg2x3o-cold"


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


def _span(name, dur, span_id=None, parent_id=None, **attrs):
    out = {"kind": "span", "name": name, "dur": dur,
           "span_id": span_id or f"{name}-{dur}", **attrs}
    if parent_id is not None:
        out["parent_id"] = parent_id
    return out


def _fresh(scale=1.0):
    """One check on a fresh object: two device calls that each acquired a
    program (the second also a helper), one that acquired none, and a
    program built ahead of time under its ``engine_acquire``."""
    s = scale
    return [
        {"kind": "step"},
        _span("engine_acquire", 0.5 * s, "acq"),
        _span("program.lower", 0.125 * s, parent_id="acq"),
        _span("program.load", 0.25 * s, parent_id="acq", hit=True,
              retrieved_s=0.125 * s),
        _span("dispatch", 1.0 * s, "d1", "c1", jaxprs_traced=100),
        _span("wait", 0.5, parent_id="c1"),
        _span("program.lower", 0.25 * s, parent_id="d1"),
        _span("program.load", 0.5 * s, parent_id="d1", hit=True,
              retrieved_s=0.25 * s),
        _span("dispatch", 2.0 * s, "d2", "c2", jaxprs_traced=300),
        _span("program.lower", 0.5 * s, parent_id="d2"),
        _span("program.load", 0.25 * s, parent_id="d2", hit=True,
              retrieved_s=0.125 * s),
        _span("program.load", 0.125 * s, parent_id="d2", hit=False),
        _span("dispatch", 0.0625 * s, "d3", "c3", jaxprs_traced=0),
        {"kind": "compile", "duration": 1.125 * s},
    ]


def _resident():
    return [_span("dispatch", 0.001, "d", "c", jaxprs_traced=0),
            _span("wait", 0.5, parent_id="c")]


def _ctx(checks, warmup=()):
    return {"checks": [{"records": r} for r in checks],
            "warmup_records": list(warmup), "median": stats.median}


def test_split_takes_self_time_by_parent_id():
    got = xacquire.split(_fresh())
    assert sorted(got) == sorted(xacquire.KEYS)
    # dispatch 3.0625 - children of a dispatch (0.75 + 0.875); the
    # engine_acquire's children are no dispatch's
    assert got["trace_s"] == 3.0625 - 1.625 == 1.4375
    assert got["lower_s"] == 0.875 and got["load_s"] == 1.125
    assert got["retrieved_s"] == 0.5 and got["programs"] == 4.0
    # what the dispatch spans hold adds up to them
    in_dispatch = got["lower_s"] + got["load_s"] - 0.125 - 0.25
    assert got["trace_s"] + in_dispatch == 3.0625


@pytest.mark.parametrize("records", [
    [],
    [{"kind": "step"}, {"kind": "compile", "duration": 1.0}],
    # a program from before the split: the seam is there, its inside is not
    [_span("dispatch", 4.0, "d", "c"), _span("wait", 1.0, parent_id="c")],
], ids=["no-records", "no-dispatch", "dispatch-without-its-inside"])
def test_where_the_seam_is_not_split_there_is_nothing_to_read(manifest, records):
    assert xacquire.split(records) is None
    ctx = _ctx([records, records], warmup=records)
    for name in WARMUP + CHECK:
        assert manifest.reader_module(name).read(ctx) is None, name
    assert manifest.reader_module(WARMUP[0]).read({}) is None


def test_the_warm_up_readers_read_the_warm_up_check(manifest):
    ctx = _ctx([_resident()], warmup=_fresh())
    assert manifest.reader_module("acquire_trace_s").read(ctx) == 1.4375
    assert manifest.reader_module("acquire_lower_s").read(ctx) == 0.875
    # the window's checks ran on resident engines: the enqueue, and zeros
    # (a number, not nothing: test_benchmark_singlecopy's rehearsal lists
    # every reader of the cold cell for a closed one and wants each printed)
    assert [manifest.reader_module(name).read(ctx) for name in CHECK] == [
        0.001, 0.0, 0.0, 0.0]


def test_the_check_readers_take_the_median_over_the_windows_checks(manifest):
    ctx = _ctx([_fresh(1.0), _fresh(4.0), _fresh(2.0)], warmup=_resident())
    want = {"acquire_trace_check_s": 2 * 1.4375, "acquire_lower_check_s": 2 * 0.875,
            "acquire_retrieval_check_s": 2 * 0.5, "programs_loaded_check": 4.0}
    for name, value in want.items():
        got = manifest.reader_module(name).read(ctx)
        assert got == value and isinstance(got, float), name
    # a warm-up check that acquired nothing reads its enqueues, no lowering
    assert manifest.reader_module("acquire_trace_s").read(ctx) == 0.001
    assert manifest.reader_module("acquire_lower_s").read(ctx) == 0.0


@pytest.mark.parametrize("name", WARMUP + CHECK)
def test_the_manifest_entries(manifest, name):
    """Asked of the manifest, not pinned: a later cell may join a list."""
    from srbench import check as chk

    (entry,) = [m for m in manifest.doc["per_layer"] if m["name"] == name]
    assert entry["layer"] == "engine set-up"
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    cells = [w["name"] for w in manifest.doc["workloads"]
             if name in {m["name"] for m in manifest.metrics_for("per_layer", w["name"])}]
    if name in WARMUP:
        # every cell's warm-up check is a fresh object: no list
        assert entry["moves"] == "setup_s" and "workloads" not in entry
        assert cells == [w["name"] for w in manifest.doc["workloads"]]
    else:
        # only a check on a model object of its own acquires anything
        assert entry["moves"] == "check_s" and COLD in cells
        assert {chk.loop_kind(manifest.workload(c)) for c in cells} == {"cold"}


def test_a_real_checks_records_add_up_to_its_dispatch_spans(manifest):
    """The program's own records through the readers: on a fresh object the
    three parts of the seam are the ``dispatch`` spans, and the load is what
    ``engine_acquire_s`` / ``acquire_check_s`` read from the ``compile``
    records; a re-check on the resident engine reads its enqueues."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(3)
    kw = dict(sync=True, capacity=1 << 12, batch=64)
    fresh = model.checker().telemetry().spawn_tpu(**kw).flight_recorder.records()
    again = model.checker().telemetry().spawn_tpu(**kw).flight_recorder.records()
    got = xacquire.split(fresh)
    dispatch = sum(r["dur"] for r in fresh
                   if r["kind"] == "span" and r["name"] == "dispatch")
    assert got["programs"] >= 2 and got["retrieved_s"] == 0.0  # no cache: misses
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["load_s"] > 0
    assert got["trace_s"] + got["lower_s"] + got["load_s"] == pytest.approx(dispatch)
    ctx = _ctx([fresh, fresh], warmup=fresh)
    assert manifest.reader_module("engine_acquire_s").read(ctx) == pytest.approx(
        got["load_s"], abs=1e-4)
    assert manifest.reader_module("acquire_check_s").read(ctx) == pytest.approx(
        got["load_s"], abs=1e-4)
    assert manifest.reader_module("acquire_trace_check_s").read(ctx) == got["trace_s"]
    assert manifest.reader_module("programs_loaded_check").read(ctx) == got["programs"]
    resident = xacquire.split(again)
    assert resident["programs"] == 0.0 and resident["lower_s"] == 0.0
    assert resident["trace_s"] < 0.1
    ctx = _ctx([again], warmup=fresh)
    assert manifest.reader_module("programs_loaded_check").read(ctx) == 0.0
    assert manifest.reader_module("acquire_lower_check_s").read(ctx) == 0.0
    assert manifest.reader_module("acquire_trace_check_s").read(ctx) == resident["trace_s"]

"""Tests of the loop's kinds and of what ``correct`` is decided by.

``loop.kind`` in a workload file names the unit of work: ``closed`` (one
model object, re-checked) or ``cold`` (a model object built inside every
check's timed span).  Rehearsed on the CPU on tiny cells added AS FILES:
the ABD register with 2 clients over 2 replicas through the compiled actor
twin (cold), 2pc-5 under ``.symmetry()`` (the exactness sample there).  Then the two proofs
that ``correct`` can come out false: the CONTROL (a workload that breaks
the configuration's first guarantee — the whole space, no bound) and a run
whose timed path is broken underneath the harness.  CPU-only, unit-cheap.
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
RUN = os.path.join(BENCH, "run.py")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from srbench import reference  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

TAG = "[CPU REHEARSAL - not a chip result] "
# a reader added as a file: in how many of the window's checks the ring
# holds the actor compiler's span AND engine acquisitions of its own
OWN_OBJECTS = '''UNIT = "count"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_counter"


def read(ctx):
    def own(records):
        spans = [r for r in records if r["kind"] == "span"]
        return (any(r["name"] == "twin_compile" for r in spans)
                and any(r["kind"] == "compile" for r in records))

    return float(sum(own(c.get("records", [])) for c in ctx["checks"]))
'''


def _bench(tmp_path_factory, name, cells, twin=()):
    """The manifest as it is plus tiny cells, each ``(cell, config)`` a
    pair of files under ``data/``; ``twin`` names the cells whose twin the
    actor compiler makes (they join those metrics' ``workloads`` lists)."""
    root = tmp_path_factory.mktemp(name)
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    (bench / "layer_metrics" / "own_object_checks.py").write_text(OWN_OBJECTS)
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for cell, config in cells:
        shutil.copy(os.path.join(DATA, f"{cell}.json"), bench / "workloads")
        wl = json.load(open(os.path.join(DATA, f"{cell}.json")))
        doc["workloads"].append({
            "name": cell, "config": config, "traffic": wl["traffic"],
            "chips": 1, "why": "a tiny cell of the benchmark's own tests",
        })
        if all(c["name"] != config for c in doc["configs"]):
            shutil.copy(os.path.join(DATA, f"{config}.json"), bench / "configs")
            cfg = json.load(open(os.path.join(DATA, f"{config}.json")))
            doc["configs"].append({
                "name": config, "source": "stateright examples",
                "file": f"benchmarks/configs/{config}.json",
                "reduced": cfg["reduced"], "why": "tiny",
            })
    doc["per_layer"].append({
        "name": "own_object_checks", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine set-up",
        "moves": "check_s", "workloads": list(twin),
    })
    for m in doc["per_layer"]:
        if "linreg2x3o-cold" in m.get("workloads", []):
            m["workloads"] += list(twin)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(str(root / "BENCHMARK.json"), str(bench)).problems() == []
    return root, doc


def _env(root):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / "jax_cache")
    env.pop("XLA_FLAGS", None)
    return env


def _rehearse(root, cell, trace=0, prelude=None, seconds=0.5):
    """``run.py`` in rehearsal mode; with ``prelude``, Python source run
    first in the same process (what breaks the timed path underneath)."""
    argv = ["--workload", cell, "--seed", "2147483801", "--seconds", repr(seconds),
            "--trace", str(trace), "--manifest", str(root / "BENCHMARK.json"),
            "--bench-dir", str(root / "benchmarks"), "--rehearse-cpu"]
    if prelude is None:
        cmd = [sys.executable, RUN, *argv]
    else:
        code = (f"import sys, runpy\nsys.path.insert(0, {BENCH!r})\n"
                f"sys.path.insert(0, {REPO!r})\n{prelude}\n"
                f"sys.argv = [{RUN!r}] + {argv!r}\n"
                f"runpy.run_path({RUN!r}, run_name='__main__')\n")
        cmd = [sys.executable, "-c", code]
    return subprocess.run(cmd, env=_env(root), capture_output=True, text=True,
                          timeout=300, cwd=str(root))


def _result(p):
    assert p.returncode == 2, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert "rehearsal complete (no result line): " in last
    return json.loads(last.split("(no result line): ", 1)[1])


def _compared_lines(p):
    """The run's last stderr lines: each compared number beside its limit."""
    tail = []
    for ln in reversed(p.stderr.strip().splitlines()):
        if not ln.startswith(TAG + "compared: "):
            break
        name, limit = ln[len(TAG + "compared: "):].split(" limit=")
        key, value = name.split("=")
        tail.append((key, float(value), float(limit)))
    return list(reversed(tail))


# -- the kinds ------------------------------------------------------------------


@pytest.mark.parametrize("workload, want", [
    ({}, "closed"),
    ({"loop": {}}, "closed"),
    ({"loop": {"clients": 1}}, "closed"),
    ({"loop": {"kind": "closed"}}, "closed"),
    ({"loop": {"kind": "cold"}}, "cold"),
])
def test_loop_kind_defaults_to_closed(workload, want):
    assert chk.loop_kind(workload) == want


def test_an_unknown_kind_is_an_error_that_names_the_kinds():
    with pytest.raises(ValueError, match="closed, cold"):
        chk.loop_kind({"loop": {"kind": "bursty"}})


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


def test_every_committed_workload_names_a_known_kind(manifest):
    """Asks the manifest, does not pin it: no count of cells, no claim
    about the kind of a cell this test does not name."""
    kinds = {w["name"]: chk.loop_kind(manifest.workload(w["name"]))
             for w in manifest.doc["workloads"]}
    assert set(kinds.values()) <= set(chk.LOOP_KINDS)
    assert kinds["linreg2x3o-cold"] == "cold"


# -- the cold loop, rehearsed ---------------------------------------------------


@pytest.fixture(scope="module")
def cold_bench(tmp_path_factory):
    return _bench(
        tmp_path_factory, "bench_cold",
        [("linreg2x2o-cold", "linreg2x2o"), ("linreg2x2o-bounded", "linreg2x2o")],
        twin=["linreg2x2o-cold", "linreg2x2o-bounded"],
    )


@pytest.fixture(scope="module")
def cold_traced(cold_bench):
    root, doc = cold_bench
    p = _rehearse(root, "linreg2x2o-cold", trace=1)
    return p, _result(p), doc


def test_the_tiny_cold_pins_are_the_plain_references(cold_bench):
    root, _ = cold_bench
    cfg = json.load(open(root / "benchmarks" / "configs" / "linreg2x2o.json"))
    model = chk.build_model(cfg)
    got = reference.reference_bfs(model)
    assert got == {k: cfg["pins"][k] for k in got}
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}


def test_cold_rehearsal_is_correct_and_labelled(cold_traced):
    p, out, _ = cold_traced
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert "traffic=cold" in p.stdout and " loop=cold" in p.stdout
    assert "unique=564 generated=813 depth=24" in p.stdout
    assert "missing=0" in p.stdout
    assert all(ln.startswith(TAG) for ln in p.stdout.splitlines() if ln.strip())


def test_every_cold_check_asks_for_the_same_programs_and_compiles_none(cold_traced):
    p, out, _ = cold_traced
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith(TAG + "check ")]
    assert len(lines) == out["attempted"]
    asked = {ln.split("compile_requests=")[1].split()[0] for ln in lines}
    assert len(asked) == 1 and int(asked.pop()) >= 1
    assert all("(persistent misses 0)" in ln and " build=" in ln for ln in lines)
    assert "'persistent_misses': 0" in p.stdout.split("window compiles=")[1]


def test_every_cold_check_is_made_on_a_model_object_of_its_own(cold_traced):
    _, out, _ = cold_traced
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the ring of EVERY window check holds the compiler's span and
    # acquisitions of its own: nothing was resident
    assert m["own_object_checks"] == out["attempted"]
    assert m["acquire_check_s"] > 0 and m["fingerprint_bridge_s"] > 0
    assert m["dispatch_s"] > 0
    assert 0 < m["twin_compile_check_s"] < 60 and 0 < m["twin_compile_s"] < 60 and m["twin_table_bytes"] > 0


def test_cold_traced_line_has_the_cells_metrics_and_the_compared_numbers_last(
    cold_traced
):
    p, out, doc = cold_traced
    assert list(out)[-1] == "compared" and list(out)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    manifest = Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)
    want = {m["name"] for m in manifest.metrics_for("per_layer", "linreg2x3o-cold")}
    assert_a_rehearsal_prints(want | {"own_object_checks"}, out["metrics"])
    compared = out["compared"]
    assert set(compared) == {
        "unique_off", "generated_off", "max_depth_off", "discoveries_off",
        "paths_off", "growth_off", "sample_missing",
        "window_persistent_misses", "compile_requests_spread",
        "checks_without_compile_requests"}
    assert all(c["value"] == 0 and c["limit"] == 0 for c in compared.values())
    # ... and they are the last lines on stderr, number beside limit
    assert _compared_lines(p) == [(k, 0.0, 0.0) for k in compared]


def test_cold_plain_line_reports_no_gen_rate(cold_bench):
    root, _ = cold_bench
    out = _result(_rehearse(root, "linreg2x2o-cold", trace=0))
    assert out["correct"] is True
    # a host-bound cell: no rate of the search (and no memory statistics on
    # a CPU, so no peak_hbm here)
    assert set(out["metrics"]) == {"check_s", "setup_s"}
    assert "breakdown" not in out and list(out)[-1] == "compared"


def test_an_unknown_kind_exits_before_any_work(cold_bench):
    root, _ = cold_bench
    path = root / "benchmarks" / "workloads" / "linreg2x2o-cold.json"
    good = path.read_text()
    wl = json.loads(good)
    wl["loop"]["kind"] = "bursty"
    path.write_text(json.dumps(wl))
    try:
        p = _rehearse(root, "linreg2x2o-cold")
    finally:
        path.write_text(good)
    assert p.returncode == 1 and p.stdout.strip() == ""
    assert "unknown loop.kind 'bursty'" in p.stderr and "closed, cold" in p.stderr


# -- correct can come out false: the control, and a broken timed path ---------------


def test_the_control_a_bounded_search_is_not_correct(cold_bench):
    """The configuration guarantees the WHOLE reachable space, no bound;
    the control breaks exactly that (``target_states``) and runs the same
    loop: it must fail a compared number, not crash."""
    root, _ = cold_bench
    p = _rehearse(root, "linreg2x2o-bounded")
    out = _result(p)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert {"unique_off", "generated_off", "sample_missing"} <= over
    assert "NOT CORRECT" in p.stdout
    assert any(v > lim for _, v, lim in _compared_lines(p))


BROKEN_ANSWER = '''
from srbench import check as chk

real = chk.builder_for


class OneShort:
    """The checker, with one answer altered where it is produced."""

    def __init__(self, checker):
        self._checker = checker

    def __getattr__(self, name):
        return getattr(self._checker, name)

    def unique_state_count(self):
        return self._checker.unique_state_count() - 1


class Builder:
    def __init__(self, builder):
        self._builder = builder

    def spawn_tpu(self, **kw):
        return OneShort(self._builder.spawn_tpu(**kw))


chk.builder_for = lambda *a, **kw: Builder(real(*a, **kw))
'''


def test_a_timed_path_that_alters_an_answer_is_not_correct(cold_bench):
    """The rest of a run, past the look for a chip, with the timed path
    broken underneath: every check reports one unique state too few."""
    root, _ = cold_bench
    p = _rehearse(root, "linreg2x2o-cold", prelude=BROKEN_ANSWER)
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["unique_off"] == {"value": 1, "limit": 0}
    others = {k: c for k, c in out["compared"].items() if k != "unique_off"}
    assert all(c["value"] <= c["limit"] for c in others.values())
    assert "unique 563 != pinned 564" in p.stdout
    assert ("unique_off", 1.0, 0.0) in _compared_lines(p)


# -- the exactness sample under .symmetry() -------------------------------------


@pytest.fixture(scope="module")
def sym_bench(tmp_path_factory):
    return _bench(tmp_path_factory, "bench_sym", [("twopc5-sym", "twopc5")])


def test_the_symmetric_pins_are_the_fifo_representative_references(sym_bench):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    root, _ = sym_bench
    cfg = json.load(open(root / "benchmarks" / "configs" / "twopc5.json"))
    got = reference.reference_bfs(chk.build_model(cfg), symmetric=True)
    assert got == {k: cfg["pins"][k] for k in got}
    assert got["unique"] == 508  # tests/test_tensor_models.py's device count
    # the unreduced space is another number
    assert reference.reference_bfs(TwoPhaseSys(5))["unique"] == 8832


def test_symmetric_rehearsal_is_correct_with_nothing_missing(sym_bench):
    root, _ = sym_bench
    p = _rehearse(root, "twopc5-sym")
    out = _result(p)
    assert out["correct"] is True and out["failed"] == 0
    assert "unique=508 generated=3174 depth=16" in p.stdout
    assert "symmetry kept<=16384 states=508 visited=508 missing=0" in p.stdout
    assert out["compared"]["sample_missing"] == {"value": 0, "limit": 0}
    # the reference's whole search is made once the window has closed: not
    # inside setup_s
    lines = p.stdout.splitlines()
    at = {key: next(i for i, ln in enumerate(lines) if key in ln)
          for key in ("window: ", "exactness sample: ")}
    assert at["window: "] < at["exactness sample: "]
    assert " loop=" not in p.stdout  # a closed cell prints what it printed


def test_the_visited_set_under_symmetry_holds_kept_representatives_not_walks():
    """What the repair is told from its absence by: the visited set of a
    symmetric check lacks the PLAIN fingerprints of reachable states, and —
    2pc's representative not being class-invariant — even the fingerprints
    of the walked states' representatives; it holds exactly what the FIFO
    representative search keeps, each named on the host objects alone."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(5)
    checker = model.checker().symmetry().spawn_tpu(
        sync=True, capacity=4096, batch=64)
    checker.join()
    visited = chk.visited_fingerprints(checker)
    assert len(visited) == checker.unique_state_count() == 508
    plain = reference.random_walk_fingerprints(model, 7, 256)
    assert len(plain) == 16640 and chk.missing_from(visited, plain) == 13957

    class AsRepresentatives:
        """The walks again, each state named through its representative."""

        def __getattr__(self, name):
            return getattr(model, name)

        def fingerprint_state(self, s):
            return model.fingerprint_state(s.representative())

    as_reps = reference.random_walk_fingerprints(AsRepresentatives(), 7, 256)
    assert 0 < chk.missing_from(visited, as_reps) < 13957
    kept = reference.kept_fingerprints(model, 7, 16384)
    assert len(kept) == 508 and chk.missing_from(visited, kept) == 0
    assert sorted(kept) == [int(v) for v in visited]


def test_the_symmetric_sample_is_a_seeded_draw_over_every_depth():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(5)
    kept = []
    reference.reference_bfs(model, symmetric=True, kept=kept)
    order = [model.fingerprint_state(s.representative()) for s in kept]
    a = reference.kept_fingerprints(model, 7, 100)
    assert a == reference.kept_fingerprints(model, 7, 100) and len(set(a)) == 100
    assert a != reference.kept_fingerprints(model, 8, 100)
    assert set(a) <= set(order)
    # not the search's first hundred: the draw reaches its last fifth
    assert set(a) != set(order[:100]) and set(a) & set(order[-100:])


def test_a_twin_that_canonicalises_wrongly_does_not_agree_with_itself():
    """The sample names classes on the HOST objects: a visited set built
    from another canonical form lacks them, whatever the twin would say of
    its own rows."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(5)
    kept = []
    reference.reference_bfs(model, symmetric=True, kept=kept)
    import numpy as np

    wrong = np.sort(np.asarray(  # a "canonicaliser" that is the identity
        [model.fingerprint_state(s) for s in kept], dtype=np.uint64))
    sample = reference.kept_fingerprints(model, 7, 16384)
    assert chk.missing_from(wrong, sample) > 0

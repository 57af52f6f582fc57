"""Tests of what the ``twopc13sym`` configuration added to the benchmark:
the configuration and cell files against the deployment the factory
builds, the device canonicaliser against the host's at the REAL width, the
two proofs that ``correct`` comes out false under ``.symmetry()`` (a twin
whose canonicaliser is broken, and the ``target_states`` control), and the
two readers that make the cell legible (``stage_hash_roofline`` with its
bytes function, ``cand_fill_pct``) by hand.  CPU-only, unit-cheap.
"""

import json
import os
import random
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from srbench import hash_bytes, stats  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_loops import (  # noqa: E402
    _bench, _compared_lines, _rehearse, _result)

CELL = "twopc13sym-presized"


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- the configuration and the cell ---------------------------------------------


def test_the_symmetric_configuration_is_the_deployment_the_factory_builds(manifest):
    entry = manifest.config_entry("twopc13sym")
    cfg = manifest.config("twopc13sym")
    assert entry["reduced"] == cfg["reduced"] == []  # nothing is cut: N is the user's
    assert entry["source"] == cfg["source"] and "check-sym" in cfg["source"]
    assert cfg["model"]["args"] == [cfg["rm_count"]] == [13]
    assert cfg["deployment"]["resource_managers"] == 13
    assert set(cfg["assumed"]) >= {"rm_count", "sizes_by_rm_count", "device_twin",
                                   "visit_order"}
    model = chk.build_model(cfg)
    assert model.rm_count == 13
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert cfg["row"] == {"width_u64": 1, "max_actions": 2 + 5 * 13}
    pins = cfg["pins"]
    assert (pins["unique"], pins["generated"], pins["max_depth"]) == (163004, 2407522, 40)
    assert pins["discoveries"] == ["abort agreement", "commit agreement"]
    # the pinned size is the table's own row, and the sizing rule's two sides
    sizes = cfg["assumed"]["sizes_by_rm_count"]
    assert sizes["13"][:3] == [pins["unique"], pins["generated"], pins["max_depth"]]
    from stateright_tpu.parallel._base import SMALL_SPACE_BREAK_EVEN

    assert sizes["12"][0] < SMALL_SPACE_BREAK_EVEN < pins["unique"]
    assert sizes["13"][3] < manifest.doc["run_seconds"] < sizes["14"][3]
    # both derivations are named, and neither is the device's nor a DFS's
    assert "reference_bfs" in pins["provenance"]
    assert "host_fifo_sym_oracle" in pins["provenance"]
    assert all("representatives" in g or "discover" in g for g in cfg["guarantees"])


def test_the_symmetric_cell_is_presized_for_the_pinned_space(manifest):
    cell, wl = manifest.cell(CELL), manifest.workload(CELL)
    pins = manifest.config("twopc13sym")["pins"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("twopc13sym", "presized", 1)
    assert wl["builder"] == [{"verb": "symmetry"}]  # exactly the symmetry verb
    assert chk.loop_kind(wl) == "closed" and wl["expect_growth"] == "none"
    spawn = wl["spawn"]
    assert spawn["queue_capacity"] >= pins["unique"]  # every kept row fits
    assert pins["unique"] / spawn["capacity"] < 0.1  # the table's load
    assert all(v & (v - 1) == 0 for v in spawn.values())  # powers of two
    names = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert {"stage_hash_s", "stage_hash_roofline", "cand_fill_pct"} <= names
    # a hand twin on a resident engine: none of the compiled twin's own
    # readers, none of the cold loop's
    assert not names & {"twin_compile_s", "twin_table_bytes", "acquire_check_s",
                        "twin_compile_check_s"}
    assert {"check_s", "gen_rate", "peak_hbm", "setup_s"} <= {
        m["name"] for m in manifest.metrics_for("end_to_end", CELL)}


def test_the_tiny_symmetric_sibling_asks_for_the_same_builder(manifest):
    """What the CPU rehearsals below run is the committed cell's path."""
    tiny = json.load(open(os.path.join(HERE, "data", "twopc5-sym.json")))
    assert tiny["builder"] == manifest.workload(CELL)["builder"]
    assert (json.load(open(os.path.join(HERE, "data", "twopc5.json")))["model"]["factory"]
            == manifest.config("twopc13sym")["model"]["factory"])


# -- the device canonicaliser against the host's, at the real width ---------------


def test_representative_rows_equals_the_hosts_representative_at_13_resource_managers():
    """Row-level parity at the REAL width: for 2,048 seeded reachable states
    of ``TwoPhaseSys(13)`` (random walks), the device form of the
    representative is the host's, bit for bit."""
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(13)
    twin = model.tensor_model()
    rng = random.Random(2147483659)
    states = []
    while len(states) < 2048:
        s = model.init_states()[0]
        for _ in range(rng.randrange(1, 48)):
            nxt = [model.next_state(s, a) for a in model.actions(s)]
            nxt = [n for n in nxt if n is not None]
            if not nxt:
                break
            s = rng.choice(nxt)
            states.append(s)
    states = states[:2048]
    assert len({s.rm_state for s in states}) > 200  # not one corner of the space
    rows = np.asarray([twin.encode_state(s) for s in states], dtype=np.uint64)
    want = np.asarray([twin.encode_state(s.representative()) for s in states],
                      dtype=np.uint64)
    assert rows.shape == (2048, 1)
    got = np.asarray(twin.representative_rows(jnp.asarray(rows)))
    assert (got == want).all()
    assert (rows != want).any(axis=-1).sum() > 500  # the canonicaliser did something


# -- correct can come out false under .symmetry() --------------------------------


@pytest.fixture(scope="module")
def sym_bench(tmp_path_factory):
    return _bench(tmp_path_factory, "bench_sym_controls",
                  [("twopc5-sym", "twopc5"), ("twopc5-sym-bounded", "twopc5")])


IDENTITY_CANONICALISER = '''
from stateright_tpu.models import two_phase_commit as tpc


class IdentityCanon(tpc.TwoPhaseTensor):
    """The twin with its canonicaliser broken: every row its own class."""

    def representative_rows(self, rows):
        return rows


tpc.TwoPhaseSys.tensor_model = lambda self: IdentityCanon(self)
'''


def test_a_twin_whose_canonicaliser_is_the_identity_is_not_correct(sym_bench):
    """``.symmetry()`` on, ``representative_rows`` the identity: the search
    keeps the unreduced space, and the pinned count says so.  The exactness
    sample may not rescue the run — it need not even see the fault (every
    kept representative is itself reachable, so the unreduced visited set
    holds its plain fingerprint)."""
    root, _ = sym_bench
    p = _rehearse(root, "twopc5-sym", prelude=IDENTITY_CANONICALISER)
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["unique_off"] == {"value": 8832 - 508, "limit": 0}
    assert out["compared"]["generated_off"]["value"] > 0
    assert "unique 8832 != pinned 508" in p.stdout
    assert ("unique_off", 8324.0, 0.0) in _compared_lines(p)


def test_the_control_under_symmetry_a_bounded_search_is_not_correct(sym_bench):
    root, _ = sym_bench
    p = _rehearse(root, "twopc5-sym-bounded")
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert {"unique_off", "generated_off", "sample_missing"} <= over
    assert "NOT CORRECT" in p.stdout
    assert any(v > lim for _, v, lim in _compared_lines(p))


# -- the two readers, by hand ---------------------------------------------------


@pytest.mark.parametrize("width, generated, want", [
    # 2pc-13 under symmetry: 1-word rows, 8 B read + 8 B key = 16 B a state
    (1, 2_407_522, 38_520_352),
    # paxos-3: 33-word rows = 264 B read + 8 B key
    (33, 2_420_477, 658_369_744),
    (21, 0, 0),
])
def test_hash_bytes_by_hand(width, generated, want):
    assert hash_bytes.hash_bytes(width, generated) == want


def test_hash_roofline_share_by_hand():
    # 1,000,000 rows of 15 words + a key = 128 MB; at 128 MB/s that is 1 s
    assert hash_bytes.hash_roofline_pct(15, 1_000_000, 128e6, 4.0) == pytest.approx(25.0)
    with pytest.raises(ValueError):
        hash_bytes.hash_roofline_pct(15, 1, 128e6, 0.0)
    with pytest.raises(ValueError):
        hash_bytes.hash_bytes(0, 1)
    with pytest.raises(ValueError):
        hash_bytes.hash_bytes(1, -1)


def test_stage_hash_roofline_without_a_trace_or_a_peak_reads_nothing(manifest):
    read = manifest.reader_module("stage_hash_roofline").read
    ctx = {"cell": {"name": "no-such-cell"}, "row": {"width": 1},
           "pins": {"generated": 1}, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read(ctx) is None  # no trace
    assert read(dict(ctx, peaks=None)) is None  # no published peak


@pytest.mark.parametrize("recorded, named", [
    ("twopc4_v5e_named.xplane.pb", True),  # PR 24: a 2pc-4 check with the stage scopes
    ("twopc4_v5e.xplane.pb", False),  # PR 23: the program had no scopes yet
])
def test_stage_hash_roofline_on_a_trace_recorded_on_a_v5e(tmp_path, capsys, recorded, named):
    """The reader at its place in a checkout (``benchmarks/layer_metrics/``)
    finds the traced check under ``.bench_trace/<cell>/``: the bytes the
    stage needs over its seconds where operations carry ``sr.hash``, every
    one of them listed; NOTHING where none does - never 0 for a share of a
    roofline."""
    from srbench import xstages

    readers = tmp_path / "benchmarks" / "layer_metrics"
    readers.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "layer_metrics", "stage_hash_roofline.py"), readers)
    trace_dir = tmp_path / ".bench_trace" / "a-cell" / "plugins" / "profile" / "recorded"
    trace_dir.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "data", recorded), trace_dir)
    read = Manifest(os.path.join(REPO, "BENCHMARK.json"),
                    str(tmp_path / "benchmarks")).reader_module("stage_hash_roofline").read
    ctx = {"cell": {"name": "a-cell"}, "row": {"width": 1}, "pins": {"generated": 5000},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    got = read(ctx)
    err = capsys.readouterr().err
    if not named:
        assert got is None and "hashops:" not in err
        return
    hash_s = xstages.analyse(os.path.join(HERE, "data", recorded))["stages"]["sr.hash"]
    assert hash_s > 0
    assert got == pytest.approx(100.0 * (5000 * 16 / 819e9) / hash_s)
    assert 0 < got < 100
    listed = [ln for ln in err.splitlines() if ln.startswith("hashops:   ")]
    assert len(listed) > 8  # every operation, not the stage table's top 8
    # (each line is printed to the microsecond)
    assert sum(float(ln.split()[1]) for ln in listed) == pytest.approx(
        hash_s, abs=1e-6 * len(listed))
    assert f"in {len(listed)} operations" in err


def test_cand_fill_by_hand(manifest):
    read = manifest.reader_module("cand_fill_pct").read

    def step(dsteps, batch=1024):
        return {"kind": "step", "dsteps": dsteps, "batch": batch}

    def ctx(checks, generated, max_actions):
        return {"checks": [{"records": r} for r in checks], "median": stats.median,
                "pins": {"generated": generated}, "row": {"max_actions": max_actions}}

    # the cell: 179 steps of 1,024 rows x 67 actions = 12,280,832 lanes
    cell = ctx([[step(0), step(179)]] * 3, 2_407_522, 67)
    assert read(cell) == pytest.approx(100.0 * 2_407_522 / 12_280_832)
    assert read(cell) == pytest.approx(19.6039, abs=1e-4)
    # a growth ladder may change the batch between records
    mixed = ctx([[step(10, 32), step(5, 64)]], 640, 4)
    assert read(mixed) == pytest.approx(100.0 * 640 / (640 * 4))
    # a program that does not count its steps, or an untraced check
    assert read(ctx([[{"kind": "step", "engine": "wavefront"}]], 1, 1)) is None
    assert read(ctx([[]], 1, 1)) is None

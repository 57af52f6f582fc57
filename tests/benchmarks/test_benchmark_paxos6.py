"""Tests of what the ``paxos6`` configuration added to the benchmark: the
configuration and cell files (``bench.sh``'s own ``paxos check 6``, uncut,
its RUN bounded), the reader ``layer_metrics/queue_fill_pct.py``, ``graded``
held to every transition of the whole paxos-1 space and of paxos-2 down to
level 9, the ``bounded`` kind rehearsed end to end on the HAND paxos twin at
a small size (3,079 of paxos-2's 16,668 states, added AS FILES under
``data/``), and one control that must come out NOT correct (a pinned level
one row off).  CPU-only, unit-cheap.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from srbench import reference, stats  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_bounded import BOUNDED_NAMES, _over  # noqa: E402
from test_benchmark_loops import (  # noqa: E402
    TAG, _bench, _compared_lines, _rehearse, _result)
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

CONFIG, CELL = "paxos6", "paxos6-bounded"
TINY, TINY_CONFIG = "paxos2-bounded-tiny", "paxos2-prefix"
METRIC = "queue_fill_pct"


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


def _paxos(clients: int):
    from stateright_tpu.models.paxos import paxos_model

    return paxos_model(clients, 3)


# -- the manifest, the configuration and the cell ---------------------------------


def test_the_manifest_and_its_files_agree(manifest):
    assert manifest.problems() == []


def test_the_configuration_is_bench_shs_own_size_uncut(manifest):
    entry = manifest.config_entry(CONFIG)
    cfg = manifest.config(CONFIG)
    assert entry["reduced"] == cfg["reduced"] == []
    assert "bench.sh:28" in entry["source"] and "paxos check 6" in entry["source"]
    assert "target_state_count" in entry["source"] and len(entry["source"]) <= 200
    assert entry["source"] == cfg["source"]
    assert cfg["model"] == {"factory": "stateright_tpu.models.paxos:paxos_model",
                            "args": [6], "kwargs": {}}
    assert (cfg["deployment"]["servers"], cfg["deployment"]["clients"]) == (3, 6)
    assert len(cfg["guarantees"]) == 4  # what a bounded configuration states
    for key in ("device_twin", "target", "whole_space", "graded"):
        assert cfg["assumed"][key]
    # no count of the whole space is invented
    assert set(cfg["pins"]) == {"bounded"}


def test_the_row_is_the_hand_twins(manifest):
    from stateright_tpu.models.paxos_tensor import PaxosTensor

    cfg = manifest.config(CONFIG)
    twin = chk.build_model(cfg).tensor_model()
    assert isinstance(twin, PaxosTensor) and hasattr(twin, "poison_rows")
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert (twin.width, twin.max_actions, twin.n_slots) == (64, 60, 60)


def test_the_cell_is_the_issues_traffic_or_its_one_fallback(manifest):
    cell = manifest.cell(CELL)
    wl = manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "bounded", 1)
    assert chk.loop_kind(wl) == "bounded" and wl["expect_growth"] == "none"
    spawn = dict(wl["spawn"])
    cand = spawn.pop("cand")
    assert cand >= 65536 and cand & (cand - 1) == 0
    assert (chk.bounded_target(wl), spawn) in [
        (3670016, {"capacity": 1 << 24, "queue_capacity": 1 << 22,
                   "batch": 4096, "steps_per_call": 512}),
        (1835008, {"capacity": 1 << 23, "queue_capacity": 1 << 21,
                   "batch": 4096, "steps_per_call": 512}),
    ]


def test_table_and_queue_are_sized_for_the_prefix(manifest):
    """At the stop ``unique x 4 <= capacity`` (the step program ends a call
    on a fuller table) and the queue holds the prefix plus one step."""
    cfg, wl = manifest.config(CONFIG), manifest.workload(CELL)
    step = wl["spawn"]["batch"] * cfg["row"]["max_actions"]
    most = chk.bounded_target(wl) + step
    assert most <= wl["spawn"]["queue_capacity"]
    assert most * 4 <= wl["spawn"]["capacity"]
    # the memory is rows the search writes: 512 B a row, 85% of the queue
    assert 100.0 * chk.bounded_target(wl) / wl["spawn"]["queue_capacity"] >= 85


def test_the_cell_reports_what_the_issue_lists(manifest):
    got = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert {"check_s", "peak_hbm", "setup_s"} <= got <= {
        "check_s", "peak_hbm", "setup_s", "gen_rate"}
    layer = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert {METRIC, "stage_props_lin_s", "stage_expand_net_s", "step_roofline",
            "twin_expand_roofline", "stage_hash_roofline"} <= layer
    # a hand twin: none of the compiled twin's readers
    assert not {"twin_compile_s", "twin_table_bytes", "stage_expand_table_s"} & layer


# -- the pins ----------------------------------------------------------------------


def test_the_pins_hang_together(manifest):
    pins = manifest.config(CONFIG)["pins"]["bounded"]
    assert len(pins["levels"]) - 1 >= 10 and sum(pins["levels"]) >= 100_000
    assert pins["reference_levels"] == 6 and sum(pins["levels"][:7]) == 3615
    assert pins["graded"] is True
    assert pins["discoveries_by_level"] == {"value chosen": 8}
    assert len(pins["witnesses"]["value chosen"]) == 8


def test_the_first_pinned_levels_are_the_plain_references(manifest):
    """Levels 0..4 again, here (1.2 s); the deeper ones are two host searches
    of minutes each (``pins.bounded.provenance``) and the run's own K = 6."""
    cfg = manifest.config(CONFIG)
    levels = []
    got = reference.reference_bfs(chk.build_model(cfg), max_level=4, levels=levels)
    assert [n for n, _ in levels] == cfg["pins"]["bounded"]["levels"][:5]
    assert not any(found for _, found in levels) and got["discoveries"] == []


def test_the_witness_replays_on_the_host_model_and_decides_value_chosen(manifest):
    cfg = manifest.config(CONFIG)
    model = chk.build_model(cfg)
    pins = cfg["pins"]["bounded"]
    actions = pins["witnesses"]["value chosen"]
    state = model.init_states()[0]
    for want in actions:
        state = next(model.next_state(state, a) for a in model.actions(state)
                     if repr(a) == want)
    assert model.property_by_name("value chosen").condition(model, state)
    fp = model.fingerprint_state(state)
    for popped, owed in ([fp], True), ([], False):
        prefix = {"popped": np.asarray(popped, np.uint64)}
        assert chk.witness_popped(model, prefix, "value chosen", 8, actions) is owed
    # one delivery short decides nothing: a pin that does not hang together
    prefix = {"popped": np.asarray([fp], np.uint64)}
    assert not chk.witness_popped(model, prefix, "value chosen", 7, actions[:7])


def deliveries(state) -> int:
    """The rank ``assumed.graded`` argues from: how many deliveries led to
    ``state``, read off the state alone.  Every message ever sent is in
    flight or was delivered (lossless, non-duplicating; a no-op delivery is
    pruned and leaves its message in flight), so deliveries = sent - in
    flight; and what was sent shows: every client's put; a server's two
    prepares iff it holds a proposal, its two accepts iff its ``accepts``
    set is not empty, its two decideds and its put_ok iff that set is a
    quorum; and one reply - prepared, accepted, get, get_ok - for every
    prepare, accept, put_ok, get that was sent and is no longer in flight."""
    from stateright_tpu.models.paxos import PaxosState

    flying = {}
    for env, n in state.network._counts.items():
        kind = env.msg[1][0] if env.msg[0] == "internal" else env.msg[0]
        flying[kind] = flying.get(kind, 0) + n
    servers = [a for a in state.actor_states if isinstance(a, PaxosState)]
    sent = {"put": len(state.actor_states) - len(servers),
            "prepare": 2 * sum(s.proposal is not None for s in servers),
            "accept": 2 * sum(len(s.accepts) >= 1 for s in servers),
            "decided": 2 * sum(len(s.accepts) >= 2 for s in servers),
            "put_ok": sum(len(s.accepts) >= 2 for s in servers)}
    for reply, to in (("prepared", "prepare"), ("accepted", "accept"),
                      ("get", "put_ok"), ("get_ok", "get")):
        sent[reply] = sent[to] - flying.get(to, 0)
    return sum(sent.values()) - sum(flying.values())


def _graded(model, max_level=None):
    """(states, transitions) of the reference's levels 0..max_level: every
    state's level is its rank (``deliveries``), and every transition out of
    a level but the last leads exactly one level deeper, never to the state
    itself (a delivery is consumed; no-op deliveries are pruned)."""
    kept, levels = [], []
    reference.reference_bfs(model, max_level=max_level, kept=kept, levels=levels)
    level_of, at = {}, 0
    for depth, (size, _) in enumerate(levels):
        level_of.update((s, depth) for s in kept[at:at + size])
        at += size
    edges = 0
    for s, depth in level_of.items():
        assert deliveries(s) == depth
        if max_level is not None and depth >= max_level:
            continue
        for n in reference.successors(model, s):
            edges += 1
            assert n != s and level_of[n] == depth + 1
    return len(level_of), edges


@pytest.mark.parametrize("clients, max_level, states, edges", [
    (1, None, 265, 481),
    (2, 9, 1919, 3313),
    (6, 4, 393, 630),
])
def test_paxos_is_graded(clients, max_level, states, edges):
    """What ``pins.bounded.graded`` states of paxos: the rank of a state is
    the number of deliveries that led to it, and the state fixes it.  The
    whole paxos-1 space, paxos-2 down to level 9 (the tiny cell's complete
    level), the cell's own paxos-6 down to level 4 (the configuration file
    names the host searches that hold it deeper)."""
    assert _graded(_paxos(clients), max_level) == (states, edges)


# -- the reader -------------------------------------------------------------------


def _ctx(uniques, queue_capacity=1000, key="records"):
    checks = [{key: [{"kind": "compile"}]
               + [{"kind": "step", "unique": u, "dsteps": 1} for u in us]}
              for us in uniques]
    spawn = {} if queue_capacity is None else {"queue_capacity": queue_capacity}
    return {"workload": {"spawn": spawn}, "checks": checks,
            "median": stats.median}


@pytest.mark.parametrize("ctx, want", [
    # the LAST step record's cumulative unique, not the sum, not the first
    (_ctx([[10, 400, 880]]), 88.0),
    # the median over the window's checks
    (_ctx([[880], [890], [100]]), 88.0),
    (_ctx([[3679213]], 1 << 22), 100.0 * 3679213 / (1 << 22)),
    # a queue left to the defaults grows: nothing to fill
    (_ctx([[880]], None), None),
    # a plain run records nothing; a window without checks
    (_ctx([[880]], key="no_records"), None),
    (_ctx([]), None),
    (_ctx([[]]), None),
])
def test_queue_fill_pct_by_hand(manifest, ctx, want):
    got = manifest.reader_module(METRIC).read(ctx)
    assert got == (None if want is None else pytest.approx(want))


def test_the_readers_constants_are_the_manifest_entrys(manifest):
    reader = manifest.reader_module(METRIC)
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == METRIC)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE)
    assert (reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "%", "peak_hbm", "program_counter")
    assert entry["better"] == "higher"
    # the bounded cells, the four-chip one since PR 55 (43.8 there); a later
    # bounded cell joins the list
    assert entry["workloads"][:3] == ["twopc10-bounded", CELL, "paxos6x4-bounded"]
    # it IS in the manifest; where - appended, nothing moved - is said once
    # for every entry (test_benchmark_room.py's prefix rule)
    assert manifest.doc["per_layer"].count(entry) == 1


# -- run.py end to end (rehearsal) on the hand paxos twin at a small size ----------


def test_the_tiny_prefix_pins_are_the_plain_references():
    pins = json.load(open(os.path.join(DATA, f"{TINY_CONFIG}.json")))["pins"]["bounded"]
    levels = []
    got = reference.reference_bfs(_paxos(2), max_level=9, levels=levels)
    assert [n for n, _ in levels] == pins["levels"][:10]
    assert {name: at for at, (_, names) in enumerate(levels)
            for name in names} == pins["discoveries_by_level"]
    assert got["discoveries"] == ["value chosen"]
    assert pins["reference_levels"] == 7 < len(pins["levels"]) - 1


@pytest.fixture(scope="module")
def paxos_bench(tmp_path_factory):
    root, doc = _bench(tmp_path_factory, "bench_paxos6", [(TINY, TINY_CONFIG)])
    for m in doc["per_layer"]:
        if m["name"] in (METRIC, "stage_props_lin_s"):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root, doc


@pytest.fixture(scope="module")
def traced(paxos_bench):
    root, doc = paxos_bench
    p = _rehearse(root, TINY, trace=1)
    return p, _result(p), doc


def test_the_bounded_rehearsal_on_the_hand_twin_is_correct(traced):
    p, out, _ = traced
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert " loop=bounded" in p.stdout and "traffic=bounded-tiny" in p.stdout
    assert "unique=3079 generated=5234 depth=9" in p.stdout
    assert "discoveries=['value chosen']" in p.stdout
    assert "head=1774 tail=3079, complete to level 9" in p.stdout
    assert "pinned levels 0..10, the reference's 0..7" in p.stdout
    assert "levels 0..9 hold 1919 of the 3079 states" in p.stdout
    assert "witness not popped: []" in p.stdout
    assert "levels [1, 2, 5, 11, 26, 58, 135, 286] = 524 states" in p.stdout
    assert all(ln.startswith(TAG) for ln in p.stdout.splitlines() if ln.strip())


def test_every_number_of_the_kind_is_compared_at_its_limit(traced):
    p, out, _ = traced
    assert set(out["compared"]) == BOUNDED_NAMES
    assert all(c == {"value": 0, "limit": 0} for c in out["compared"].values())
    assert _compared_lines(p) == [(k, 0.0, 0.0) for k in out["compared"]]


def test_the_traced_rehearsal_reports_the_queues_fill(traced):
    _, out, doc = traced
    want = {m["name"] for m in doc["per_layer"]
            if "workloads" not in m or TINY in m["workloads"]}
    assert METRIC in want
    assert_a_rehearsal_prints(want, out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m[METRIC] == pytest.approx(100.0 * 3079 / 8192)
    assert out["metrics"][METRIC]["unit"] == "%"
    # 1,774 rows popped in 13 steps of 256 lanes, 20 action columns
    assert m["device_steps"] == 13
    assert m["batch_fill_pct"] == pytest.approx(100.0 * 1774 / (13 * 256))
    assert m["cand_fill_pct"] == pytest.approx(100.0 * 5234 / (13 * 256 * 20))


def test_control_a_pinned_level_one_row_off_is_not_correct(paxos_bench):
    """The configuration pins 868 states at level 9 where the host search
    and the queue's labels count 867: ``level_sizes_off`` 1, nothing else."""
    root, _ = paxos_bench
    path = root / "benchmarks" / "configs" / f"{TINY_CONFIG}.json"
    good = path.read_text()
    cfg = json.loads(good)
    cfg["pins"]["bounded"]["levels"][9] += 1
    path.write_text(json.dumps(cfg))
    try:
        p = _rehearse(root, TINY)
    finally:
        path.write_text(good)
    out = _result(p)
    assert out["correct"] is False
    assert out["compared"]["level_sizes_off"] == {"value": 1, "limit": 0}
    assert _over(out) == {"level_sizes_off"}
    assert ("level_sizes_off", 1.0, 0.0) in _compared_lines(p)

"""Tests of the ``bounded`` loop kind: a check that ``target_states`` stops.

A search that cannot exhaust in a window has no pin of the whole space, so a
check is held to what a PREFIX of a breadth-first search owes
(``srbench/check.py:compare_bounded``, ``bounded_prefix``): the bound reached
and not overshot, the same stop every time, the table complete down to a
level the harness derives from the snapshot alone, every state of the plain
reference's first levels in it, every sampled slot reachable on the host
model.  Here: the rule of the kind, the argument for the complete level held
against EVERY reference level of 2pc-6 over five settings, the kind rehearsed
on a tiny cell added AS FILES (2,000 of 2pc-5's 8,832 states), and the four
controls that must come out NOT correct.  CPU-only, unit-cheap.
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
from srbench import reference  # noqa: E402
from test_benchmark_loops import (  # noqa: E402
    BROKEN_ANSWER, TAG, _bench, _compared_lines, _rehearse, _result)
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

CELL, OVER, CONFIG = "twopc5-bounded-tiny", "twopc5-bounded-over", "twopc5-prefix"
SEED = 2147483801  # _rehearse's
BOUNDED_NAMES = {
    "target_short", "target_over", "discoveries_missing", "paths_off",
    "growth_off", "repeat_off", "visited_off", "levels_beyond_complete",
    "level_sizes_off", "sample_missing", "prefix_missing", "unreachable",
    "window_persistent_misses", "window_compile_requests"}


def _workload(**changes):
    wl = json.load(open(os.path.join(DATA, f"{CELL}.json")))
    wl.update(changes)
    return wl


# -- the rule of the kind ---------------------------------------------------------


def test_a_bounded_workload_names_its_kind_and_its_bound():
    wl = _workload()
    assert chk.loop_kind(wl) == "bounded" and chk.bounded_target(wl) == 3400
    assert "bounded" in chk.LOOP_KINDS


@pytest.mark.parametrize("changes, rule", [
    ({"builder": []}, "target_states"),
    ({"builder": [{"verb": "target_states"}]}, "target_states"),
    ({"builder": [{"verb": "target_states", "args": [3400]},
                  {"verb": "symmetry"}]}, "symmetry"),
    ({"spawn": {"capacity": 32768}}, "spawn.batch"),
])
def test_a_bounded_workload_that_breaks_the_rule_is_refused(changes, rule):
    with pytest.raises(ValueError, match=rule):
        chk.loop_kind(_workload(**changes))


@pytest.mark.parametrize("kind", ["closed", "cold"])
def test_the_older_kinds_refuse_nothing_they_accepted(kind):
    """``target_states`` under ``closed`` / ``cold`` is the older cells'
    CONTROL (a data file): it must stay a run that comes out not correct,
    never an error of the kind."""
    wl = _workload(loop={"kind": kind})
    assert chk.loop_kind(wl) == kind
    assert chk.loop_kind(_workload(loop={"kind": kind}, builder=[])) == kind


# -- one check's rows ---------------------------------------------------------------


def _rows(result, first=None, **wl):
    cfg = json.load(open(os.path.join(DATA, f"{CONFIG}.json")))
    base = {"unique": 3669, "generated": 17584, "discoveries": ["abort agreement"],
            "paths": {}, "growth_events": 0}
    base.update(result)
    return {name: number for name, number, limit, _ in
            chk.compare_bounded(None, cfg, _workload(**wl), base, first)
            if number > limit}


@pytest.mark.parametrize("result, first, over", [
    ({}, None, {}),
    ({}, {"unique": 3669, "generated": 17584}, {}),
    ({"unique": 3399}, None, {"target_short": 1}),
    # one step is batch x max_actions = 256 x 27 = 6,912 states
    ({"unique": 3400 + 6912}, None, {}),
    ({"unique": 3400 + 6913}, None, {"target_over": 1}),
    # a pinned discovery suppressed: guarantee 4's control
    ({"discoveries": []}, None, {"discoveries_missing": 1}),
    # one beyond the pinned ones is the path row's alone
    ({"discoveries": ["abort agreement", "commit agreement"]}, None, {}),
    ({"growth_events": 2}, None, {"growth_off": 2}),
    ({"unique": 3670, "generated": 17594}, {"unique": 3669, "generated": 17584},
     {"repeat_off": 11}),
])
def test_what_one_bounded_check_is_held_to(result, first, over):
    assert _rows(result, first) == over


# -- the plain reference's first levels ---------------------------------------------


@pytest.fixture(scope="module")
def twopc5():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(5)


def test_the_tiny_prefix_pins_are_the_plain_references(twopc5):
    pins = json.load(open(os.path.join(DATA, f"{CONFIG}.json")))["pins"]["bounded"]
    kept, levels = [], []
    got = reference.reference_bfs(twopc5, max_level=len(pins["levels"]) - 1,
                                  kept=kept, levels=levels)
    assert [n for n, _ in levels] == pins["levels"]
    assert {name: at for at, (_, names) in enumerate(levels)
            for name in names} == pins["discoveries_by_level"]
    assert got["unique"] == len(kept) == sum(pins["levels"])
    assert got["max_depth"] == len(pins["levels"]) - 1
    assert got["discoveries"] == sorted(pins["discoveries_by_level"])
    # the sizes are pinned deeper than a run's own reference searches
    assert pins["reference_levels"] == 5 < len(pins["levels"]) - 1


@pytest.mark.parametrize("rms, states", [(5, 8832), (6, 50816)])
def test_two_phase_commit_is_graded(rms, states):
    """What ``pins.bounded.graded`` states, held to EVERY transition of the
    whole space: it leads to the state itself or exactly one level deeper,
    so every path to a state has one length and a depth label is its
    level, whatever order the rows were popped in."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(rms)
    kept, levels = [], []
    reference.reference_bfs(model, kept=kept, levels=levels)
    level_of, at = {}, 0
    for depth, (size, _) in enumerate(levels):
        level_of.update((s, depth) for s in kept[at:at + size])
        at += size
    assert len(level_of) == states
    assert all(n == s or level_of[n] == depth + 1
               for s, depth in level_of.items()
               for n in reference.successors(model, s))


def test_a_bounded_reference_search_is_a_prefix_of_the_whole_one(twopc5):
    whole_kept, whole_levels = [], []
    whole = reference.reference_bfs(twopc5, kept=whole_kept, levels=whole_levels)
    assert whole["unique"] == 8832 and len(whole_levels) == whole["max_depth"] + 1
    kept, levels = [], []
    reference.reference_bfs(twopc5, max_level=3, kept=kept, levels=levels)
    assert levels == whole_levels[:4] and kept == whole_kept[:len(kept)]
    # without the arguments the search answers what it always answered
    assert reference.reference_bfs(twopc5) == whole


# -- the complete level of a prefix -----------------------------------------------


@pytest.mark.parametrize("labels, head, want", [
    # a sorted queue: complete to the shallowest label that waits
    ([0, 1, 1, 2, 2, 2, 3, 3], 3, 2),
    ([0, 1, 1, 2, 2, 2, 3, 3], 6, 3),
    # nothing waits: everything reachable is in the table
    ([0, 1, 1, 2], 4, 2),
    # a 3 popped ahead of a 2 (one step's rows in table order): a state of
    # level 3 may carry the label 4, so level 4 can lack states whose
    # parents wait under a deeper label: complete to V + 1 = 3 at most
    ([0, 1, 1, 2, 3, 2, 3, 3, 4, 4, 5], 9, 3),
    # ... and to the shallowest waiting label where that is less
    ([0, 1, 1, 2, 3, 2, 3, 3, 4, 4, 5], 5, 2),
    ([0], 0, 0),
])
def test_complete_level_by_hand(labels, head, want):
    assert chk.complete_level(np.asarray(labels, np.uint32), head, len(labels)) == want
    # rows past ``tail`` are garbage and are not read
    padded = np.asarray(labels + [0, 9, 0], np.uint32)
    assert chk.complete_level(padded, head, len(labels)) == want


@pytest.mark.parametrize("labels, head, want", [
    # a graded model's labels are levels whatever the order: complete to the
    # shallowest label that waits (the same queues as above: 3 and 2 there)
    ([0, 1, 1, 2, 3, 2, 3, 3, 4, 4, 5], 9, 4),
    ([0, 1, 1, 2, 3, 2, 3, 3, 4, 4, 5], 5, 2),
    ([0, 1, 1, 2, 2, 2, 3, 3], 6, 3),
    ([0, 1, 1, 2], 4, 2),
])
def test_complete_level_of_a_graded_model_by_hand(labels, head, want):
    got = chk.complete_level(np.asarray(labels, np.uint32), head, len(labels),
                             graded=True)
    assert got == want >= chk.complete_level(np.asarray(labels, np.uint32),
                                             head, len(labels))


class _Snapshot:
    """A checker's public ``checkpoint()``, by hand."""

    def __init__(self, fps, q_fp, q_depth, head):
        empty = np.uint64(chk.EMPTY_FP)
        self._snap = {
            "table_fp": np.asarray([empty, *fps, empty], np.uint64),
            "table_parent": np.asarray([0, 0, *fps[:-1], 0], np.uint64),
            "q_fp": np.asarray(q_fp + [0, 0], np.uint64),  # garbage past tail
            "q_depth": np.asarray(q_depth + [9, 9], np.uint32),
            "head": np.int32(head), "tail": np.int32(len(q_fp)),
        }

    def checkpoint(self):
        return dict(self._snap)


def test_the_prefix_is_read_from_the_public_snapshot():
    prefix = chk.bounded_prefix(_Snapshot([9, 5, 7], [5, 9, 7], [0, 1, 1], head=1))
    assert prefix["visited"].tolist() == [5, 7, 9]
    assert prefix["parents"].tolist() == [9, 5, 0]  # aligned with ``visited``
    assert prefix["popped"].tolist() == [5] and (prefix["head"], prefix["tail"]) == (1, 3)
    assert prefix["complete_level"] == 1 and prefix["labels"] == [1, 2]


def test_a_queue_the_host_compacted_owes_nothing():
    """A queue that grew mid-check dropped its popped rows: it holds fewer
    rows than the table holds states, no level can be read from it, and
    every pinned level lies beyond -1 (``levels_beyond_complete``)."""
    prefix = chk.bounded_prefix(_Snapshot([9, 5, 7], [9, 7], [1, 1], head=0))
    assert prefix["complete_level"] == -1 and len(prefix["visited"]) == 3
    assert len(prefix["popped"]) == 0  # ... nor is any witness "popped"
    # ... not even a walk's init state, though its last state was "popped"
    prefix["popped"] = np.asarray([7], np.uint64)
    assert chk.walks_missing(prefix, [[5, 9, 7]], closure=True) == (0, 0, 0)


@pytest.fixture(scope="module")
def twopc6_levels():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(6)
    kept, levels = [], []
    reference.reference_bfs(model, kept=kept, levels=levels)
    fps, at = [], 0
    for size, _ in levels:
        fps.append(np.asarray([model.fingerprint_state(s) for s in kept[at:at + size]],
                              dtype=np.uint64))
        at += size
    return model, kept, fps


# batch, target -> unique, max_depth, the complete level, the first level
# with a state missing (ISSUE 45's five settings of ``TwoPhaseSys(6)``;
# host levels 1 / 13 / 78 / 292 / 781 / 1,632 / 2,856 / 4,391 / 6,039 / ...)
SETTINGS = [
    (256, 3000, 3913, 5, 4, 5),
    (256, 10000, 10580, 7, 5, 7),
    (2048, 10000, 14286, 7, 7, 8),
    (2048, 20000, 22068, 8, 7, 8),
    (1024, 30000, 30084, 9, 7, 10),
]


@pytest.mark.parametrize("batch, target, unique, depth, level, first_short", SETTINGS)
def test_every_reference_level_down_to_the_complete_one_is_in_the_table(
    twopc6_levels, batch, target, unique, depth, level, first_short
):
    """The argument for C (``benchmarks/README.md``), held to EVERY level of
    the plain reference of 2pc-6: each level <= C is in the table whole, C
    is never past the first level that lacks a state, and the table holds
    every successor of every popped row (the closure the walks lean on)."""
    model, kept, levels = twopc6_levels
    checker = model.checker().target_states(target).spawn_tpu(
        sync=True, capacity=1 << 18, queue_capacity=1 << 16, batch=batch,
        steps_per_call=64, cand=16 * batch)
    checker.join()
    assert (checker.unique_state_count(), checker.max_depth()) == (unique, depth)
    prefix = chk.bounded_prefix(checker)
    assert len(prefix["visited"]) == unique == prefix["tail"]
    short = [chk.missing_from(prefix["visited"], fps) for fps in levels]
    assert next(i for i, n in enumerate(short) if n) == first_short
    assert prefix["complete_level"] == level < first_short
    assert not any(short[:level + 1])
    # 2pc is graded (above): a label is the level whatever the order, the
    # prefix is complete to the shallowest waiting label - never past the
    # first level that lacks a state - and the queue's rows by label ARE the
    # reference's level sizes down to it
    graded = chk.bounded_prefix(checker, graded=True)
    deep = graded["complete_level"]
    assert level <= deep < first_short and not any(short[:deep + 1])
    assert graded["labels"][:deep + 1] == [len(fps) for fps in levels[:deep + 1]]
    # closure: no successor of a popped row is missing
    by_fp = {model.fingerprint_state(s): s for s in kept}
    successors = [model.fingerprint_state(n) for fp in prefix["popped"].tolist()
                  for n in reference.successors(model, by_fp[fp])]
    assert len(prefix["popped"]) == prefix["head"]
    assert chk.missing_from(prefix["visited"], successors) == 0
    # ... so the walks are owed well past C, and none of them is missing
    walks = reference.random_walks(model, batch + target, 64)
    owed, missing, deepest = chk.walks_missing(prefix, walks, closure=True)
    assert missing == 0 and deepest > level
    by_level = chk.walks_missing(prefix, walks, closure=False)
    assert by_level[1] == 0 and by_level[2] == level and by_level[0] < owed
    assert chk.unreachable(model, prefix, seed=target, draws=64) == []


def test_walks_are_owed_by_level_and_by_closure():
    prefix = {"visited": np.asarray([10, 11, 12, 13, 14], np.uint64),
              "popped": np.asarray([10, 11, 13], np.uint64), "complete_level": 1}
    # 10 -> 11 by level; 12 because 11 was popped; 99 is not owed (12 waits)
    assert chk.walks_missing(prefix, [[10, 11, 12, 99]], True) == (3, 0, 2)
    assert chk.walks_missing(prefix, [[10, 11, 12, 99]], False) == (2, 0, 1)
    # a successor of a popped row that the table lacks IS owed, and missing
    assert chk.walks_missing(prefix, [[10, 13, 98, 97]], True) == (3, 1, 2)
    # a state the level owes, missing
    assert chk.walks_missing(prefix, [[10, 77]], False) == (2, 1, 1)


WITNESS = [f"('rm_choose_abort', {i})" for i in range(5)]


@pytest.mark.parametrize("actions, level, popped, owed", [
    (WITNESS, 5, True, True),
    # the check did not pop the witness state: the discovery is not owed yet
    (WITNESS, 5, False, False),
    # a pin that does not hang together is owed by nobody: another level
    # than the path's length, a path that decides nothing, an action the
    # host model does not have there, no witness at all
    (WITNESS, 4, True, False),
    (WITNESS[:4], 4, True, False),
    (WITNESS[:4] + ["('rm_choose_abort', 3)"], 5, True, False),
    (None, 5, True, False),
])
def test_a_pinned_discovery_is_owed_once_its_witness_was_popped(
        twopc5, actions, level, popped, owed):
    state = twopc5.init_states()[0]
    for want in WITNESS[:len(actions or WITNESS)]:
        state = next(twopc5.next_state(state, a) for a in twopc5.actions(state)
                     if repr(a) == want)
    prefix = {"popped": np.asarray(
        [twopc5.fingerprint_state(state)] if popped else [], np.uint64)}
    assert chk.witness_popped(
        twopc5, prefix, "abort agreement", level, actions) is owed


# -- run.py end to end (rehearsal) on the tiny cell -------------------------------


@pytest.fixture(scope="module")
def bounded_bench(tmp_path_factory):
    return _bench(tmp_path_factory, "bench_bounded",
                  [(CELL, CONFIG), (OVER, CONFIG)])


@pytest.fixture(scope="module")
def traced(bounded_bench):
    root, doc = bounded_bench
    p = _rehearse(root, CELL, trace=1)
    return p, _result(p), doc


def test_the_bounded_rehearsal_is_correct_and_labelled(traced):
    p, out, _ = traced
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert " loop=bounded" in p.stdout and "traffic=bounded-tiny" in p.stdout
    assert "unique=3669 generated=17584 depth=7" in p.stdout
    assert "complete to level 6" in p.stdout
    assert "pinned levels 0..7, the reference's 0..5" in p.stdout
    assert "levels 0..6 hold 2226 of the 3669 states" in p.stdout
    assert "witness not popped: []" in p.stdout
    assert "levels [1, 11, 55, 170, 375, 652] = 1264 states" in p.stdout
    assert all(ln.startswith(TAG) for ln in p.stdout.splitlines() if ln.strip())


def test_every_bounded_number_is_compared_and_printed_beside_its_limit(traced):
    p, out, _ = traced
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == BOUNDED_NAMES
    assert all(c == {"value": 0, "limit": 0} for c in out["compared"].values())
    assert _compared_lines(p) == [(k, 0.0, 0.0) for k in out["compared"]]


def test_every_reader_gives_the_bounded_cell_a_number_or_nothing(traced):
    """No reader of a cell without a ``workloads`` list fails on a check
    that has no pin of the whole space; where one reads ``pins`` it is given
    the check's own counts (``unique``: the states it popped)."""
    from srbench.manifest import Manifest

    _, out, doc = traced
    manifest = Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)
    want = {m["name"] for m in doc["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert want >= {m["name"] for m in manifest.doc["per_layer"]
                    if "workloads" not in m}
    assert_a_rehearsal_prints(want, out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # 2,285 rows popped in 12 steps of 256 lanes: a share, not the 119.4%
    # that the 3,669 states VISITED would read
    assert m["device_steps"] == 12
    assert m["batch_fill_pct"] == pytest.approx(100.0 * 2285 / (12 * 256))
    assert m["cand_fill_pct"] == pytest.approx(100.0 * 17584 / (12 * 256 * 27))


def test_a_plain_bounded_run_reports_the_cells_end_to_end_metrics(bounded_bench):
    root, _ = bounded_bench
    out = _result(_rehearse(root, CELL, trace=0))
    assert out["correct"] is True and set(out["compared"]) == BOUNDED_NAMES
    assert set(out["metrics"]) == {"check_s", "setup_s"}  # no gen_rate: not listed


def test_bounded_without_the_verb_exits_before_any_work(bounded_bench):
    root, _ = bounded_bench
    path = root / "benchmarks" / "workloads" / f"{CELL}.json"
    good = path.read_text()
    path.write_text(json.dumps(_workload(builder=[])))
    try:
        p = _rehearse(root, CELL)
    finally:
        path.write_text(good)
    assert p.returncode == 1 and p.stdout.strip() == ""
    assert "loop.kind 'bounded' needs" in p.stderr and "target_states" in p.stderr


# -- the four controls: correct must come out false ------------------------------


def _over(out):
    return {k for k, c in out["compared"].items() if c["value"] > c["limit"]}


def test_control_a_target_above_the_space_is_not_correct(bounded_bench):
    """The space (8,832 states) runs out under the bound (10,000): the
    check exhausted, it did not stop, and that is another unit of work."""
    root, _ = bounded_bench
    p = _rehearse(root, OVER)
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["target_short"] == {"value": 1168, "limit": 0}
    assert "ended at 8832 unique states, under its bound 10000" in p.stdout
    assert ("target_short", 1168.0, 0.0) in _compared_lines(p)


def test_control_an_altered_answer_is_not_correct(bounded_bench):
    """The timed path broken underneath (the loops' fake: every check
    reports one unique state too few): the table holds one more than the
    check says it counted."""
    root, _ = bounded_bench
    out = _result(_rehearse(root, CELL, prelude=BROKEN_ANSWER))
    assert out["correct"] is False
    assert out["compared"]["visited_off"] == {"value": 1, "limit": 0}
    assert _over(out) == {"visited_off"}


BROKEN_SNAPSHOT = '''
import random
import numpy as np
from srbench import check as chk
from stateright_tpu.models.two_phase_commit import TwoPhaseSys

real = chk.bounded_prefix


def broken(checker, graded=False):
    prefix = real(checker, graded)
    {body}
    return prefix


chk.bounded_prefix = broken
'''
# the FIRST slot the seeded draw takes gets a parent that is a visited state
# but no predecessor of it (the table's last fingerprint)
BROKEN_CHAIN = BROKEN_SNAPSHOT.replace("{body}", f'''
    at = random.Random({SEED}).sample(range(len(prefix["visited"])), chk.DRAWS)[0]
    prefix["parents"] = prefix["parents"].copy()
    prefix["parents"][at] = prefix["visited"][-1 if at != len(prefix["visited"]) - 1 else 0]
''')
# one state of level 2 swapped for a fingerprint no state has: the count of
# occupied slots stays, the prefix lacks a state the reference holds
MISSING_STATE = BROKEN_SNAPSHOT.replace("{body}", '''
    model = TwoPhaseSys(5)
    init = model.init_states()[0]
    one = next(n for a in model.actions(init) for n in [model.next_state(init, a)] if n)
    two = next(n for a in model.actions(one) for n in [model.next_state(one, a)] if n)
    at = int(np.searchsorted(prefix["visited"], np.uint64(model.fingerprint_state(two))))
    assert int(prefix["visited"][at]) == model.fingerprint_state(two)
    prefix["visited"] = prefix["visited"].copy()
    prefix["visited"][at] += np.uint64(1)
''')


def test_control_a_broken_parent_chain_is_not_correct(bounded_bench):
    root, _ = bounded_bench
    p = _rehearse(root, CELL, prelude=BROKEN_CHAIN)
    out = _result(p)
    assert out["correct"] is False
    assert out["compared"]["unreachable"]["value"] >= 1
    assert _over(out) == {"unreachable"}
    assert "does not replay on the host model" in p.stdout or "leaves the table" in p.stdout


def test_control_a_prefix_that_lacks_a_reference_state_is_not_correct(bounded_bench):
    root, _ = bounded_bench
    p = _rehearse(root, CELL, prelude=MISSING_STATE)
    out = _result(p)
    assert out["correct"] is False
    assert out["compared"]["prefix_missing"] == {"value": 1, "limit": 0}
    assert "prefix_missing" in _over(out)
    assert "1 of the reference's 1264 states of levels 0..5 not visited" in p.stdout


# one state of level 6 - deeper than the run's own reference searches (K = 5),
# inside the complete level (C = 6) - swapped for a state of level 9 or
# deeper that the prefix does not hold yet: the table stays as full and
# every slot stays reachable-looking; the queue's own count of the level is
# what reads one short of the pinned size
MISSING_BEYOND_K = BROKEN_SNAPSHOT.replace("{body}", '''
    prefix["labels"] = list(prefix["labels"])
    prefix["labels"][6] -= 1
    prefix["labels"][7] += 1
''')


def test_control_a_level_beyond_the_reference_that_lacks_a_state_is_not_correct(
        bounded_bench):
    """What pinning the sizes deeper than K buys: a state lost at a level
    the run's reference does not search is caught by the count."""
    root, _ = bounded_bench
    p = _rehearse(root, CELL, prelude=MISSING_BEYOND_K)
    out = _result(p)
    assert out["correct"] is False
    assert out["compared"]["level_sizes_off"] == {"value": 1, "limit": 0}
    assert _over(out) == {"level_sizes_off"}


# guarantee 4's control: the checker reports no discovery at all (the
# loops' fake, on another answer) - every count stays what it was
SUPPRESSED_DISCOVERY = BROKEN_ANSWER.replace(
    """    def unique_state_count(self):
        return self._checker.unique_state_count() - 1
""", """    def discoveries(self):
        return {}
""")


def test_control_a_suppressed_discovery_is_not_correct(bounded_bench):
    """The prefix holds the popped witness of `abort agreement` (first met at
    level 5); a checker that reports nothing passes every count and every
    path it has, and is not correct."""
    assert "def discoveries" in SUPPRESSED_DISCOVERY
    root, _ = bounded_bench
    p = _rehearse(root, CELL, prelude=SUPPRESSED_DISCOVERY)
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["discoveries_missing"] == {"value": 1, "limit": 0}
    assert _over(out) == {"discoveries_missing"}
    assert "the prefix pins ['abort agreement']; not reported" in p.stdout


def test_control_a_discovery_pinned_beyond_what_was_popped_is_not_correct(
        bounded_bench):
    """A configuration that pins a discovery the prefix at the cell's N does
    not owe (`commit agreement`, level 16, far past 3,669 states; no
    witness) is refused by the run, not waved through."""
    root, _ = bounded_bench
    path = root / "benchmarks" / "configs" / f"{CONFIG}.json"
    good = path.read_text()
    cfg = json.loads(good)
    cfg["pins"]["bounded"]["discoveries_by_level"]["commit agreement"] = 16
    path.write_text(json.dumps(cfg))
    try:
        out = _result(_rehearse(root, CELL))
    finally:
        path.write_text(good)
    assert out["correct"] is False
    assert out["compared"]["levels_beyond_complete"] == {"value": 1, "limit": 0}
    assert _over(out) == {"levels_beyond_complete", "discoveries_missing"}

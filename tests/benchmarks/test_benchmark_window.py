"""Tests of when a window stops starting checks (``srbench/check.py:
window_closes``, PR 42): once ``--seconds`` have passed, as ever, or once
the window holds a check and another as short as its shortest would end past
one and a half windows.  The pure function on the committed cells' ledger
medians (inert there) and on checks near the window's length (exactly one a
window), then ``run.py`` end to end in rehearsal mode, every loop kind, on
tiny cells whose check is padded TO a fixed length - one second, three for
the cold cell, whose own 0.8-2 s of compiling and tracing vary with the
host's load by more than the rule's margins - so that neither the host
clock's jitter, nor the collector's 30 ms between checks, nor a busy host
decides the outcome.  CPU-only, unit-cheap.
"""

import math
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from test_benchmark_loops import _bench, _result  # noqa: E402
from test_benchmark_loops import _rehearse as loops_rehearse  # noqa: E402
from test_benchmark_own import extended_benchmark  # noqa: E402,F401 - the fixture

RUN_SECONDS = 40.0


def closes_after(check_s: float, seconds: float = RUN_SECONDS) -> tuple:
    """Back-to-back checks of one length: how many the window holds and
    which rule closed it."""
    durations = []
    while True:
        durations.append(check_s)
        why = chk.window_closes(sum(durations), durations, seconds)
        if why:
            return len(durations), why


# -- the pure function ----------------------------------------------------------


@pytest.mark.parametrize("check_s, held", [
    # the seven cells' ledger medians (PR 38) at run_seconds 40
    (3.79, 11), (4.18, 10), (10.89, 4), (1.76, 23), (7.23, 6), (0.68, 59), (2.15, 19),
])
def test_the_rule_is_inert_where_checks_are_short(check_s, held):
    assert held == math.ceil(RUN_SECONDS / check_s)  # the count they have today
    assert closes_after(check_s) == (held, "seconds")


@pytest.mark.parametrize("check_s, want", [
    (35.8, (1, "overrun")),  # batch 4096: two a window before this rule
    (39.9, (1, "overrun")),  # batch 8192: one or two, on noise
    (40.05, (1, "seconds")),  # ``seconds`` is asked first
    (51.5, (1, "seconds")),
    (20.1, (2, "seconds")),
    (29.9, (2, "seconds")),  # the second ends at 59.8 s, inside 1.5 windows
    (30.1, (1, "overrun")),  # ... at 60.2 s, past them
])
def test_a_check_over_three_quarters_of_the_window_gets_exactly_one(check_s, want):
    assert closes_after(check_s) == want


@pytest.mark.parametrize("elapsed, durations, seconds, want", [
    (0.0, [], 40.0, None),
    (39.99, [], 40.0, None),  # an empty window never closes by ``overrun``
    (40.0, [], 40.0, "seconds"),
    (1e9, [], 40.0, "seconds"),
    # the SHORTEST check of this window decides, not the last or the mean
    (36.0, [12.0, 24.0], 40.0, None),
    (36.0, [24.0, 12.0], 40.0, None),
    (36.0, [30.0], 40.0, "overrun"),
    (36.0, [30.0, 24.1], 40.0, "overrun"),
    # the rule scales with the window's length
    (1.04, [1.01], 2.0, None),
    (1.04, [1.01], 1.2, "overrun"),
])
def test_window_closes_by_hand(elapsed, durations, seconds, want):
    assert chk.window_closes(elapsed, durations, seconds) == want


def test_the_longest_committed_check_cannot_trip_the_rule():
    """A check started before ``seconds`` ends before ``seconds`` + its own
    length: no cell whose checks are under half a window can close by
    ``overrun``, whatever the order of its checks."""
    for shortest in (0.68, 11.2, 19.9):
        for elapsed in (0.0, 20.0, 39.99):
            assert chk.window_closes(elapsed, [shortest, 19.9], 40.0) is None


# -- run.py end to end, rehearsed, both loop kinds ------------------------------

# every check is padded TO ``T`` seconds inside its timed span (``run_check``
# notes when it began, the last call of the span - a discovery's path - waits
# out the rest): a check that takes under T on its own lasts T whatever the
# host is doing, so two windows sized from ONE measured check hold what the
# rule says.  (To PR 44 a check GAINED a second, and the cold check's own
# 0.8-2 s under six workers' load failed one whole tier-1 run in three.)
PADDED = '''
import time
from srbench import check as chk

T = {seconds}
real_run, real_builder = chk.run_check, chk.builder_for
began = []


def run_check(make_model, workload, telemetry):
    began.append(time.monotonic())
    return real_run(make_model, workload, telemetry)


class PaddedChecker:
    def __init__(self, checker):
        self._checker = checker

    def __getattr__(self, name):
        return getattr(self._checker, name)

    def discovery(self, name):
        path = self._checker.discovery(name)
        time.sleep(max(0.0, began[-1] + T - time.monotonic()))
        return path


class Builder:
    def __init__(self, builder):
        self._builder = builder

    def spawn_tpu(self, **kw):
        return PaddedChecker(self._builder.spawn_tpu(**kw))


chk.run_check = run_check
chk.builder_for = lambda *a, **kw: Builder(real_builder(*a, **kw))
'''
PADDED_TO = {"closed": 1.0, "cold": 3.0, "bounded": 1.0}


def _rehearse(root, cell, seconds, padded_to=1.0):
    p = loops_rehearse(root, cell, seconds=seconds,
                       prelude=PADDED.replace("{seconds}", repr(padded_to)))
    out = _result(p)
    (line,) = [ln for ln in p.stdout.splitlines() if "] window: " in ln]
    got = re.search(r"window: (\d+) checks in [0-9.]+s closed_by=(\w+); "
                    r"check_s median=([0-9.]+)", line)
    return out, int(got[1]), got[2], float(got[3])


@pytest.fixture(scope="module")
def cold_bench(tmp_path_factory):
    return _bench(tmp_path_factory, "bench_window",
                  [("linreg2x2o-cold", "linreg2x2o")], twin=["linreg2x2o-cold"])


@pytest.fixture(scope="module")
def bounded_bench(tmp_path_factory):
    return _bench(tmp_path_factory, "bench_window_bounded",
                  [("twopc5-bounded-tiny", "twopc5-prefix")])


@pytest.mark.parametrize("kind", chk.LOOP_KINDS)
def test_a_rehearsed_window_does_not_start_a_check_it_can_see_will_overrun(
        kind, extended_benchmark, cold_bench, bounded_bench):  # noqa: F811
    root, cell = {"closed": (extended_benchmark[0], "twopc3-tiny"),
                  "cold": (cold_bench[0], "linreg2x2o-cold"),
                  "bounded": (bounded_bench[0], "twopc5-bounded-tiny")}[kind]
    pad = PADDED_TO[kind]
    # measure: ``seconds`` is asked first, so a window shorter than its
    # first check holds that one and says so
    out, held, why, check_s = _rehearse(root, cell, 0.01, pad)
    assert out["correct"] is True and (held, why) == (1, "seconds")
    assert check_s >= 0.99 * pad  # the padding is inside the timed span
    # under 4/3 of the measured check: a second would end past 1.5 windows
    out, held, why, again = _rehearse(root, cell, 1.2 * check_s, pad)
    assert again < 1.2 * check_s, "the check outlasted its window: retry"
    assert out["correct"] is True and out["attempted"] == 1 and out["failed"] == 0
    assert (held, why) == (1, "overrun")
    # twice the check and a half: two fit, and the rule stays out of it
    out, held, why, _ = _rehearse(root, cell, 1.5 * check_s, pad)
    assert out["correct"] is True and (held, why) == (2, "seconds")

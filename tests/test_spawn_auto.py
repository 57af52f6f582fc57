"""``spawn_auto()`` — engine selection by measured space size.

The small-space footgun (bench r4): the device engine's fixed per-run
cost dominates below ~1e5 states, where CPU BFS is 8-100x faster
(lin-reg-2's 544-state space: 927 states/s on a v5e vs 7.4k/s on one CPU
core).  ``spawn_auto`` runs a time-bounded CPU probe first; a space that
exhausts within the budget returns the finished CPU checker, a bigger
one escalates to the device engine.  No reference counterpart (the
reference has one strategy family); the CLI shape being served is
``examples/paxos.rs:314-395``'s check commands.
"""

import pytest

from stateright_tpu.checker.bfs import BfsChecker
from stateright_tpu.checker.dfs import DfsChecker
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.wavefront import TpuChecker


def test_small_space_finishes_on_cpu():
    """A space the CPU probe exhausts is answered by the probe itself —
    the device is never touched (no compile cost, no host syncs)."""
    c = TwoPhaseSys(3).checker().spawn_auto()
    assert isinstance(c, BfsChecker)
    assert c.is_done() and not c.timed_out
    assert c.unique_state_count() == 288  # examples/2pc.rs:128
    assert set(c.discoveries()) == {"abort agreement", "commit agreement"}


def test_large_space_escalates_to_device_engine():
    """A probe that times out means the space outgrew its CPU budget:
    the check restarts on the device engine and completes there."""
    c = (
        TwoPhaseSys(5)
        .checker()
        .spawn_auto(probe_secs=0.01, sync=True, capacity=1 << 17)
    )
    assert isinstance(c, TpuChecker)
    assert c.unique_state_count() == 8832  # examples/2pc.rs:133
    assert set(c.discoveries()) == {"abort agreement", "commit agreement"}


def test_no_tensor_twin_checks_on_cpu():
    """Object-form-only models (no tensor twin) go straight to CPU."""
    from stateright_tpu.core import Model, Property

    class Toggle(Model):
        def init_states(self):
            return [0]

        def actions(self, state):
            return ["flip"]

        def next_state(self, state, action):
            return 1 - state

        def properties(self):
            return [Property.sometimes("one", lambda m, s: s == 1)]

    c = Toggle().checker().spawn_auto()
    assert isinstance(c, BfsChecker)
    assert c.unique_state_count() == 2
    assert set(c.discoveries()) == {"one"}


def test_visitor_small_space_finishes_on_thread_probe():
    """Visitors: the device engines are out, but the probe still runs —
    a small space is answered by the finished thread checker without
    paying mp fork/queue setup."""
    seen = []
    c = (
        TwoPhaseSys(3)
        .checker()
        .visitor(lambda model, path: seen.append(path.final_state()))
        .spawn_auto()
    )
    assert isinstance(c, BfsChecker)
    c.join()
    assert len(seen) == 288


@pytest.mark.medium
def test_visitor_large_space_escalates_to_mp(monkeypatch):
    """A visitor run whose space outgrows the probe escalates to the
    process-parallel BFS (multi-core + visitor via replay), never to a
    device engine."""
    import os

    from stateright_tpu.checker.mp import MpBfsChecker

    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    seen = []
    c = (
        TwoPhaseSys(5)
        .checker()
        .visitor(lambda model, path: seen.append(1))
        .spawn_auto(probe_secs=0.01)
    )
    assert isinstance(c, MpBfsChecker)
    assert c.unique_state_count() == 8832
    assert len(seen) == 8832


@pytest.mark.medium
def test_visitor_escalation_defers_visits_to_run_end(monkeypatch):
    """ADVICE item 6 — the visitor-timing hole, pinned: when a visitor
    run escalates to mp-BFS, the callbacks are DEFERRED TO RUN END.
    Worker processes record per-round visit orders (fingerprints only —
    callbacks cannot cross the fork boundary) and the PARENT replays
    them round-major through the visitor only after every worker joined
    and the parent map merged, so each callback sees a complete,
    reconstructable path and the replay is a valid BFS level order.
    Callers needing LIVE per-state visits (progress bars, streaming
    consumers) should stay on the thread engine — spawn_bfs() — where
    visits interleave with exploration; this is the documented
    behavior, not a bug (docs/telemetry.md "Visitors and engines")."""
    import os

    from stateright_tpu.checker import mp as mp_mod

    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    at_replay = {}
    orig = mp_mod.MpBfsChecker._replay_visits

    def spy(self, visitor, results):
        # the moment callbacks start: the merged space must already be
        # COMPLETE (deferred-to-run-end, not live)
        at_replay["unique"] = len(self._generated)
        at_replay["count"] = self._count
        return orig(self, visitor, results)

    monkeypatch.setattr(mp_mod.MpBfsChecker, "_replay_visits", spy)
    depths = []
    c = (
        TwoPhaseSys(5)
        .checker()
        .visitor(lambda model, path: depths.append(len(path.into_vec())))
        .spawn_auto(probe_secs=0.01)
    )
    assert isinstance(c, mp_mod.MpBfsChecker)
    # visits began only after the full space was merged...
    assert at_replay["unique"] == 8832
    # ...fired exactly once per unique state...
    assert len(depths) == 8832
    # ...in round-major replay order = a valid BFS level order
    assert depths == sorted(depths)


def test_symmetry_probe_uses_dfs():
    """With ``symmetry()`` the CPU probe is DFS (the host engine that
    supports representative dedup, as in the reference where symmetry is
    DFS-only) and pins the reduced count."""
    c = TwoPhaseSys(5).checker().symmetry().spawn_auto(probe_secs=30.0)
    assert isinstance(c, DfsChecker)
    assert c.unique_state_count() == 665  # examples/2pc.rs:138


def test_tiny_user_timeout_stays_on_cpu():
    """A user timeout within the probe budget means the whole run fits in
    the probe: no point paying device setup for a run this short."""
    c = TwoPhaseSys(3).checker().timeout(0.5).spawn_auto(probe_secs=2.0)
    assert isinstance(c, BfsChecker)
    c.join()
    assert c.unique_state_count() == 288


def test_check_auto_cli_verb(capsys):
    """The ``check-auto`` CLI verb runs end-to-end on every model that
    wires it, including argument passing (the single-copy NETWORK
    argument regression class)."""
    from stateright_tpu.models import (
        single_copy_register,
        two_phase_commit,
        write_once_register,
    )

    two_phase_commit.main(["check-auto", "3"])
    out = capsys.readouterr().out
    assert "auto engine selection" in out
    assert "unique=288" in out

    single_copy_register.main(["check-auto", "2", "ordered"])
    out = capsys.readouterr().out
    assert "Done." in out  # the ordered network parsed and ran

    write_once_register.main(["check-auto", "2", "1"])
    out = capsys.readouterr().out
    assert "unique=71" in out


def test_timed_out_flag_distinguishes_deadline_from_completion():
    """``timed_out`` is the probe's decision signal: set only by the
    deadline, not by finishing or reaching target_states."""
    done = TwoPhaseSys(3).checker().spawn_bfs().join()
    assert not done.timed_out
    capped = (
        TwoPhaseSys(5).checker().target_states(100).spawn_bfs().join()
    )
    assert not capped.timed_out
    cut = TwoPhaseSys(6).checker().timeout(0.01).spawn_bfs().join()
    assert cut.timed_out
    assert cut.unique_state_count() < 30_000  # stopped well short

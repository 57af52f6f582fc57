"""Example-model parity tests: pinned unique-state counts and witness traces
(reference ``examples/*.rs`` tests; values mirrored in BASELINE.md)."""

import pytest

from stateright_tpu import Property
from stateright_tpu.actor import Deliver, Id
from stateright_tpu.actor.register import Get, GetOk, Internal, Put, PutOk
from stateright_tpu.models.increment import Increment
from stateright_tpu.models.increment_lock import IncrementLock
from stateright_tpu.models.linearizable_register import (
    AckQuery,
    AckRecord,
    Query,
    Record,
    abd_model,
)
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.single_copy_register import single_copy_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys


# ---------------------------------------------------------------------------
# 2PC (reference ``2pc.rs:125-140``)
# ---------------------------------------------------------------------------

def test_2pc_bfs_3_rms():
    checker = TwoPhaseSys(3).checker().spawn_bfs().join()
    assert checker.unique_state_count() == 288
    checker.assert_properties()


def test_2pc_dfs_5_rms():
    checker = TwoPhaseSys(5).checker().spawn_dfs().join()
    assert checker.unique_state_count() == 8832
    checker.assert_properties()


def test_2pc_dfs_5_rms_symmetry():
    checker = TwoPhaseSys(5).checker().symmetry().spawn_dfs().join()
    assert checker.unique_state_count() == 665
    checker.assert_properties()


# ---------------------------------------------------------------------------
# single-copy register (reference ``single-copy-register.rs:84-122``)
# ---------------------------------------------------------------------------

def test_single_copy_one_server_linearizable():
    checker = single_copy_model(2, 1).checker().spawn_dfs().join()
    assert checker.unique_state_count() == 93
    checker.assert_properties()
    checker.assert_discovery(
        "value chosen",
        [
            Deliver(src=Id(2), dst=Id(0), msg=Put(2, "B")),
            Deliver(src=Id(0), dst=Id(2), msg=PutOk(2)),
            Deliver(src=Id(2), dst=Id(0), msg=Get(4)),
        ],
    )


def test_single_copy_two_servers_violation():
    checker = single_copy_model(2, 2).checker().spawn_bfs().join()
    # stale read: client 3 puts 'B' to server 1, then reads '\0' from server 0
    checker.assert_discovery(
        "linearizable",
        [
            Deliver(src=Id(3), dst=Id(1), msg=Put(3, "B")),
            Deliver(src=Id(1), dst=Id(3), msg=PutOk(3)),
            Deliver(src=Id(3), dst=Id(0), msg=Get(6)),
            Deliver(src=Id(0), dst=Id(3), msg=GetOk(6, "\0")),
        ],
    )
    # NOTE: the reference pins 20 here; the exact early-exit count depends on
    # within-level exploration order (its HashSet iteration order), which is
    # implementation-specific. Ours is deterministic too, just different.
    assert checker.unique_state_count() == 26


# ---------------------------------------------------------------------------
# ABD linearizable register (reference ``linearizable-register.rs:234-282``)
# ---------------------------------------------------------------------------

def test_abd_2_clients_2_servers():
    checker = abd_model(2, 2).checker().spawn_bfs().join()
    assert checker.unique_state_count() == 544
    checker.assert_properties()
    checker.assert_discovery(
        "value chosen",
        [
            Deliver(src=Id(3), dst=Id(1), msg=Put(3, "B")),
            Deliver(src=Id(1), dst=Id(0), msg=Internal(Query(3))),
            Deliver(
                src=Id(0),
                dst=Id(1),
                msg=Internal(AckQuery(3, (0, Id(0)), "\0")),
            ),
            Deliver(
                src=Id(1),
                dst=Id(0),
                msg=Internal(Record(3, (1, Id(1)), "B")),
            ),
            Deliver(src=Id(0), dst=Id(1), msg=Internal(AckRecord(3))),
            Deliver(src=Id(1), dst=Id(3), msg=PutOk(3)),
            Deliver(src=Id(3), dst=Id(0), msg=Get(6)),
            Deliver(src=Id(0), dst=Id(1), msg=Internal(Query(6))),
            Deliver(
                src=Id(1),
                dst=Id(0),
                msg=Internal(AckQuery(6, (1, Id(1)), "B")),
            ),
            Deliver(
                src=Id(0),
                dst=Id(1),
                msg=Internal(Record(6, (1, Id(1)), "B")),
            ),
            Deliver(src=Id(1), dst=Id(0), msg=Internal(AckRecord(6))),
        ],
    )


def test_abd_dfs_matches():
    checker = abd_model(2, 2).checker().spawn_dfs().join()
    assert checker.unique_state_count() == 544
    checker.assert_properties()


# ---------------------------------------------------------------------------
# Paxos (reference ``paxos.rs:270-312``) — the benchmark workload
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paxos_2_clients_3_servers():
    checker = paxos_model(2, 3).checker().spawn_bfs().join()
    assert checker.unique_state_count() == 16668
    checker.assert_properties()
    checker.assert_discovery(
        "value chosen",
        [
            Deliver(src=Id(4), dst=Id(1), msg=Put(4, "B")),
            Deliver(
                src=Id(1),
                dst=Id(0),
                msg=Internal(("prepare", (1, Id(1)))),
            ),
            Deliver(
                src=Id(0),
                dst=Id(1),
                msg=Internal(("prepared", (1, Id(1)), None)),
            ),
            Deliver(
                src=Id(1),
                dst=Id(2),
                msg=Internal(("accept", (1, Id(1)), (4, Id(4), "B"))),
            ),
            Deliver(
                src=Id(2),
                dst=Id(1),
                msg=Internal(("accepted", (1, Id(1)))),
            ),
            Deliver(src=Id(1), dst=Id(4), msg=PutOk(4)),
            Deliver(
                src=Id(1),
                dst=Id(2),
                msg=Internal(("decided", (1, Id(1)), (4, Id(4), "B"))),
            ),
            Deliver(src=Id(4), dst=Id(2), msg=Get(8)),
        ],
    )


# ---------------------------------------------------------------------------
# increment / increment_lock (reference ``increment.rs:36-105``)
# ---------------------------------------------------------------------------

class _IncrementFull(Increment):
    """Disable early exit to enumerate the documented full space."""

    def properties(self):
        return list(super().properties()) + [
            Property.sometimes("never", lambda m, s: False)
        ]


def test_increment_full_space_documented_counts():
    checker = _IncrementFull(2).checker().spawn_dfs().join()
    assert checker.unique_state_count() == 13
    checker = _IncrementFull(2).checker().symmetry().spawn_dfs().join()
    assert checker.unique_state_count() == 8


def test_increment_race_found():
    checker = Increment(2).checker().spawn_bfs().join()
    path = checker.assert_any_discovery("fin")  # the data race
    # interleaved read-read-write-write: counter 1, finished 2
    final = path.final_state()
    assert sum(1 for _t, pc in final.s if pc == 3) != final.i


def test_increment_lock_holds():
    checker = IncrementLock(2).checker().spawn_bfs().join()
    checker.assert_no_discovery("fin")
    checker.assert_no_discovery("mutex")


def test_increment_lock_symmetry():
    full = IncrementLock(3).checker().spawn_dfs().join()
    sym = IncrementLock(3).checker().symmetry().spawn_dfs().join()
    # same verdicts under reduction
    assert not sym.discoveries() and not full.discoveries()


def test_check_tpu_without_a_device_twin_exits_nonzero():
    """A ``check-tpu`` verb that cannot run on the device must not return
    as if it had: ABD under a duplicating network has no device twin
    (unbounded clocks), and the verb exits non-zero saying so."""
    from stateright_tpu.models import linearizable_register

    with pytest.raises(SystemExit) as exc:
        linearizable_register.main(
            ["check-tpu", "2", "unordered_duplicating"]
        )
    assert exc.value.code not in (0, None)
    assert "no device twin" in str(exc.value.code)

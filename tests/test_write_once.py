"""Write-once register example: end-to-end checks, pinned counts, symmetry,
and compiled-device-twin parity (closing the reference's unexercised
write-once harness, ``src/actor/write_once_register.rs:119-299``)."""

import pytest

from stateright_tpu.actor import Envelope, Id
from stateright_tpu.actor.network import Network
from stateright_tpu.actor.register import NULL_VALUE
from stateright_tpu.models.write_once_register import (
    WOServer,
    main,
    server_representative,
    wo_register_model,
)
from stateright_tpu.semantics import LinearizabilityTester, WORegister


def test_one_server_is_linearizable_pinned_counts():
    checker = wo_register_model(2, 1).checker().spawn_bfs().join()
    assert checker.unique_state_count() == 71
    assert checker.state_count() == 97
    checker.assert_properties()  # no linearizability violation
    assert sorted(checker.discoveries()) == ["value chosen"]


def test_one_server_dfs_agrees():
    checker = wo_register_model(2, 1).checker().spawn_dfs().join()
    assert checker.unique_state_count() == 71
    checker.assert_properties()


def test_two_independent_servers_violate_linearizability():
    checker = wo_register_model(2, 2).checker().spawn_dfs().join()
    path = checker.assert_any_discovery("linearizable")
    # the witness ends in a genuinely inconsistent history
    assert not path.final_state().history.is_consistent()


def test_second_write_fails_and_history_records_write_fail():
    model = wo_register_model(2, 1)
    state = model.init_states()[0]

    def deliver(pred):
        action = next(
            a
            for a in model.actions(state)
            if type(a).__name__ == "Deliver" and pred(a)
        )
        return model.next_state(state, action)

    # both puts reach the server (first wins), then both replies deliver
    state = deliver(lambda a: a.msg[0] == "put" and a.src == Id(1))
    state = deliver(lambda a: a.msg[0] == "put" and a.src == Id(2))
    assert {e.msg[0] for e in state.network.iter_all()} == {
        "put_ok",
        "put_fail",
    }
    state = deliver(lambda a: a.msg[0] == "put_ok")
    state = deliver(lambda a: a.msg[0] == "put_fail")
    rets = sorted(
        ret
        for t in state.history.history_by_thread.values()
        for (_, _, ret) in t
    )
    assert rets == [("write_fail",), ("write_ok",)]
    # the server kept the first value
    assert state.actor_states[0] == "A"


def test_symmetry_preserves_verdicts():
    plain = wo_register_model(2, 2).checker().spawn_dfs().join()
    sym = (
        wo_register_model(2, 2)
        .checker()
        .symmetry_with(lambda s: server_representative(s, 2))
        .spawn_dfs()
        .join()
    )
    assert sorted(plain.discoveries()) == sorted(sym.discoveries()) == [
        "linearizable",
        "value chosen",
    ]


def test_server_representative_canonicalizes_permuted_servers():
    """Two hand-built states differing only by a server permutation (with
    ids rewritten through the network) share a representative; clients are
    never permuted."""
    model = wo_register_model(1, 2)
    base = model.init_states()[0]
    S = type(base)

    def with_servers(v0, v1, dst):
        return S(
            actor_states=(v0, v1) + base.actor_states[2:],
            network=Network.new_unordered_nonduplicating().send(
                Envelope(src=Id(2), dst=Id(dst), msg=("get", 9))
            ),
            is_timer_set=base.is_timer_set,
            history=base.history,
        )

    a = with_servers("A", NULL_VALUE, 0)
    b = with_servers(NULL_VALUE, "A", 1)  # servers swapped, ids rewritten
    ra = server_representative(a, 2)
    rb = server_representative(b, 2)
    assert ra == rb
    # fixed point + client block untouched
    assert server_representative(ra, 2) == ra
    assert ra.actor_states[2:] == base.actor_states[2:]


def test_wo_spec_semantics():
    t = LinearizabilityTester(WORegister(None))
    t = t.on_invoke(1, ("write", "A")).on_return(1, ("write_ok",))
    t = t.on_invoke(2, ("write", "B")).on_return(2, ("write_fail",))
    t = t.on_invoke(1, ("read",)).on_return(1, ("read_ok", "A"))
    assert t.is_consistent()
    # a read of B is impossible: B's write failed
    t2 = LinearizabilityTester(WORegister(None))
    t2 = t2.on_invoke(1, ("write", "A")).on_return(1, ("write_ok",))
    t2 = t2.on_invoke(2, ("write", "B")).on_return(2, ("write_fail",))
    t2 = t2.on_invoke(1, ("read",)).on_return(1, ("read_ok", "B"))
    assert not t2.is_consistent()


def test_compiled_twin_parity_single_device():
    cpu = wo_register_model(2, 1).checker().spawn_bfs().join()
    tpu = wo_register_model(2, 1).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert tpu.unique_state_count() == cpu.unique_state_count() == 71
    assert tpu.state_count() == cpu.state_count() == 97
    assert sorted(tpu.discoveries()) == sorted(cpu.discoveries())
    tpu.assert_properties()


def test_compiled_twin_parity_mesh():
    tpu = wo_register_model(2, 1).checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 12, frontier_capacity=1 << 7
    )
    assert tpu.unique_state_count() == 71
    tpu.assert_properties()


def test_compiled_twin_finds_violation_on_two_servers():
    tpu = wo_register_model(2, 2).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    path = tpu.assert_any_discovery("linearizable")
    assert not path.final_state().history.is_consistent()


def test_cli_check_smoke(capsys):
    main(["check", "2", "1"])
    out = capsys.readouterr().out
    assert "write-once register" in out and "sec=" in out


def test_cli_check_sym_smoke(capsys):
    main(["check-sym", "2", "2"])
    out = capsys.readouterr().out
    assert "symmetry" in out and "sec=" in out

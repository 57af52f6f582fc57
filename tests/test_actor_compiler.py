"""Mechanical actor→tensor compiler: equivalence + engine parity.

The compiler (``parallel/actor_compiler.py``) must reproduce the object
model's transition semantics (reference ``src/actor/model.rs:187-306``)
table-for-table: pinned counts 544 (ABD, reference
``linearizable-register.rs:258``) and 93 (single-copy, reference
``single-copy-register.rs:100``), plus crawl-level successor-set equality.
"""

import pytest

from stateright_tpu.core import Expectation
from stateright_tpu.models.linearizable_register import abd_model
from stateright_tpu.models.paxos import paxos_model
from stateright_tpu.models.single_copy_register import single_copy_model
from stateright_tpu.parallel.actor_compiler import CompiledActorTensor
from stateright_tpu.parallel.history_tensor import LinHistoryCodec
from stateright_tpu.semantics import LinearizabilityTester
from stateright_tpu.semantics.register import READ, Register, write

from test_paxos_tensor import crawl_and_check


# ---------------------------------------------------------------------------
# history codec
# ---------------------------------------------------------------------------


def test_history_codec_roundtrip_and_verdicts():
    hc = LinHistoryCodec([3, 4], ["A", "B"], "\0")
    hc.ensure_table()  # the closure strategy no longer enumerates eagerly
    # every enumerated joint state round-trips and the baked verdict equals
    # the live tester's
    seen = 0
    t = LinearizabilityTester(Register("\0"))
    t = t.on_invoke(3, write("A")).on_invoke(4, write("B"))
    frontier = [t]
    visited = {t}
    while frontier:
        cur = frontier.pop()
        seen += 1
        fields = hc.fields_of_tester(cur)
        assert hc.tester_of_fields(fields) == cur
        key = hc.key_of_fields(fields)
        import numpy as np

        i = int(np.searchsorted(hc.table_keys, key))
        assert hc.table_keys[i] == key
        assert bool(hc.table_ok[i]) == cur.is_consistent()
        for thread in (3, 4):
            infl = cur.in_flight_by_thread.get(thread)
            comp = cur.history_by_thread.get(thread, ())
            if infl is not None and infl[1] == READ:
                nxts = [
                    cur.on_return(thread, ("read_ok", v))
                    for v in ("\0", "A", "B")
                ]
            elif infl is not None:
                nxts = [cur.on_return(thread, ("write_ok",))]
            elif len(comp) == 1:
                nxts = [cur.on_invoke(thread, READ)]
            else:
                nxts = []
            for n in nxts:
                if n not in visited:
                    visited.add(n)
                    frontier.append(n)
    assert seen == len(hc.table_keys) == 124


def test_multiop_codec_roundtrip_and_verdicts():
    """put_count=2 codec (reference ``register.rs:96,178-186``): every
    enumerated joint tester state round-trips fields→tester→fields and the
    baked verdict equals the live tester's — including write-invocation
    snapshots, which the K=1 layout cannot express."""
    import numpy as np

    from stateright_tpu.parallel.history_tensor import MultiOpLinHistoryCodec

    hc = MultiOpLinHistoryCodec([2, 3], [["A", "Z"], ["B", "Y"]], "\0")
    assert hc.K == 2 and len(hc.table_keys) == 2016
    step = max(1, len(hc.table_keys) // 200)
    for idx in range(0, len(hc.table_keys), step):
        key = int(hc.table_keys[idx])
        fields = []
        for i in range(hc.C):
            word = (key >> (i * hc.thread_bits)) & (
                (1 << hc.thread_bits) - 1
            )
            phase = word & ((1 << hc.phase_bits) - 1)
            off = hc.phase_bits
            snaps = []
            for _ in range(hc.K):
                snaps.append((word >> off) & ((1 << hc.snap_bits) - 1))
                off += hc.snap_bits
            rval = (word >> off) & ((1 << hc.rval_bits) - 1)
            fields.append((phase, tuple(snaps), rval))
        tester = hc.tester_of_fields(fields)
        assert hc.fields_of_tester(tester) == fields
        assert hc.key_of_fields(fields) == key
        assert bool(hc.table_ok[idx]) == tester.is_consistent()


# ---------------------------------------------------------------------------
# single-copy register (compiled)
# ---------------------------------------------------------------------------


@pytest.mark.medium
# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_single_copy_compiled_equivalence():
    m = single_copy_model(2, 1)
    tm = m.tensor_model()
    assert isinstance(tm, CompiledActorTensor)
    seen = crawl_and_check(m, tm)
    assert len(seen) == 93


def test_single_copy_tpu_pinned_counts():
    m = single_copy_model(2, 1)
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 10, frontier_capacity=1 << 7)
    assert t.unique_state_count() == 93
    assert set(t.discoveries()) == {"value chosen"}
    t.assert_properties()


def test_single_copy_two_servers_tpu_finds_violation():
    m = single_copy_model(2, 2)
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 10, frontier_capacity=1 << 7)
    disc = t.discoveries()
    assert set(disc) == {"linearizable", "value chosen"}
    # the counterexample is a real trace: re-execution reaches a state whose
    # history is NOT linearizable (reference ``single-copy-register.rs:103-120``)
    final = disc["linearizable"].final_state()
    assert not final.history.is_consistent()


def test_single_copy_mesh_matches():
    m = single_copy_model(2, 1)
    t = m.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 10, frontier_capacity=1 << 7
    )
    assert t.unique_state_count() == 93
    assert set(t.discoveries()) == {"value chosen"}


# ---------------------------------------------------------------------------
# ABD register (compiled)
# ---------------------------------------------------------------------------


@pytest.mark.medium
def test_abd_compiled_prefix_equivalence():
    m = abd_model(2, 2)
    tm = m.tensor_model()
    assert isinstance(tm, CompiledActorTensor)
    crawl_and_check(m, tm, max_levels=5)


def test_abd_tpu_pinned_counts():
    m = abd_model(2, 2)
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 12, frontier_capacity=1 << 9)
    assert t.unique_state_count() == 544
    assert set(t.discoveries()) == {"value chosen"}
    t.assert_properties()


def test_abd_put2_host_device_pinned():
    """put_count=2 ABD (the round-4 device-story gap: reference
    ``register.rs:96,178-186`` supports arbitrary put_count, the compiler
    stopped at 1): full enumeration pinned host=device with discovery
    parity.  ABD stays linearizable, so no 'linearizable' discovery."""
    m = abd_model(2, 2, put_count=2)
    h = m.checker().spawn_bfs().join()
    assert h.unique_state_count() == 2980
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 14)
    assert t.unique_state_count() == 2980
    assert sorted(t.discoveries()) == sorted(h.discoveries()) == [
        "value chosen"
    ]
    t.assert_properties()


def test_singlecopy_put2_violation_discovery_parity():
    """The put_count=2 linearizability verdict's FALSE path: two
    unreplicated servers violate; host and device both discover it, and
    the device witness re-executes to a genuinely inconsistent history."""
    m = single_copy_model(2, 2, put_count=2)
    h = m.checker().spawn_bfs().join()
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 12)
    assert sorted(t.discoveries()) == sorted(h.discoveries()) == [
        "linearizable",
        "value chosen",
    ]
    final = t.discoveries()["linearizable"].final_state()
    assert not final.history.is_consistent()
    h.assert_discovery(
        "linearizable", list(t.discoveries()["linearizable"].actions())
    )


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_singlecopy_put2_full_crawl_equivalence():
    """Per-state equivalence over the FULL put_count=2 single-copy space
    (no early exit): encode/decode round-trip, fingerprint agreement,
    successor-set equality, and property-mask agreement — including
    states where the device linearizability verdict is False."""
    m = single_copy_model(2, 2, put_count=2)
    tm = m.tensor_model()
    assert isinstance(tm, CompiledActorTensor)
    seen = crawl_and_check(m, tm)
    assert len(seen) == 384


def test_singlecopy_put2_single_server_pinned():
    m = single_copy_model(2, 1, put_count=2)
    h = m.checker().spawn_bfs().join()
    assert h.unique_state_count() == 369
    t = m.checker().spawn_tpu(sync=True, capacity=1 << 12)
    assert t.unique_state_count() == 369
    assert set(t.discoveries()) == {"value chosen"}
    t.assert_properties()


def test_wo_rejects_put2():
    """Write-once workloads stay put_count=1 (a failed write changes
    which op takes effect; the multi-op codec models write_ok only)."""
    from stateright_tpu.actor.write_once_register import WORegisterClient
    from stateright_tpu.models.write_once_register import wo_register_model
    from stateright_tpu.parallel.actor_compiler import (
        CompileError,
        compile_actor_model,
    )

    m = wo_register_model(2, 1)
    for a in m.actors:
        if isinstance(a, WORegisterClient):
            a.put_count = 2
    with pytest.raises(CompileError, match="put_count"):
        compile_actor_model(m)


def test_abd_mesh_matches():
    m = abd_model(2, 2)
    t = m.checker().spawn_tpu(
        devices=8, sync=True, capacity=1 << 12, frontier_capacity=1 << 9
    )
    assert t.unique_state_count() == 544
    assert set(t.discoveries()) == {"value chosen"}


# ---------------------------------------------------------------------------
# compiled paxos agrees with the hand-built twin
# ---------------------------------------------------------------------------


def test_compiled_paxos_agrees_with_hand_twin():
    # same config through both twins: unique counts and discoveries agree
    hand = paxos_model(1, 3)
    assert not isinstance(hand.tensor_model(), CompiledActorTensor)
    h = hand.checker().spawn_tpu(
        sync=True, capacity=1 << 12, frontier_capacity=1 << 9
    )

    compiled = paxos_model(1, 3)
    tm = compiled._compiled_tensor(1)
    assert isinstance(tm, CompiledActorTensor)
    # force the compiled twin in place of the hand twin
    object.__setattr__(compiled, "_tensor_model_cache", tm)
    c = compiled.checker().spawn_tpu(
        sync=True, capacity=1 << 12, frontier_capacity=1 << 9
    )
    assert h.unique_state_count() == c.unique_state_count() == 265
    assert set(h.discoveries()) == set(c.discoveries())


# -- duplicating-network compilation -----------------------------------------


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_single_copy_duplicating_compiled_equivalence():
    """Duplicating network (redelivery allowed; reference network.rs:203-205)
    through the mechanical compiler: full device/host parity."""
    from stateright_tpu.actor import Network

    m = single_copy_model(2, 1, Network.new_unordered_duplicating())
    tm = m.tensor_model()
    assert tm is not None and tm.dup
    crawl_and_check(m, tm)


def test_single_copy_duplicating_full_enumeration_parity():
    """1 client / 1 server: no concurrency, so linearizability holds and
    both engines enumerate the whole (finite) duplicating-network space —
    counts must agree exactly."""
    from stateright_tpu.actor import Network

    def build():
        return single_copy_model(1, 1, Network.new_unordered_duplicating())

    cpu = build().checker().spawn_bfs().join()
    tpu = build().checker().spawn_tpu(sync=True)
    assert "linearizable" not in cpu.discoveries()
    assert cpu.unique_state_count() == tpu.unique_state_count()
    assert set(cpu.discoveries()) == set(tpu.discoveries())


def test_single_copy_lossy_duplicating_parity():
    """Lossy + duplicating (the reference's harshest unordered config): a
    drop removes the envelope forever (network.rs:242-244) while deliveries
    never consume it; full-enumeration count parity on the 1-client system."""
    from stateright_tpu.actor import Network

    def build():
        m = single_copy_model(1, 1, Network.new_unordered_duplicating())
        m.lossy_network(True)
        return m

    cpu = build().checker().spawn_bfs().join()
    tpu = build().checker().spawn_tpu(sync=True)
    assert "linearizable" not in cpu.discoveries()
    assert cpu.unique_state_count() == tpu.unique_state_count()
    assert set(cpu.discoveries()) == set(tpu.discoveries())


def test_bounded_models_reject_duplicating_twins():
    """ABD/paxos closure bounds assume at-most-once delivery (a redelivered
    put restarts a round, growing clocks/ballots unboundedly), so their
    compiled twins must refuse duplicating networks and fall back to
    structural fingerprints rather than poison real reachable states."""
    from stateright_tpu.actor import Network

    m = abd_model(1, 2, Network.new_unordered_duplicating())
    assert m.tensor_model() is None
    # structural fingerprints survive genuinely redelivery-reachable states
    s = m.init_states()[0]
    for _ in range(8):
        nxt = m.next_states(s)
        if not nxt:
            break
        s = nxt[0]
        m.fingerprint_state(s)

    p = paxos_model(1, 3, Network.new_unordered_duplicating())
    assert p.tensor_model() is None


# -- ordered-network compilation ---------------------------------------------


@pytest.mark.medium
def test_single_copy_ordered_compiled_equivalence():
    """Ordered (per-pair FIFO) network through the compiler: rank-in-slot
    encoding must reproduce the object flows state-for-state."""
    from stateright_tpu.actor import Network

    m = single_copy_model(2, 1, Network.new_ordered())
    tm = m.tensor_model()
    assert tm is not None and tm.ordered
    crawl_and_check(m, tm)


@pytest.mark.medium
def test_abd_ordered_compiled_equivalence():
    from stateright_tpu.actor import Network

    m = abd_model(2, 2, Network.new_ordered())
    tm = m.tensor_model()
    assert tm is not None and tm.ordered
    crawl_and_check(m, tm, max_levels=6)


def test_abd3_ordered_compiles_to_a_device_twin():
    """The reference bench's ``lin-reg 3 ordered`` config (bench.sh:31-34)
    compiles — pinning the fact the round-2 bench comment got wrong (it
    claimed ordered networks were outside the compiled fragment).  Full
    engine parity for ordered ABD is pinned at (2,2) below; the (3,2)
    config's device rate is recorded by bench.py's protocol sweep."""
    from stateright_tpu.actor import Network

    m = abd_model(3, 2, Network.new_ordered())
    tm = m.tensor_model()
    assert tm is not None and tm.ordered


def test_abd_ordered_engine_parity():
    """The reference bench protocol's ``lin-reg N ordered`` config
    (bench.sh:31-34) on the device engine."""
    from stateright_tpu.actor import Network

    def build():
        return abd_model(2, 2, Network.new_ordered())

    cpu = build().checker().spawn_bfs().join()
    tpu = build().checker().spawn_tpu(sync=True)
    assert "linearizable" not in cpu.discoveries()
    assert cpu.unique_state_count() == tpu.unique_state_count()
    assert set(cpu.discoveries()) == set(tpu.discoveries())


def test_single_copy_ordered_lossy_parity():
    """Lossy ordered network: drops remove flow heads only (the object model
    enumerates Drop over iter_deliverable)."""
    from stateright_tpu.actor import Network

    def build():
        m = single_copy_model(1, 1, Network.new_ordered())
        m.lossy_network(True)
        return m

    cpu = build().checker().spawn_bfs().join()
    tpu = build().checker().spawn_tpu(sync=True)
    assert cpu.unique_state_count() == tpu.unique_state_count()
    assert set(cpu.discoveries()) == set(tpu.discoveries())


# re-tiered fast->slow (PR 2): the fast tier blew the 870s tier-1 budget
@pytest.mark.slow
def test_paxos_ordered_lossy_deep_flow_equivalence():
    """Lossy ordered paxos reaches ≥2-deep flows (e.g. prepare then accept
    queued on one pair), exercising head-only drop semantics and mid-flow
    rank bookkeeping that shallow configs cannot distinguish."""
    from stateright_tpu.actor import Network

    m = paxos_model(1, 3, Network.new_ordered())
    m.lossy_network(True)
    tm = m.tensor_model()
    assert tm is not None and tm.ordered
    crawl_and_check(m, tm)


def test_paxos_ordered_engine_parity():
    from stateright_tpu.actor import Network

    def build():
        return paxos_model(1, 3, Network.new_ordered())

    cpu = build().checker().spawn_bfs().join()
    tpu = build().checker().spawn_tpu(sync=True)
    assert cpu.unique_state_count() == tpu.unique_state_count() == 99
    assert set(cpu.discoveries()) == set(tpu.discoveries())


def test_register_workload_accepts_extra_factored_properties():
    """Register workloads compile the two standard history-driven
    properties PLUS any factored extras — evaluated as tabulated lookups
    on device, the same predicate directly on host."""
    from stateright_tpu.actor.device_props import exists_actor, forall_actors
    from stateright_tpu.actor.register import NULL_VALUE
    from stateright_tpu.models.single_copy_register import single_copy_model

    m = single_copy_model(2, 1)
    m.property(
        Expectation.ALWAYS,
        "server value known",  # holds: never discovered
        forall_actors(lambda i, s: i != 0 or s in (NULL_VALUE, "A", "B")),
    )
    m.property(
        Expectation.SOMETIMES,
        "server took a write",  # discovered once a put lands
        exists_actor(lambda i, s: i == 0 and s in ("A", "B")),
    )
    h = m.checker().spawn_bfs().join()
    c = m.checker().spawn_tpu(sync=True, capacity=1 << 13)
    assert h.unique_state_count() == c.unique_state_count() == 93
    assert (
        sorted(h.discoveries())
        == sorted(c.discoveries())
        == ["server took a write", "value chosen"]
    )


def test_register_workload_rejects_non_factored_extras():
    from stateright_tpu.models.single_copy_register import single_copy_model
    from stateright_tpu.parallel.actor_compiler import (
        CompileError,
        compile_actor_model,
    )

    m = single_copy_model(2, 1)
    m.property(Expectation.ALWAYS, "opaque", lambda mm, s: True)
    with pytest.raises(CompileError, match="factored"):
        compile_actor_model(m)

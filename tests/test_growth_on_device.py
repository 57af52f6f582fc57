"""A growth event is a device program from one carry to a larger one (PR 48).

``TpuChecker._grow``, where ``_grows_on_device`` says so, splits every bucket
where the table lies (``ops/buckets.bucket_split``) and slides the live queue
window to row 0 of buffers of the new allocation (``wavefront._slide_queue``);
the host decides from the packed stats vector it already holds.  Held here, on
XLA:CPU:

 - a check from a tiny table and queue (seven table growths, queue doublings,
   candidate-budget doublings) equals the presized check in ``unique``,
   ``states``, max depth and every discovery path, says ``path="device"`` on
   every ``growth`` record, and moves under 4 KB across over all of them;
 - the carry after EVERY growth of such a run equals the host executor's of
   the pulled carry, buffer by buffer and bit for bit - and so does a jump of
   several rungs at once, which the run loop itself never asks for (a step
   inserts at most ``cand <= cap / 4`` states past a load of 25%);
 - who keeps the host path: a spill-armed run says ``path="host"``.

(``bucket_split`` against ``host_bucket_rehash`` alone, and the fence on what
it compiles to, are ``tests/test_buckets.py``'s.)
"""

import numpy as np
import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel import wavefront as wf
from stateright_tpu.parallel.carry import QUEUE_FIELDS, ST_DISC, leaf_names
from stateright_tpu.telemetry.memory import ENV_DEVICE_BYTES

# the smallest table the engine takes (4 buckets) under a queue of 64 rows
# and a candidate budget a batch overflows: every status the loop grows on
TINY = dict(capacity=64, queue_capacity=64, batch=32, cand=16,
            steps_per_call=8)
PRESIZED = dict(capacity=1 << 13, queue_capacity=1 << 11, batch=32,
                cand=32 * 13, steps_per_call=8)
UNIQUE = 1568  # 2pc-4


def _check(**spawn):
    c = TwoPhaseSys(4).checker().telemetry().spawn_tpu(sync=True, **spawn)
    c.join()
    return c


def _equal_carries(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    names = leaf_names(got)
    for name, g, w in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (name, g.shape, w.shape)
        np.testing.assert_array_equal(g, w, err_msg=name)


def _held_to_the_host(real, seen):
    """``_grow`` with every call of it that transforms the carry where it
    lies held to the host executor on the pulled carry, every buffer of
    the thirteen, bit for bit; the FIRST such call is preceded by a jump of
    three rungs at once on a copy of its carry."""

    def grow(self, carry, status, cap, qcap, batch, cand, at=None, parent=None):
        assert self._grows_on_device(carry)
        pulled = carry.pulled()

        def host(status, cand):
            # the other executor, off the record: no span, no byte counted
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(self, "flight_recorder", None)
                patch.setattr(self, "_grows_on_device", lambda carry: False)
                return real(self, pulled, status, cap, qcap, batch, cand, at=at)

        if not seen:
            # a budget no run reaches at this table: 64 -> 512 slots
            full = wf._STATUS_TABLE_FULL
            got = real(self, carry, full, cap, qcap, batch, cap * 2, at=at,
                       parent=parent)
            want = host(full, cap * 2)
            assert got[1] == want[1] == cap * 8 and got[2:4] == want[2:4]
            _equal_carries(got[0], want[0])
            seen.append(("jump", cap, got[1]))
            # the jump's slides ran in place: hand the run its buffers back
            carry = pulled.pushed()
        out = real(self, carry, status, cap, qcap, batch, cand, at=at,
                   parent=parent)
        want = host(status, cand)
        assert out[1:4] == want[1:4]
        _equal_carries(out[0], want[0])
        assert all(isinstance(c, jax.Array) for c in jax.tree.leaves(out[0]))
        if out[4] is not None:
            per_bucket = (
                np.asarray(want[0].table_fp).reshape(-1, wf.SLOTS) != wf.EMPTY
            ).sum(axis=1)
            assert np.asarray(out[4]).tolist() == np.bincount(
                per_bucket, minlength=wf.SLOTS + 1
            ).tolist()
        if out[0].q_rows is not carry.q_rows:
            # (a candidate budget that fits the table transforms nothing)
            seen.append((status, cap, out[1], qcap, out[2]))
        return out

    return grow


@pytest.fixture(scope="module")
def grown():
    """One check from the tiny capacities, every growth of it compared with
    the host's as it happens: ``(checker, the growths seen)``."""
    seen = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            wf.TpuChecker, "_grow",
            _held_to_the_host(wf.TpuChecker._grow, seen),
        )
        return _check(**TINY), seen


def test_a_check_that_grows_on_the_device_equals_the_presized_check(grown):
    c, _ = grown
    base = _check(**PRESIZED)
    assert not base.growth_events
    assert c.unique_state_count() == base.unique_state_count() == UNIQUE
    assert c.state_count() == base.state_count()
    assert c._results["depth"] == base._results["depth"]
    # Path equality is every state and every action of the path
    assert c.discoveries() == base.discoveries() and base.discoveries()
    growth = c.flight_recorder.records("growth")
    assert {g["status"] for g in growth} == {
        "table_full", "queue_full", "cand_full",
    }
    assert c._cap == 1 << 13 and c._qcap > TINY["queue_capacity"]


def test_the_carry_after_every_growth_is_the_hosts_bit_for_bit(grown):
    c, seen = grown
    assert seen[0] == ("jump", 64, 512)
    events = seen[1:]
    # a candidate-budget doubling that fits the table transforms nothing
    assert 7 <= len(events) <= len(c.growth_events)
    assert sum(1 for e in events if e[2] > e[1]) >= 3  # table growths
    assert sum(1 for e in events if e[4] > e[3]) >= 1  # queue doublings
    assert any(e[2] == e[1] for e in events)  # a slide alone
    for k in QUEUE_FIELDS:
        assert getattr(c._final_carry, k).shape[0] == c._qalloc(c._qcap, c._batch)


def test_every_growth_says_device_and_moves_bytes_not_buffers(grown):
    c, _ = grown
    rec = c.flight_recorder
    growth = rec.records("growth")
    assert len(growth) == len(c.growth_events) >= 7
    assert all(g["path"] == "device" for g in growth)
    # the three scalars the host rewrites (the first event holds the
    # fixture's jump too), and nothing pulled: the table's occupancy
    # histogram (SLOTS + 1 words a split) is read after the event
    assert growth[0]["h2d_bytes"] == 24
    assert {g["h2d_bytes"] for g in growth[1:]} <= {0, 12}
    assert all(g["d2h_bytes"] == 0 for g in growth)
    occupancy = rec.records("occupancy")
    assert occupancy and all(o["at"] == "growth" for o in occupancy)
    assert [o["nbuckets"] for o in occupancy] == sorted(
        o["nbuckets"] for o in occupancy
    )
    assert occupancy[-1]["nbuckets"] * wf.SLOTS == c._cap
    # over the whole run: what crossed besides the packed stats vector of
    # each sync stays under 4 KB (one buffer of this carry is more)
    syncs = len(rec.records("step"))
    stats_bytes = 8 * (ST_DISC + len(c._props))
    crossed = rec.counters()["d2h_bytes"] - syncs * stats_bytes
    assert 0 < crossed < 4096
    assert rec.counters()["h2d_bytes"] < 4096
    # the four phase spans are there to be read, children of ``grow``
    spans = [r["name"] for r in rec.records("span")]
    for name in ("grow", "grow.pull", "grow.rehash", "grow.queue", "grow.push"):
        assert name in spans


def test_a_spill_armed_run_grows_on_the_host(monkeypatch):
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(1 << 30))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    c = TwoPhaseSys(3).checker().spill().telemetry().spawn_tpu(
        sync=True, capacity=1 << 8, batch=32, queue_capacity=64,
        steps_per_call=8,
    )
    c.join()
    growth = c.flight_recorder.records("growth")
    assert growth and all(g["path"] == "host" for g in growth)
    # the whole carry crossed, both ways, and the record says how much
    assert all(g["d2h_bytes"] > 4096 and g["h2d_bytes"] > 4096 for g in growth)
    assert c.unique_state_count() == 288

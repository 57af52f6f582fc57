"""The ABD quorum register on ordered FIFO links, through the COMPILED actor
twin on the device engine, against the plain reference (a BFS over the host
object model: the actors' own ``on_msg``, ``OrderedNetwork``,
``LinearizabilityTester``; ``benchmarks/srbench/reference.py``).

The configurations are the small siblings of the benchmark's ``linreg2x3o``
(``abd_model(2, 3, ordered)``, 270,381 unique: pinned in its configuration
file, checked on the chip) and the whole reference ``bench.sh`` leg
``linearizable-register check 3 ordered``.
"""

import os
import sys

import pytest

from stateright_tpu.actor import Network
from stateright_tpu.models.linearizable_register import abd_model, abd_ordered

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks"))

from srbench.reference import reference_bfs  # noqa: E402


def test_abd_ordered_is_abd_model_on_ordered_links():
    m = abd_ordered(2, 3)
    assert m.init_network.name == Network.new_ordered().name
    assert len(m.actors) == 5
    assert m.tensor_model().ordered


@pytest.mark.parametrize("clients, servers", [(2, 2), (1, 3)])
def test_compiled_twin_on_the_engine_equals_the_plain_reference(clients, servers):
    model = abd_ordered(clients, servers)
    want = reference_bfs(model)
    checker = model.checker().spawn_tpu(sync=True)
    checker.join()  # a poisoned row (a compile-time bound crossed) raises here
    assert checker.unique_state_count() == want["unique"]
    assert checker.state_count() == want["generated"]
    assert checker.max_depth() == want["max_depth"]
    found = checker.discoveries()
    assert sorted(found) == want["discoveries"] == ["value chosen"]
    for name in found:
        path = checker.discovery(name)
        prop = model.property_by_name(name)
        assert prop.condition(model, path.last_state())
        # the device's parent chain, replayed on the host model step by step
        states, actions = path.states(), path.actions()
        assert states[0] in model.init_states()
        for before, action, after in zip(states, actions, states[1:]):
            assert model.next_state(before, action) == after
        assert len(states) - 1 <= want["max_depth"]


def test_bench_sh_leg_check_3_ordered_is_pinned_through_the_engine():
    """``bench.sh:31-34``'s ``linearizable-register check 3 ordered``
    (3 clients, 2 servers), to exhaustion through the compiled twin."""
    checker = abd_model(3, 2, Network.new_ordered()).checker().spawn_tpu(
        sync=True, capacity=1 << 18, queue_capacity=1 << 16, batch=4096,
        steps_per_call=64,
    )
    checker.join()
    assert checker.unique_state_count() == 36_213
    assert checker.state_count() == 63_053
    assert sorted(checker.discoveries()) == ["value chosen"]
    assert len(checker.growth_events) == 0

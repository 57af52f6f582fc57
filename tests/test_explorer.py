"""Explorer endpoint tests (reference ``src/checker/explorer.rs:242-447``):
exact JSON views against a live (background) server over small models."""

import json
import urllib.request

import pytest

from stateright_tpu.explorer import serve
from stateright_tpu.models.two_phase_commit import TwoPhaseSys

from fixtures import LinearEquation


@pytest.fixture(scope="module")
def lineq_server():
    server = serve(
        LinearEquation(a=2, b=10, c=14).checker(),
        "localhost:0",  # ephemeral port
        block=False,
    )
    server.checker.join()
    yield server
    server.shutdown()


def get(server, path):
    with urllib.request.urlopen(f"http://{server.addr}{path}") as r:
        return json.loads(r.read())


def get_status(server, path):
    try:
        with urllib.request.urlopen(f"http://{server.addr}{path}") as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_status_endpoint(lineq_server):
    s = get(lineq_server, "/.status")
    assert s["done"] is True
    assert s["model"] == "LinearEquation"
    assert s["unique_state_count"] == 12  # reference checker.rs:459-461
    assert s["state_count"] == 15
    kinds = {name: kind for kind, name, _ in s["properties"]}
    assert kinds == {"solvable": "sometimes"}
    # the sometimes-property discovery is an encoded fingerprint path
    discovery = dict(
        (name, disc) for _, name, disc in s["properties"]
    )["solvable"]
    assert discovery is not None and "/" in discovery


def test_init_states_view(lineq_server):
    views = get(lineq_server, "/.states/")
    assert len(views) == 1
    assert views[0]["state"] == "(0, 0)"
    assert "action" not in views[0]
    assert int(views[0]["fingerprint"]) > 0


def test_steps_view_follows_fingerprints(lineq_server):
    init = get(lineq_server, "/.states/")[0]
    steps = get(lineq_server, f"/.states/{init['fingerprint']}")
    # format_action is repr(), like the reference's Debug formatting
    assert {v["action"] for v in steps} == {"'IncreaseX'", "'IncreaseY'"}
    for v in steps:
        assert "state" in v and "fingerprint" in v
    # walk one more level
    nxt = steps[0]
    steps2 = get(
        lineq_server, f"/.states/{init['fingerprint']}/{nxt['fingerprint']}"
    )
    assert len(steps2) == 2


def test_unknown_fingerprint_404(lineq_server):
    code, body = get_status(lineq_server, "/.states/12345")
    assert code == 404 and "Unable to find state" in body["error"]


def test_unparseable_fingerprint_404(lineq_server):
    code, body = get_status(lineq_server, "/.states/zzz")
    assert code == 404 and "Unable to parse" in body["error"]


def test_ui_is_served(lineq_server):
    with urllib.request.urlopen(f"http://{lineq_server.addr}/") as r:
        html = r.read().decode()
    assert "State Space Explorer" in html
    with urllib.request.urlopen(f"http://{lineq_server.addr}/app.js") as r:
        assert "pollStatus" in r.read().decode()


def test_discovery_path_resolves_through_states_endpoint():
    server = serve(TwoPhaseSys(3).checker(), "localhost:0", block=False)
    try:
        server.checker.join()
        s = get(server, "/.status")
        disc = dict((n, d) for _, n, d in s["properties"])
        fps = disc["commit agreement"].split("/")
        # every prefix of the discovery path resolves
        for i in range(len(fps)):
            views = get(server, "/.states/" + "/".join(fps[: i + 1]))
            assert isinstance(views, list)
        # the recent-path snapshot was populated by the visitor
        assert s["recent_path"] is None or s["recent_path"].startswith("[")
    finally:
        server.shutdown()


def test_actor_svg_sequence_diagram():
    """An actor-model trace renders as a sequence-diagram SVG, surfaced in
    the ``/.states`` views (reference ``src/actor/model.rs:384-475`` +
    ``explorer.rs:231``)."""
    from stateright_tpu.models.paxos import paxos_model

    model = paxos_model(1)
    # direct: a delivery arrow appears for a short concrete trace
    init = model.init_states()[0]
    action = next(a for a in model.actions(init) if type(a).__name__ == "Deliver")
    nxt = model.next_state(init, action)
    from stateright_tpu.checker.path import Path

    svg = model.as_svg(Path([(init, action), (nxt, None)]))
    assert svg is not None and svg.startswith("<svg")
    assert "svg-actor-timeline" in svg and "svg-event-line" in svg
    assert "marker-end='url(#arrow)'" in svg

    # endpoint: the init view itself has no deliveries yet, but step views do
    server = serve(model.checker().target_states(50), "localhost:0", block=False)
    try:
        server.checker.join()
        inits = get(server, "/.states/")
        steps = get(server, f"/.states/{inits[0]['fingerprint']}")
        svgs = [v["svg"] for v in steps if "svg" in v]
        assert svgs and all(s.startswith("<svg") for s in svgs)
        assert any("svg-event-line" in s for s in svgs)
    finally:
        server.shutdown()


def test_timeout_renders_circle():
    from fixtures_actor import PingPongCfg, ping_pong_model
    from stateright_tpu.actor import Actor, ActorModel, Id
    from stateright_tpu.checker.path import Path
    from stateright_tpu.core import Expectation

    class TimerActor(Actor):
        def on_start(self, id, out):
            out.set_timer()
            return 0

        def on_timeout(self, id, state, out):
            out.send(id, "tick")
            return state + 1

    model = ActorModel().actor(TimerActor()).property(
        Expectation.ALWAYS, "small", lambda m, s: s.actor_states[0] < 3
    )
    init = model.init_states()[0]
    timeout = next(
        a for a in model.actions(init) if type(a).__name__ == "Timeout"
    )
    nxt = model.next_state(init, timeout)
    svg = model.as_svg(Path([(init, timeout), (nxt, None)]))
    assert "<circle" in svg and "Timeout" in svg


def test_status_reports_discoveries_mid_run():
    """Discoveries are visible in ``/.status`` while the check is still
    running (reference ``explorer.rs:133-157`` reads the live map)."""
    import threading
    import time as _time

    from fixtures_actor import PingPongCfg, ping_pong_model

    from stateright_tpu import Expectation

    model = ping_pong_model(PingPongCfg(maintains_history=True, max_nat=150_000))
    # violated a few steps in, while the bounded space is far from exhausted,
    # so the discovery must surface mid-run
    model.property(
        Expectation.ALWAYS,
        "never above 3",
        lambda m, s: max(s.actor_states) <= 3,
    )
    gate = threading.Event()

    # A visitor that blocks after a while keeps the check "running" while we
    # poll the status endpoint.
    seen = [0]

    def slow_visit(m, path):
        seen[0] += 1
        if seen[0] > 200:
            gate.wait(10.0)

    server = serve(
        model.checker().visitor(slow_visit), "localhost:0", block=False
    )
    try:
        deadline = _time.monotonic() + 30.0
        status = get(server, "/.status")
        while _time.monotonic() < deadline:
            status = get(server, "/.status")
            disc = {n: d for _, n, d in status["properties"] if d is not None}
            if disc and not status["done"]:
                break
            _time.sleep(0.1)
        assert not status["done"]
        disc = {n: d for _, n, d in status["properties"] if d is not None}
        # the falsifiable liveness property is discovered long before the
        # huge bounded space is exhausted
        assert disc, "no discovery surfaced while the check was running"
    finally:
        gate.set()
        server.checker._stop.set()
        server.shutdown()


def test_serve_tpu_strategy_endpoints():
    """The Explorer can browse a device wavefront run (beyond the reference,
    whose Explorer wraps only BfsChecker): ``/.status`` serves the engine's
    counters and parent-walk-reconstructed discovery paths, and ``/.states``
    browsing works identically (it re-executes the object form)."""
    server = serve(
        TwoPhaseSys(3).checker(), "localhost:0", block=False, strategy="tpu"
    )
    try:
        server.checker.join()
        s = get(server, "/.status")
        assert s["done"] is True
        assert s["unique_state_count"] == 288  # examples/2pc.rs:128
        disc = {n: d for _, n, d in s["properties"] if d is not None}
        assert set(disc) == {"abort agreement", "commit agreement"}
        # every discovery path resolves through /.states (object-form
        # re-execution matches device fingerprints bit-for-bit)
        for encoded in disc.values():
            code, views = get_status(server, f"/.states/{encoded}")
            assert code == 200
        # init view works too
        views = get(server, "/.states/")
        assert len(views) == 1
    finally:
        server.shutdown()


def test_serve_tpu_live_status_mid_run():
    """``/.status`` surfaces live counters and discovery paths while the
    device run is still in flight: tiny batches plus
    per-step host syncs keep the run pollable."""
    import time as _time

    server = serve(
        TwoPhaseSys(5).checker(),
        "localhost:0",
        block=False,
        strategy="tpu",
        batch=32,
        steps_per_call=1,
    )
    try:
        saw_live = False
        saw_live_disc = False
        deadline = _time.monotonic() + 120.0
        while _time.monotonic() < deadline:
            status = get(server, "/.status")
            if status["done"]:
                break
            if status["unique_state_count"] > 0:
                saw_live = True
            disc = {n for _, n, d in status["properties"] if d is not None}
            if disc:
                saw_live_disc = True
                break
            _time.sleep(0.02)
        assert saw_live, "no live counter surfaced before completion"
        assert saw_live_disc, "no discovery path surfaced mid-run"
        server.checker.join()
        status = get(server, "/.status")
        assert status["done"] is True
        assert status["unique_state_count"] == 8832  # examples/2pc.rs:133
        disc = {n: d for _, n, d in status["properties"] if d is not None}
        assert set(disc) == {"abort agreement", "commit agreement"}
    finally:
        server.checker._stop.set()
        server.shutdown()


def test_explorer_serves_general_fragment_tpu_run():
    """The Explorer browses a device run of the compiled general fragment
    (raft): live status, discovery path links, and state pages with the
    per-step outcomes."""
    from stateright_tpu.models.raft import raft_model

    server = serve(
        raft_model(3).checker(),
        "localhost:0",
        strategy="tpu",
        block=False,
        sync=True,
        capacity=1 << 14,
    )
    try:
        server.checker.join()
        s = get(server, "/.status")
        assert s["done"] is True
        assert s["unique_state_count"] == 5_725
        props = {name: disc for _, name, disc in s["properties"]}
        assert props["a leader is elected"] is not None
        # follow the witness path to its final state page
        code, view = get_status(
            server, "/.states/" + props["a leader is elected"]
        )
        assert code == 200
        assert isinstance(view, list)
    finally:
        server.shutdown()

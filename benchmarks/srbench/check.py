"""The unit of work: one whole check, as a user makes it.

``model.checker().spawn_tpu(sync=True, **spawn)`` -> ``join()`` -> the
counts -> ``discoveries()`` and ``discovery(name)`` for each discovered
property (the device's parent chain replayed on the host object model).
The benchmark times it on the host clock and holds the answers to the
configuration's pins; nothing here reads the program's internals.

The workload file's ``loop.kind`` says on what a check is made: ``closed``
re-checks ONE model object (its engines stay resident), ``cold`` builds
the model object anew INSIDE every check's timed span.  When a window of
either kind stops starting checks is ``window_closes``.
"""

from __future__ import annotations

import importlib
import time
from typing import Optional

RECORDER_CAPACITY = 8192  # ring size in traced runs: every record is kept
LOOP_KINDS = ("closed", "cold")


def loop_kind(workload: dict) -> str:
    """The workload's ``loop.kind``; an absent ``loop`` or ``kind`` is
    ``closed``.  ValueError, naming the kinds, for one the loop has not."""
    kind = (workload.get("loop") or {}).get("kind", "closed")
    if kind not in LOOP_KINDS:
        raise ValueError(
            f"unknown loop.kind {kind!r}: the kinds are {', '.join(LOOP_KINDS)}"
        )
    return kind


def window_closes(elapsed: float, durations: list, seconds: float) -> Optional[str]:
    """Whether the window starts no further check, and by which rule.

    ``elapsed`` is the time since the window opened, ``durations`` the
    ``check_s`` of the checks it holds, ``seconds`` its asked length.
    ``"seconds"``: they have passed.  ``"overrun"``: the window holds a
    check, and another as short as its shortest would end past one and a
    half windows — a check over three quarters of ``seconds`` gets exactly
    one a window, not two on noise.  ``None``: start the next check."""
    if elapsed >= seconds:
        return "seconds"
    if durations and elapsed + min(durations) > 1.5 * seconds:
        return "overrun"
    return None


def build_model(config: dict):
    """The configuration's model, from its factory's dotted path."""
    spec = config["model"]
    module, _, attr = spec["factory"].partition(":")
    factory = getattr(importlib.import_module(module), attr)
    return factory(*spec.get("args", []), **spec.get("kwargs", {}))


def builder_for(model, workload: dict, telemetry: bool):
    b = model.checker()
    for verb in workload.get("builder", []):
        b = getattr(b, verb["verb"])(
            *verb.get("args", []), **verb.get("kwargs", {})
        )
    if telemetry:
        # the flight recorder ONLY: the cartography / memory / roofline
        # flags change the step program or its acquisition path
        b = b.telemetry(capacity=RECORDER_CAPACITY)
    return b


def run_check(make_model, workload: dict, telemetry: bool) -> dict:
    """One timed check, on the model object ``make_model()`` returns INSIDE
    the timed span: the ``closed`` loop hands back its one object, the
    ``cold`` loop builds one from the configuration, so twin, engines and
    checker are all this check's.  Returns the answers, the host-clock
    spans and — in a traced run — the flight recorder's records."""
    spans = []
    t0 = time.monotonic()
    model = make_model()
    t_spawn = time.monotonic()
    spans.append(("build_model", t0, t_spawn))
    checker = builder_for(model, workload, telemetry).spawn_tpu(
        sync=True, **workload.get("spawn", {})
    )
    checker.join()
    unique = checker.unique_state_count()
    generated = checker.state_count()
    depth = checker.max_depth()
    t_join = time.monotonic()
    spans.append(("spawn_join", t_spawn, t_join))
    found = checker.discoveries()
    paths = {name: checker.discovery(name) for name in found}
    t1 = time.monotonic()
    spans.append(("reconstruct", t_join, t1))
    out = {
        "check_s": t1 - t0,
        "t0": t0,
        "t1": t1,
        "unique": unique,
        "generated": generated,
        "max_depth": depth,
        "discoveries": sorted(found),
        "paths": paths,
        "growth_events": len(getattr(checker, "growth_events", ())),
        "spans": spans,
        "checker": checker,
        "model": model,
    }
    rec = getattr(checker, "flight_recorder", None)
    if telemetry and rec is not None:
        out["records"] = rec.records()
        out["stages"] = rec.stages() or {}
        out["recorder_t0"] = rec.t0_monotonic
        out["recorder_dropped"] = rec.dropped
    return out


def compare(model, config: dict, workload: dict, result: dict) -> list:
    """Every number this check is held to, as ``(name, number, limit,
    messages)``: each pinned count's distance from its pin, the discovery
    set's, the discovery paths whose last state its property does not
    single out on the host model, and the cell's growth expectation.  All
    are exact: the limit is 0, and ``messages`` say why a number is over."""
    pins = config["pins"]
    rows = []
    for key in ("unique", "generated", "max_depth"):
        rows.append((
            f"{key}_off", abs(result[key] - pins[key]), 0,
            [f"{key} {result[key]} != pinned {pins[key]}"],
        ))
    wanted = sorted(pins["discoveries"])
    rows.append((
        "discoveries_off", len(set(result["discoveries"]) ^ set(wanted)), 0,
        [f"discoveries {result['discoveries']} != pinned {wanted}"],
    ))
    bad_paths = []
    for name, path in result["paths"].items():
        if path is None:
            bad_paths.append(f"discovery {name!r} has no path")
            continue
        prop = model.property_by_name(name)
        holds = bool(prop.condition(model, path.last_state()))
        if holds != (prop.expectation.name == "SOMETIMES"):
            bad_paths.append(
                f"the replayed path of {name!r} ends in a state its "
                "property does not single out"
            )
    rows.append(("paths_off", len(bad_paths), 0, bad_paths))
    growth = workload.get("expect_growth")
    events = result["growth_events"]
    if growth == "none":
        rows.append(("growth_off", events, 0,
                     [f"{events} growth events in a presized cell"]))
    elif growth == "some":
        rows.append(("growth_off", int(events == 0), 0,
                     ["no growth event in a cell that starts from the defaults"]))
    return rows


def visited_fingerprints(checker) -> Optional["np.ndarray"]:  # noqa: F821
    """The sorted fingerprints of the checker's visited set, through the
    public ``checkpoint()`` snapshot; None where the surface has no table."""
    import numpy as np

    snap = checker.checkpoint()
    table = snap.get("table_fp")
    if table is None:
        return None
    table = np.asarray(table).reshape(-1)
    empty = np.uint64(0xFFFFFFFFFFFFFFFF)
    return np.sort(table[table != empty])


def missing_from(visited, fingerprints: list) -> int:
    """How many of ``fingerprints`` the sorted ``visited`` array lacks."""
    import numpy as np

    want = np.asarray(fingerprints, dtype=np.uint64)
    at = np.searchsorted(visited, want)
    at[at >= len(visited)] = 0
    return int((visited[at] != want).sum())

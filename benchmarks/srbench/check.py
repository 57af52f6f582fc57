"""The unit of work: one whole check, as a user makes it.

``model.checker().spawn_tpu(sync=True, **spawn)`` -> ``join()`` -> the
counts -> ``discoveries()`` and ``discovery(name)`` for each discovered
property (the device's parent chain replayed on the host object model).
The benchmark times it on the host clock and holds the answers to the
configuration's pins; nothing here reads the program's internals.
"""

from __future__ import annotations

import importlib
import time
from typing import Optional

RECORDER_CAPACITY = 8192  # ring size in traced runs: every record is kept


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


def run_check(model, workload: dict, telemetry: bool) -> dict:
    """One timed check.  Returns the answers, the host-clock spans and —
    in a traced run — the flight recorder's records."""
    spans = []
    t0 = time.monotonic()
    checker = builder_for(model, workload, telemetry).spawn_tpu(
        sync=True, **workload.get("spawn", {})
    )
    checker.join()
    unique = checker.unique_state_count()
    generated = checker.state_count()
    depth = checker.max_depth()
    t_join = time.monotonic()
    spans.append(("spawn_join", t0, t_join))
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
    }
    rec = getattr(checker, "flight_recorder", None)
    if telemetry and rec is not None:
        out["records"] = rec.records()
        out["stages"] = rec.stages() or {}
        out["recorder_t0"] = rec.t0_monotonic
        out["recorder_dropped"] = rec.dropped
    return out


def pin_failures(model, config: dict, workload: dict, result: dict) -> list:
    """Why this check is NOT correct (empty when it is): every pinned
    count, the discovery set, each discovery path's last state judged by
    its property on the host model, and the cell's growth expectation."""
    pins = config["pins"]
    bad = []
    for key in ("unique", "generated", "max_depth"):
        if result[key] != pins[key]:
            bad.append(f"{key} {result[key]} != pinned {pins[key]}")
    if result["discoveries"] != sorted(pins["discoveries"]):
        bad.append(
            f"discoveries {result['discoveries']} != pinned "
            f"{sorted(pins['discoveries'])}"
        )
    for name, path in result["paths"].items():
        if path is None:
            bad.append(f"discovery {name!r} has no path")
            continue
        prop = model.property_by_name(name)
        holds = bool(prop.condition(model, path.last_state()))
        wanted = prop.expectation.name == "SOMETIMES"
        if holds != wanted:
            bad.append(
                f"the replayed path of {name!r} ends in a state its "
                "property does not single out"
            )
    growth = workload.get("expect_growth")
    if growth == "none" and result["growth_events"] != 0:
        bad.append(f"{result['growth_events']} growth events in a presized cell")
    if growth == "some" and result["growth_events"] == 0:
        bad.append("no growth event in a cell that starts from the defaults")
    return bad


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

"""The unit of work: one whole check, as a user makes it.

``model.checker().spawn_tpu(sync=True, **spawn)`` -> ``join()`` -> the
counts -> ``discoveries()`` and ``discovery(name)`` for each discovered
property (the device's parent chain replayed on the host object model).
The benchmark times it on the host clock and holds the answers to the
configuration's pins; nothing here reads the program's internals.

The workload file's ``loop.kind`` says on what a check is made: ``closed``
re-checks ONE model object (its engines stay resident), ``cold`` builds
the model object anew INSIDE every check's timed span, ``bounded`` is
``closed`` with a check that the ``target_states`` verb of the workload's
``builder`` stops: a search that cannot exhaust in a window, held to what a
PREFIX of a breadth-first search owes (``compare_bounded``,
``bounded_prefix``) where the others are held to the whole space's pins.
When a window of any kind stops starting checks is ``window_closes``.
"""

from __future__ import annotations

import importlib
import time
from typing import Optional

RECORDER_CAPACITY = 8192  # ring size in traced runs: every record is kept
LOOP_KINDS = ("closed", "cold", "bounded")
EMPTY_FP = 0xFFFFFFFFFFFFFFFF  # a free slot of the visited table
DRAWS = 1024  # occupied slots of the seeded soundness draw


def loop_kind(workload: dict) -> str:
    """The workload's ``loop.kind``; an absent ``loop`` or ``kind`` is
    ``closed``.  ValueError, naming the kinds, for one the loop has not -
    and, naming the rule, for a ``bounded`` workload that nothing bounds
    (``bounded_target``)."""
    kind = (workload.get("loop") or {}).get("kind", "closed")
    if kind not in LOOP_KINDS:
        raise ValueError(
            f"unknown loop.kind {kind!r}: the kinds are {', '.join(LOOP_KINDS)}"
        )
    if kind == "bounded":
        bounded_target(workload)
    return kind


def bounded_target(workload: dict) -> int:
    """The N of a ``bounded`` workload: the argument of the
    ``target_states`` verb in its ``builder``.  ValueError where the rule of
    the kind is broken: the verb is there, and so is ``spawn.batch`` (one
    step's reach past N is ``batch x max_actions``); ``symmetry`` is not
    (a prefix under it has no sample the host can name)."""
    verbs = {v["verb"]: v for v in workload.get("builder", [])}
    if "target_states" not in verbs or not verbs["target_states"].get("args"):
        raise ValueError(
            "loop.kind 'bounded' needs {\"verb\": \"target_states\", "
            "\"args\": [N]} in the workload's builder: a bounded check is "
            "stopped by the program's own verb, never by the harness"
        )
    if "symmetry" in verbs:
        raise ValueError("loop.kind 'bounded' takes no 'symmetry' verb")
    if "batch" not in workload.get("spawn", {}):
        raise ValueError(
            "loop.kind 'bounded' needs spawn.batch in the workload file: "
            "target_over allows one step of batch x max_actions past N"
        )
    return int(verbs["target_states"]["args"][0])


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
    rows.append(_paths_row(model, result))
    rows.extend(_growth_rows(workload, result))
    return rows


def _paths_row(model, result: dict) -> tuple:
    """``paths_off``: the discovery paths whose replay on the host model
    does not end in a state the property singles out."""
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
    return ("paths_off", len(bad_paths), 0, bad_paths)


def _growth_rows(workload: dict, result: dict) -> list:
    """``growth_off``: the cell's growth expectation, where it has one."""
    growth = workload.get("expect_growth")
    events = result["growth_events"]
    if growth == "none":
        return [("growth_off", events, 0,
                 [f"{events} growth events in a presized cell"])]
    if growth == "some":
        return [("growth_off", int(events == 0), 0,
                 ["no growth event in a cell that starts from the defaults"])]
    return []


def compare_bounded(model, config: dict, workload: dict, result: dict,
                    first: Optional[dict]) -> list:
    """What ONE check of a ``bounded`` cell is held to, as ``compare``'s
    rows, every limit 0.  No pin of the whole space exists for a search
    that stops early; a check owes: the bound was reached and the space did
    not run out under it (``target_short``); it stopped within one step of
    it (``target_over``: ``batch x max_actions`` is all one step can add);
    the discoveries the configuration pins for the prefix
    (``discoveries_missing``; that the prefix at the cell's N owes each -
    the check POPPED the pinned witness state, ``witness_popped`` - is
    ``levels_beyond_complete``'s to hold, once a run; one the device reports
    beyond them is held by ``paths_off`` alone: its path replays and its
    last state decides the property); no growth; and the SAME stop as the run's ``first`` check
    (``repeat_off``; None for that check itself) - the same program on the
    same capacities stops at the same step, which is what makes two checks
    the same work."""
    target = bounded_target(workload)
    step = int(workload["spawn"]["batch"]) * int(config["row"]["max_actions"])
    unique, generated = result["unique"], result["generated"]
    wanted = sorted(config["pins"]["bounded"]["discoveries_by_level"])
    lacking = sorted(set(wanted) - set(result["discoveries"]))
    rows = [
        ("target_short", max(0, target - unique), 0,
         [f"the search ended at {unique} unique states, under its bound "
          f"{target}"]),
        ("target_over", max(0, unique - target - step), 0,
         [f"{unique} unique states is more than one step ({step}) past "
          f"the bound {target}"]),
        ("discoveries_missing", len(lacking), 0,
         [f"the prefix pins {wanted}; not reported: {lacking}"]),
        _paths_row(model, result),
    ]
    rows.extend(_growth_rows(workload, result))
    if first is not None:
        rows.append((
            "repeat_off",
            abs(unique - first["unique"]) + abs(generated - first["generated"]),
            0,
            [f"stopped at {unique} unique / {generated} generated, the "
             f"run's first check at {first['unique']} / {first['generated']}"],
        ))
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
    return np.sort(table[table != np.uint64(EMPTY_FP)])


def missing_from(visited, fingerprints: list) -> int:
    """How many of ``fingerprints`` the sorted ``visited`` array lacks."""
    import numpy as np

    want = np.asarray(fingerprints, dtype=np.uint64)
    at = np.searchsorted(visited, want)
    at[at >= len(visited)] = 0
    return int((visited[at] != want).sum())


# -- the prefix of a bounded check, from the public checkpoint() snapshot ----

def complete_level(q_depth, head: int, tail: int, graded: bool = False) -> int:
    """The deepest level C down to which a stopped FIFO search holds EVERY
    state, from the queue's depth labels alone (``q_depth[:tail]``, the rows
    before ``head`` popped and expanded, the rest waiting):

        C = min(W, V + 1, the deepest label)

    with W the shallowest label still waiting and V the smallest label that
    sits BEHIND a larger one in the queue (no such pair: no V).  The
    argument is written out once in ``benchmarks/README.md`` ("The complete
    level of a prefix"); in short, a label is a path's length, so it is at
    least the state's level, and it IS the level as long as no deeper row
    was popped ahead of a shallower one that could have reached the same
    state first (levels <= V + 1); every state of level d is in the table
    once every row of level d - 1 was popped (d <= W).  It holds for any
    model, whatever order one step appends its rows in.

    ``graded``: the configuration states that every transition of its model
    leads to the state itself or exactly one level deeper
    (``pins.bounded.graded``).  Every path to a state then has one length,
    a label is ALWAYS the level, V costs nothing and C = min(W, the deepest
    label).  A configuration that says so wrongly cannot hide a fault: rows
    by label then differ from the pinned level sizes, and a level it calls
    complete lacks states (both compared, both read not correct)."""
    import numpy as np

    if tail <= 0:
        return -1
    labels = np.asarray(q_depth)[:tail].astype(np.int64)
    level = int(labels.max())
    if head < tail:
        level = min(level, int(labels[head:].min()))
    behind = labels < np.maximum.accumulate(labels)
    if behind.any() and not graded:
        level = min(level, int(labels[behind].min()) + 1)
    return level


def bounded_prefix(checker, graded: bool = False) -> dict:
    """What a stopped check visited, through the public ``checkpoint()``
    snapshot: ``visited`` (the sorted fingerprints of the occupied table
    slots), ``parents`` (each one's recorded parent, aligned; 0 marks an
    init state), ``popped`` (the sorted fingerprints of the queue rows before
    ``head``: the states whose successors the search generated),
    ``complete_level`` (``graded``: as the configuration states its model)
    and ``labels`` (the count of queue rows by depth label).  Where the
    queue no longer holds every visited state (the host grew it mid-check
    and dropped the popped rows) the complete level is -1 and ``popped`` is
    empty: nothing is owed, and every pin is beyond it.  The snapshot itself - the whole table - is let go before this
    returns."""
    import numpy as np

    snap = checker.checkpoint()
    fp = np.asarray(snap["table_fp"]).reshape(-1)
    occupied = fp != np.uint64(EMPTY_FP)
    fp = fp[occupied]
    parents = np.asarray(snap["table_parent"]).reshape(-1)[occupied]
    order = np.argsort(fp, kind="stable")
    head, tail = int(snap["head"]), int(snap["tail"])
    labels = np.asarray(snap["q_depth"])[:tail]
    # a queue the host grew mid-check was compacted (popped rows dropped):
    # it no longer holds every row, and nothing can be said from it
    whole = tail == len(fp)
    return {
        "visited": fp[order],
        "parents": parents[order],
        "popped": np.sort(
            np.asarray(snap["q_fp"]).reshape(-1)[:head if whole else 0]),
        "head": head,
        "tail": tail,
        "complete_level": complete_level(labels, head, tail, graded) if whole else -1,
        # how many queue rows carry each depth label: down to the complete
        # level a label IS the level, so these are the levels' sizes
        "labels": np.bincount(labels).tolist(),
    }


def _holds(sorted_fps, fp: int) -> bool:
    return len(sorted_fps) > 0 and missing_from(sorted_fps, [fp]) == 0


def witness_popped(model, prefix: dict, name: str, level: int, actions) -> bool:
    """Whether the prefix OWES the pinned discovery ``name``: the
    configuration's witness - ``level`` actions of the HOST model from an
    init state, each named by its ``repr`` (``pins.bounded.witnesses``) -
    replays, ends in a state that decides the property, and the check
    POPPED that state (a step evaluates the properties on the rows it pops).
    A level alone would not do: one step appends its rows in table order, so
    a few rows of a level are popped long after the next level's first, and
    "every row of level L popped" comes far later than the one that
    matters."""
    prop = model.property_by_name(name)
    if actions is None or len(actions) != level:
        return False
    for state in model.init_states():
        for want in actions:
            state = next((nxt for a in model.actions(state) if repr(a) == want
                          for nxt in [model.next_state(state, a)]
                          if nxt is not None), None)
            if state is None:
                break
        else:
            decides = bool(prop.condition(model, state))
            if decides == (prop.expectation.name == "SOMETIMES"):
                return _holds(prefix["popped"], model.fingerprint_state(state))
    return False


def walks_missing(prefix: dict, walks: list, closure: bool) -> tuple:
    """``(owed, missing, deepest)`` over seeded random walks of the host
    model (``reference.random_walks``): a walk's state at step i is OWED by
    the prefix while i <= its complete level (it is reachable in i
    transitions) or - ``closure`` - while the state before it on the walk
    was popped: a popped row's every successor was inserted in that step or
    before.  ``closure`` is off where the search ended on its discoveries
    (rows popped after the last one are not expanded).  A walk is followed
    to its first state that is not owed."""
    owed = missing = deepest = 0
    level = prefix["complete_level"]
    for walk in walks:
        for i, fp in enumerate(walk):
            if i > level and not (
                closure and i > 0 and _holds(prefix["popped"], walk[i - 1])
            ):
                break
            owed += 1
            deepest = max(deepest, i)
            missing += not _holds(prefix["visited"], fp)
    return owed, missing, deepest


def _parent_chain(prefix: dict, at: int) -> Optional[list]:
    """The fingerprints from an init state down to the slot ``at`` of
    ``prefix["visited"]``, by the table's recorded parents (0 marks an init
    state); None where a parent is not in the table or the chain is longer
    than the queue (a cycle)."""
    visited, parents = prefix["visited"], prefix["parents"]
    chain = [int(visited[at])]
    while int(parents[at]) != 0:
        parent = int(parents[at])
        if len(chain) > prefix["tail"] or not _holds(visited, parent):
            return None
        at = int(visited.searchsorted(parents[at]))
        chain.append(parent)
    chain.reverse()
    return chain


def unreachable(model, prefix: dict, seed: int, draws: int = DRAWS) -> list:
    """Soundness of the prefix: a seeded draw of ``draws`` occupied slots,
    each followed through the table's recorded parents to an init state and
    replayed on the HOST object model (``Path.from_fingerprints``).  Returns
    one message for every slot whose chain leaves the table, does not end in
    an init state, or takes a step the host model does not have."""
    import random

    from stateright_tpu.checker.path import Path

    slots = len(prefix["visited"])
    bad = []
    for at in random.Random(seed).sample(range(slots), min(draws, slots)):
        chain = _parent_chain(prefix, at)
        if chain is None:
            bad.append(f"the parent chain of {int(prefix['visited'][at]):#x} "
                       "leaves the table or never ends")
            continue
        try:
            Path.from_fingerprints(model, chain)
        except Exception as e:  # noqa: BLE001 - any refusal is the finding
            bad.append(f"the chain to {chain[-1]:#x} ({len(chain)} states) "
                       f"does not replay on the host model: {e}")
    return bad

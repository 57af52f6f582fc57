"""The plain reference: a level-by-level BFS over the HOST object model.

Independent of everything the benchmark measures: no tensor twin, no
fingerprints, no device, no worker pool — ``init_states`` / ``actions`` /
``next_state`` / ``within_boundary`` and a Python ``set`` of the states
themselves.  It defines what the pins mean (the reference semantics,
``src/checker/bfs.rs``):

 - ``generated`` counts the init states plus every within-boundary
   successor, duplicates included
 - ``unique`` is the size of the visited set
 - ``max_depth`` is the deepest BFS level, the init level being 0 (the
   number of transitions on the longest shortest path)
 - a ``sometimes`` property is discovered by the first state that
   satisfies it, an ``always`` property by the first that violates it;
   the search stops once every property has a discovery

Under ``.symmetry()`` (``symmetric=True``) the same search runs over the
ORIGINAL states in FIFO order and deduplicates on each state's
``representative()``: the visited set holds one representative a class.
Where the representative is not class-invariant that count depends on the
visit order, so a symmetric configuration's pins are THIS search's, the
order a BFS engine visits in — not the depth-first count upstream prints
(2pc with 5 resource managers: 508 here, 665 there).

It also draws the seeded random walks of the exactness sample.
"""

from __future__ import annotations

import random
from typing import Optional


def _discovers(prop, model, state) -> bool:
    kind = prop.expectation.name
    if kind == "SOMETIMES":
        return bool(prop.condition(model, state))
    if kind == "ALWAYS":
        return not prop.condition(model, state)
    raise NotImplementedError(
        f"the plain reference does not model {kind} properties "
        f"({prop.name!r})"
    )


def successors(model, state) -> list:
    out = []
    for action in model.actions(state):
        nxt = model.next_state(state, action)
        if nxt is not None and model.within_boundary(nxt):
            out.append(nxt)
    return out


def reference_bfs(model, symmetric: bool = False,
                  kept: Optional[list] = None,
                  max_level: Optional[int] = None,
                  levels: Optional[list] = None) -> dict:
    """Exhaust ``model`` on the host; returns the four pinned quantities.
    ``symmetric``: deduplicate on ``state.representative()`` (the states
    themselves are searched on, in FIFO order).  Every state that enters
    the visited set is appended to ``kept``, in that order.

    ``max_level=K`` searches the first K + 1 levels only (the init level is
    0): level K's states are visited and their properties evaluated, none of
    them is expanded, so the four quantities are the PREFIX's (``generated``
    counts the successors of levels below K).  ``levels`` receives one
    ``(size, [properties first discovered at this level])`` a level."""
    key = (lambda s: s.representative()) if symmetric else (lambda s: s)
    props = list(model.properties())
    found: set = set()
    seen: set = set()
    frontier: list = []
    generated = 0
    for s in model.init_states():
        if not model.within_boundary(s):
            continue
        generated += 1
        k = key(s)
        if k not in seen:
            seen.add(k)
            frontier.append(s)
            if kept is not None:
                kept.append(s)
    depth = -1
    done = False
    while frontier and not done:
        depth += 1
        last = max_level is not None and depth >= max_level
        here: list = []  # properties first discovered at this level
        if levels is not None:
            levels.append((len(frontier), here))
        nxt_frontier: list = []
        for s in frontier:
            for p in props:
                if p.name not in found and _discovers(p, model, s):
                    found.add(p.name)
                    here.append(p.name)
            if props and len(found) == len(props):
                done = True
                break
            if last:
                continue
            for n in successors(model, s):
                generated += 1
                k = key(n)
                if k not in seen:
                    seen.add(k)
                    nxt_frontier.append(n)
                    if kept is not None:
                        kept.append(n)
        frontier = nxt_frontier
    return {
        "unique": len(seen),
        "generated": generated,
        "max_depth": max(depth, 0),
        "discoveries": sorted(found),
    }


def kept_fingerprints(model, seed: int, count: int) -> list:
    """The exactness sample under ``.symmetry()``: ``count`` states drawn
    with ``seed`` from ALL the states the FIFO representative search keeps
    (every depth alike; all of them where it keeps fewer), each named as a
    checker's visited set names it there — the fingerprint of its class's
    representative, worked out on the HOST objects alone
    (``model.fingerprint_state(state.representative())``; nothing of the
    twin's own canonicaliser is asked).  Random walks do NOT serve there: a
    walk leaves the kept members after one step, and where the
    representative is not class-invariant (2pc's sorts by one field) the
    representative of a reachable state that no kept member generated is in
    nobody's set."""
    kept: list = []
    reference_bfs(model, symmetric=True, kept=kept)
    if len(kept) > count:
        kept = random.Random(seed).sample(kept, count)
    return [model.fingerprint_state(s.representative()) for s in kept]


def random_walks(model, seed: int, walks: int, max_steps: int = 64) -> list:
    """``walks`` seeded random walks of the host object model, each the list
    of its states' fingerprints in walk order (from a random init state, one
    random enabled action at a time, until a terminal state or
    ``max_steps``): the state at index i is reachable in i transitions."""
    rng = random.Random(seed)
    inits = [s for s in model.init_states() if model.within_boundary(s)]
    out = []
    for _ in range(walks):
        s = rng.choice(inits)
        fps = [model.fingerprint_state(s)]
        for _ in range(max_steps):
            actions = list(model.actions(s))
            rng.shuffle(actions)
            for action in actions:
                nxt = model.next_state(s, action)
                if nxt is not None and model.within_boundary(nxt):
                    break
            else:
                break  # terminal: no enabled action leaves the state
            s = nxt
            fps.append(model.fingerprint_state(s))
        out.append(fps)
    return out


def random_walk_fingerprints(model, seed: int, walks: int,
                             max_steps: int = 64) -> list:
    """Fingerprints of every state on ``walks`` seeded random walks
    (``random_walks``), one flat list.  Every one of them is reachable, so a
    checker that exhausted the space must hold all of them in its visited
    set."""
    return [fp for walk in random_walks(model, seed, walks, max_steps)
            for fp in walk]

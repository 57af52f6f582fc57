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

It also draws the seeded random walks of the exactness sample.
"""

from __future__ import annotations

import random


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


def reference_bfs(model) -> dict:
    """Exhaust ``model`` on the host; returns the four pinned quantities."""
    props = list(model.properties())
    found: set = set()
    seen: set = set()
    frontier: list = []
    generated = 0
    for s in model.init_states():
        if not model.within_boundary(s):
            continue
        generated += 1
        if s not in seen:
            seen.add(s)
            frontier.append(s)
    depth = -1
    done = False
    while frontier and not done:
        depth += 1
        nxt_frontier: list = []
        for s in frontier:
            for p in props:
                if p.name not in found and _discovers(p, model, s):
                    found.add(p.name)
            if props and len(found) == len(props):
                done = True
                break
            for n in successors(model, s):
                generated += 1
                if n not in seen:
                    seen.add(n)
                    nxt_frontier.append(n)
        frontier = nxt_frontier
    return {
        "unique": len(seen),
        "generated": generated,
        "max_depth": max(depth, 0),
        "discoveries": sorted(found),
    }


def random_walk_fingerprints(model, seed: int, walks: int,
                             max_steps: int = 64) -> list:
    """Fingerprints of every state on ``walks`` seeded random walks of the
    host object model (each from a random init state, one random enabled
    action at a time, until a terminal state or ``max_steps``).  Every one
    of them is reachable, so a checker that exhausted the space must hold
    all of them in its visited set."""
    rng = random.Random(seed)
    inits = [s for s in model.init_states() if model.within_boundary(s)]
    fps = []
    for _ in range(walks):
        s = rng.choice(inits)
        fps.append(model.fingerprint_state(s))
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
    return fps

"""Search-cartography reductions: cheap on-device counters for *how the
search is going* (docs/telemetry.md "Search cartography").

The flight recorder (telemetry/) answers *where time goes*; nothing
answered which actions dominate the frontier, how deep the wave is,
whether properties are being exercised.
These helpers fold those answers into the engines' step programs as small
integer reductions over masks the step already computes (the enabled-action
mask, the live mask, the property masks, the insert selection) — the
PAPERS.md coverage-guided-checking move applied to the wavefront.

Contract, mirroring telemetry/checked/prededup: with cartography OFF the
step jaxpr is bit-identical to an engine built before the feature existed
(pinned by test); ON, each step pays a couple of small column-sums whose
outputs ride the existing packed stats vector — no extra host round-trip.
The depth histogram costs NOTHING per step on the wavefront engine: it is
derived at sync time from the queue's depth buffer, which is a sorted
record of every insert (:func:`queue_depth_hist`).

Reconciliation invariants (pinned by ``tests/test_cartography.py``):

 - ``sum(depth_hist) == unique`` — every fresh insert is counted exactly
   once, at the depth it was inserted (init states at depth 0);
 - ``sum(action_hist) == states - n_init`` — every generated successor is
   counted under its action slot (``states`` counts init states too);
 - with no early exit, ``prop_evaluated[i] == unique`` for every property
   (each unique row is popped and evaluated exactly once).

Growth replays never double-count: accumulation is either inherently
replay-proof (the depth histogram reads the queue, and an overflowed
batch appended nothing) or explicitly guarded/rolled back alongside the
engine's other counters.
"""

from __future__ import annotations

import numpy as np

# Per-depth frontier bins.  BFS depths beyond the last bin clamp into it
# (the bin is then a ">= DEPTH_BINS-1" tail); 128 covers every bundled
# model's diameter with wide margin while keeping the per-step reduction
# and the stats-vector ride-along small.
DEPTH_BINS = 128

# Cartography snapshot schema version (the JSONL/report "v" field).
CARTOGRAPHY_V = 1


def cart_carry_shapes(arity: int, n_props: int) -> tuple:
    """The carry-tail shapes, in carry order: per-action successor
    counts, per-property evaluation / condition-hit tallies.  Property
    arrays keep at least one lane so the carry stays non-empty (same
    convention as the engines' ``disc`` vector).  No depth histogram:
    the engine derives depths from its queue at sync time
    (:func:`queue_depth_hist`) instead of paying a per-step counter."""
    p = max(n_props, 1)
    return ((max(arity, 1),), (p,), (p,))


def queue_depth_hist(qdepth, tail):
    """Per-depth fresh-insert histogram for the wavefront engine, derived
    from the queue: ``qdepth[:tail]`` holds the BFS depth of EVERY unique
    state ever inserted (the queue never evicts — pops only advance
    ``head``), in non-decreasing order (FIFO parents ⇒ monotone child
    depths).  So the histogram is ``DEPTH_BINS`` bounded binary searches
    over a sorted prefix — a few hundred gathers ONCE PER HOST SYNC,
    versus the per-step lane-wide scatter-add this replaces (XLA lowers
    scatter serially on CPU: measured ~1.6ms/step at a 16k candidate
    budget, the whole ≤5% overhead pin by itself).  Depths past the last
    bin clamp into it; garbage lanes past ``tail`` are never read
    (``hi`` starts at ``tail``)."""
    import jax.numpy as jnp

    n = qdepth.shape[0]
    vals = jnp.arange(1, DEPTH_BINS + 1, dtype=qdepth.dtype)
    lo = jnp.zeros((DEPTH_BINS,), jnp.int32)
    hi = jnp.full((DEPTH_BINS,), tail, jnp.int32)
    for _ in range(max(int(n).bit_length(), 1)):
        mid = (lo + hi) >> 1
        go = (mid < hi) & (qdepth[mid] < vals)
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    # lo[i] = #lanes with depth < i+1; diff -> per-bin counts, with the
    # ≥DEPTH_BINS tail folded into the last bin
    prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), lo[:-1]])
    hist = (lo - prev).astype(jnp.int64)
    return hist.at[-1].add((tail - lo[-1]).astype(jnp.int64))


def queue_depth_hist_np(qdepth, tail: int) -> np.ndarray:
    """Host mirror of :func:`queue_depth_hist` (same clamp-into-last-bin
    semantics) for syncs served from a host-side carry."""
    dep = np.minimum(
        np.asarray(qdepth[: int(tail)], dtype=np.int64), DEPTH_BINS - 1
    )
    return np.bincount(dep, minlength=DEPTH_BINS).astype(np.int64)


def prefix_depth_hist(qdepth, n):
    """:func:`queue_depth_hist_np` of ``qdepth[:n]`` where the queue lies
    (the same clamp-into-last-bin semantics, ``DEPTH_BINS`` words back):
    what a growth on the device banks of the prefix it reclaims, and what
    the sync after it reads of the queue it left, so that no depth lane
    crosses to the host.  A compare and a sum a bin - no scatter, no
    search, and no assumption about the lanes' order."""
    import jax.numpy as jnp

    dep = jnp.minimum(qdepth, DEPTH_BINS - 1).astype(jnp.int32)
    live = jnp.arange(qdepth.shape[0], dtype=jnp.int32) < n
    bins = jnp.arange(DEPTH_BINS, dtype=jnp.int32)
    return jnp.sum(
        (dep[None, :] == bins[:, None]) & live[None, :], axis=1,
        dtype=jnp.int64,
    )


def action_hist_delta(valid):
    """Per-action-slot generated-successor counts for one batch: a column
    sum of the enabled-action mask the step already computed."""
    import jax.numpy as jnp

    return jnp.sum(valid, axis=0, dtype=jnp.int64)


def prop_tally_delta(live, masks, n_props: int):
    """(d_evals, d_hits) for one batch: rows evaluated (the live count,
    identical for every property) and rows whose condition mask held, per
    property.  Shapes follow :func:`cart_carry_shapes`."""
    import jax.numpy as jnp

    p = max(n_props, 1)
    n_live = jnp.sum(live, dtype=jnp.int64)
    d_evals = jnp.where(jnp.arange(p) < n_props, n_live, jnp.int64(0))
    if n_props:
        d_hits = jnp.sum(live[:, None] & masks, axis=0, dtype=jnp.int64)
    else:
        d_hits = jnp.zeros((p,), jnp.int64)
    return d_evals, d_hits


def trim_hist(values) -> list:
    """Drop the all-zero tail of a histogram (deterministic, keeps at
    least one bin) — report/JSON ergonomics only."""
    vals = [int(v) for v in np.asarray(values).tolist()]
    last = 0
    for i, v in enumerate(vals):
        if v:
            last = i
    return vals[: last + 1]


def shard_imbalance(loads) -> dict:
    """Imbalance summary over per-shard table loads: max/mean plus their
    ratio (1.0 = perfectly balanced; fingerprint uniformity should keep
    this near 1 — routing skew shows up here first on multi-chip runs).
    The mesh engine's ``mesh_stats`` reads it off the final table."""
    arr = np.asarray(loads, dtype=np.float64).reshape(-1)
    if arr.size == 0:
        return {"max": 0, "mean": 0.0, "ratio": 1.0}
    mean = float(arr.mean())
    mx = float(arr.max())
    return {
        "max": int(mx),
        "mean": round(mean, 3),
        "ratio": round(mx / mean, 4) if mean > 0 else 1.0,
    }


def snapshot(
    *,
    depth_hist,
    action_hist,
    prop_evals,
    prop_hits,
    prop_names,
    states: int,
    unique: int,
    por=None,
) -> dict:
    """Assemble the host-facing cartography block (JSON-safe) from raw
    counter arrays.  ``states``/``unique`` are the engine's cumulative
    totals — the duplicate/fresh split is derived, not separately counted
    (it is exactly ``states - unique`` by construction)."""
    n_props = len(prop_names)
    out = {
        "v": CARTOGRAPHY_V,
        "depth_hist": trim_hist(depth_hist),
        "action_hist": [int(v) for v in np.asarray(action_hist).tolist()],
        "props": [
            {
                "name": prop_names[i],
                "evaluated": int(np.asarray(prop_evals)[i]),
                "condition_hits": int(np.asarray(prop_hits)[i]),
            }
            for i in range(n_props)
        ],
        "fresh_inserts": int(unique),
        "duplicate_hits": max(int(states) - int(unique), 0),
    }
    if por is not None:
        # partial-order reduction: the reduced-vs-full split (ops/por.py)
        # — rows expanded with a reduced ample set, proviso-forced full
        # re-expansions, and candidates never generated at all
        out["por"] = {k: int(v) for k, v in dict(por).items()}
    return out

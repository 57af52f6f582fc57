"""The wavefront engine's carry: one pytree with named fields.

The carry is what the jitted run program takes and returns and what the
host loop holds between device calls: the visited table, the work queue,
the cursors and counters (the thirteen *base* buffers, which are also
exactly what a snapshot keeps, under these names), and up to four optional
*tails* a build flag adds - ``err`` (checked mode's sticky failure flag),
``por`` (the boundary boost and the reduced-vs-full tallies), ``spill``
(the Bloom filter, the spill base and the pending buffers) and ``cart``
(the cartography counters).  An absent tail is ``None``, an empty subtree.

This module is the one place that knows the buffers' names and order,
which tails a ``(checked, por, spill, cartography)`` combination builds,
their shapes at given capacities (:func:`carry_avals`), the snapshot keys
and the packed stats vector's layout.  The mesh engine maps its partition
rules over :func:`leaf_names`, the memory ledger zips them against the
avals (:func:`ledger_name` keeps its historical spellings), and the
engine reads and writes fields by name.  Leaves flatten in field order:
base, ``err``, ``por``, ``spill``, ``cart``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.cartography import (
    cart_carry_shapes,
    prefix_depth_hist,
    queue_depth_hist,
    queue_depth_hist_np,
)
from ..ops.hashing import EMPTY


def _node(cls):
    """A frozen dataclass registered as a pytree; ``replace`` returns a
    copy with the given fields swapped (every other field the same
    object)."""
    cls = dataclasses.dataclass(frozen=True)(cls)
    cls.replace = dataclasses.replace
    return jax.tree_util.register_dataclass(cls)


@_node
class PorTail:
    boost: Any  # i32: > 0 forces one fully expanded batch (boundaries)
    stats: Any  # i64[3]: reduced rows, proviso re-expansions, pruned


@_node
class SpillTail:
    bloom: Any  # u32[bits / 32]: the spilled set's filter (host-written)
    base: Any  # i64: unique states living off the device
    pend_fp: Any  # the Bloom-positive candidates deferred to the host
    pend_rows: Any
    pend_parent: Any
    pend_ebits: Any
    pend_depth: Any
    pend_count: Any  # i32
    stats: Any  # i64[2]: deferred, decided on the device


@_node
class CartTail:
    action_hist: Any
    prop_evals: Any
    prop_hits: Any


@_node
class Carry:
    # No occupancy-counts buffer exists: bucket occupancy is implicit in
    # the table (slots fill densely; see ops/buckets.py).
    table_fp: Any
    table_parent: Any
    q_rows: Any
    q_fp: Any
    q_ebits: Any
    q_depth: Any
    head: Any
    tail: Any
    unique: Any
    scount: Any
    disc: Any
    maxdepth: Any
    status: Any
    # the tails: snapshots drop them, a resumed run starts them afresh
    # (:func:`fresh_tails`; the spill tail from the snapshot's host tier)
    err: Any = None
    por: Optional[PorTail] = None
    spill: Optional[SpillTail] = None
    cart: Optional[CartTail] = None

    def base(self) -> tuple:
        """The thirteen base buffers, in order."""
        return tuple(getattr(self, k) for k in SNAPSHOT_KEYS)

    def _moved(self, to, fields) -> "Carry":
        return self.replace(**{
            k: to(getattr(self, k)) for k in fields or SNAPSHOT_KEYS
        })

    def pulled(self, fields=None) -> "Carry":
        """``fields`` (default: the base) as numpy arrays on the host;
        the tails stay where they are."""
        return self._moved(np.asarray, fields)

    def pushed(self, fields=None) -> "Carry":
        """The inverse of :meth:`pulled`: ``fields`` as device arrays."""
        return self._moved(jnp.asarray, fields)


_TAILS = ("err", "por", "spill", "cart")
# the on-disk names of a snapshot's buffers: the base fields, in order
SNAPSHOT_KEYS = tuple(
    f.name for f in dataclasses.fields(Carry) if f.name not in _TAILS
)
QUEUE_FIELDS = SNAPSHOT_KEYS[2:6]


def leaf_names(carry: Carry) -> tuple:
    """The names of ``carry``'s leaves (arrays, avals, shardings alike), in
    flattening order: a base buffer's own name, a tail's buffers prefixed
    with the tail's (``spill_pend_fp``, ``cart_action_hist``)."""
    paths, _ = jax.tree_util.tree_flatten_with_path(carry)
    return tuple("_".join(k.name for k in path) for path, _ in paths)


def ledger_name(name: str) -> str:
    """The memory ledger's spelling of a leaf's name (its records and the
    planners' tables predate the carry's)."""
    if name == "err":
        return "checked_err"
    if name.startswith("spill_pend_"):
        return name[len("spill_"):]
    return name


def queue_alloc(qcap: int, m: int, por: bool, spill) -> int:
    """Rows the queue buffers hold for a high-water mark ``qcap``: one
    window of ``m = batch * max_actions`` candidates over it, so the
    dynamic slice/update at ``head``/``tail`` never clamps.  POR's cycle
    proviso appends a SECOND novel window a step (at ``tail + n_new``) -
    a clamped ``dynamic_update_slice`` would silently shift the write onto
    live queue rows - and the spill inject program appends a window
    ``pend_cap`` (``spill[1]``) wide the same way, so the larger governs."""
    if por:
        return qcap + 2 * m
    if spill:
        return qcap + max(spill[1], m)
    return qcap + m


def carry_avals(tensor, n_props: int, cap: int, qcap: int, batch: int,
                checked: bool, cartography: bool = False,
                por: bool = False, spill=None) -> Carry:
    """Abstract carry of the engine built for these capacities - what
    ahead-of-time compilation (``run_fn.lower(avals).compile()``), the
    memory ledger and the mesh engine's shardings need instead of concrete
    arrays.  Mirrors ``init_fn``'s output exactly (``tests/test_carry.py``
    holds the two together for every flag combination)."""
    width, arity = tensor.width, tensor.max_actions
    m = batch * arity
    qalloc = queue_alloc(qcap, m, por, spill)
    sds = jax.ShapeDtypeStruct
    avals = Carry(
        sds((cap,), jnp.uint64), sds((cap,), jnp.uint64),
        sds((qalloc, width), jnp.uint64), sds((qalloc,), jnp.uint64),
        sds((qalloc,), jnp.uint32), sds((qalloc,), jnp.uint32),
        sds((), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int64), sds((), jnp.int64),
        sds((max(n_props, 1),), jnp.uint64),
        sds((), jnp.int32), sds((), jnp.int32),
    )
    if checked:
        avals = avals.replace(err=sds((), jnp.bool_))
    if por:
        avals = avals.replace(
            por=PorTail(sds((), jnp.int32), sds((3,), jnp.int64))
        )
    if spill:
        spill_bits, pend_cap = spill
        palloc = pend_cap + m
        avals = avals.replace(spill=SpillTail(
            sds((spill_bits // 32,), jnp.uint32), sds((), jnp.int64),
            sds((palloc,), jnp.uint64), sds((palloc, width), jnp.uint64),
            sds((palloc,), jnp.uint64), sds((palloc,), jnp.uint32),
            sds((palloc,), jnp.uint32), sds((), jnp.int32),
            sds((2,), jnp.int64),
        ))
    if cartography:
        avals = avals.replace(cart=CartTail(*(
            sds(s, jnp.int64) for s in cart_carry_shapes(arity, n_props)
        )))
    return avals


def fresh_tails(avals: Carry) -> dict:
    """The tails a carry of ``avals``'s build starts from, by field: the
    failure flag clear, ``por.boost`` 0, every tally at zero, an all-zero
    Bloom filter (nothing spilled yet, so nothing ever defers) and empty
    pending buffers."""
    tails = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        {k: getattr(avals, k) for k in _TAILS},
    )
    if avals.spill is not None:
        tails["spill"] = tails["spill"].replace(
            pend_fp=jnp.full(avals.spill.pend_fp.shape, EMPTY, jnp.uint64)
        )
    return tails


def repad_queue(carry: Carry, qalloc: int) -> Carry:
    """The (host) queue buffers padded (EMPTY/0 fill) or truncated to
    ``qalloc`` rows.  Shared by snapshot-resume and growth."""
    out = {}
    for k in QUEUE_FIELDS:
        arr = np.asarray(getattr(carry, k))
        if arr.shape[0] < qalloc:
            pad_shape = (qalloc - arr.shape[0],) + arr.shape[1:]
            fill = EMPTY if k == "q_fp" else 0
            arr = np.concatenate([arr, np.full(pad_shape, fill, arr.dtype)])
        out[k] = arr[:qalloc]
    return carry.replace(**out)


# -- the packed stats vector ---------------------------------------------------
#
# Every scalar the host loop reads rides one small ``u64`` vector, so a host
# sync costs a single device round-trip: [head, tail, unique, scount,
# maxdepth, status, dsteps, append_chunks, disc..., por stats (3)?, spill
# section (4)?, cartography section?].  ``dsteps`` is the trip count of the
# device call's ``while_loop`` and ``append_chunks`` the chunk writes of its
# appends (``wavefront.append_novel``'s trips, summed over the call's steps);
# both ride the run program's loop beside the carry, not in it, and are 0
# from ``init_fn`` and from :func:`stats_np`.
_STATS_SCALARS = ("head", "tail", "unique", "scount", "maxdepth", "status")
ST_DSTEPS = len(_STATS_SCALARS)
ST_DISC = ST_DSTEPS + 2


class Stats(NamedTuple):
    """The packed vector, read back by name (:func:`read_stats`)."""

    head: int
    tail: int
    unique: int
    scount: int
    maxdepth: int
    status: int
    dsteps: int
    append_chunks: int
    disc: np.ndarray
    por: Optional[np.ndarray]  # the three POR tallies
    # [pending count, spill base, deferred total, on-device total]
    spill: Optional[np.ndarray]
    # the queue-derived depth histogram, then the counter buffers
    cart: Optional[np.ndarray]


def stats_of(carry: Carry, dsteps, append_chunks):
    """The packed stats vector of ``carry``, on the device (traced into
    the run and init programs)."""
    parts = [
        jnp.stack(
            [getattr(carry, k).astype(jnp.uint64) for k in _STATS_SCALARS]
            + [dsteps.astype(jnp.uint64), append_chunks.astype(jnp.uint64)]
        ),
        carry.disc,
    ]
    if carry.por is not None:
        parts.append(carry.por.stats.astype(jnp.uint64))
    if carry.spill is not None:
        # the pending count is the host's resolve trigger
        parts.append(jnp.stack([
            carry.spill.pend_count.astype(jnp.uint64),
            carry.spill.base.astype(jnp.uint64),
        ]))
        parts.append(carry.spill.stats.astype(jnp.uint64))
    if carry.cart is not None:
        # the depth histogram is derived HERE - once per sync, from the
        # depth-sorted queue (every fresh insert ever made sits in
        # qdepth[:tail]) - so the per-step program pays nothing for it
        parts.append(
            queue_depth_hist(carry.q_depth, carry.tail).astype(jnp.uint64)
        )
        parts += [
            c.astype(jnp.uint64) for c in jax.tree.leaves(carry.cart)
        ]
    return jnp.concatenate(parts)


_prefix_depth_hist = jax.jit(prefix_depth_hist)


def depth_hist(qdepth, n) -> np.ndarray:
    """The per-depth histogram of ``qdepth[:n]``, counted where the lanes
    lie: of a queue on the device ``DEPTH_BINS`` words cross, not 4 B a
    lane (a growth on the device, and the sync after it)."""
    if isinstance(qdepth, jax.Array):
        return np.asarray(_prefix_depth_hist(qdepth, n))
    return queue_depth_hist_np(qdepth, int(np.asarray(n)))


def stats_np(carry: Carry) -> np.ndarray:
    """Host-side equivalent of :func:`stats_of` (same layout), for a carry
    the host has just transformed."""
    vals = [np.asarray(getattr(carry, k)) for k in _STATS_SCALARS] + [0, 0]
    vals.extend(np.asarray(carry.disc))
    if carry.por is not None:
        vals.extend(np.asarray(carry.por.stats).reshape(-1))
    if carry.spill is not None:
        vals.append(np.asarray(carry.spill.pend_count))
        vals.append(np.asarray(carry.spill.base))
        vals.extend(np.asarray(carry.spill.stats).reshape(-1))
    if carry.cart is not None:
        vals.extend(depth_hist(carry.q_depth, carry.tail))
        for arr in jax.tree.leaves(carry.cart):
            vals.extend(np.asarray(arr).reshape(-1))
    return np.asarray(vals, dtype=np.uint64)


def read_stats(stats: np.ndarray, carry: Carry) -> Stats:
    """``stats`` by name; ``carry`` (the one it was packed from, or any of
    its build) says how long ``disc`` is and which sections follow it."""
    scalars = [int(v) for v in stats[:ST_DISC]]
    o = ST_DISC + carry.disc.shape[0]
    disc = np.asarray(stats[ST_DISC:o])
    por = spill = cart = None
    if carry.por is not None:
        por, o = stats[o:o + 3], o + 3
    if carry.spill is not None:
        spill, o = stats[o:o + 4], o + 4
    if carry.cart is not None:
        cart = stats[o:]
    return Stats(*scalars, disc, por, spill, cart)

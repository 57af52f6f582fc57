"""Named-mesh construction + partition rules for the mesh engine.

One place for everything the GSPMD-partitioned wavefront needs to say
about *placement* (``parallel/mesh.py`` says nothing — it only applies
what this module decides):

 - :data:`MESH_AXES` — the ``('host', 'chip')`` axis pair.  One process
   builds a ``1 x N`` mesh over its local devices; the ``host`` axis is
   there so that the same names scale to DCN x ICI without touching the
   partition rules (everything below shards over the *flattened* pair).
 - :func:`build_mesh` — the one constructor both the checker and the
   tests use.
 - :func:`match_partition_rules` — the regex-rule matcher (the
   SNIPPETS.md [2]/[3] pattern): first rule whose pattern matches a
   buffer's name decides its :class:`~jax.sharding.PartitionSpec`, with
   two hard guards layered on top — scalars are always replicated, and a
   dimension whose size the flattened mesh does not divide falls back to
   replication (jax rejects uneven GSPMD shards with a ``ValueError``;
   correctness never depends on a buffer *being* sharded, only on the
   rules being applied consistently to inputs and outputs).
 - :data:`WAVEFRONT_CARRY_RULES` — the partition-rule table for the
   wavefront carry: visited table sharded by bucket owner (positions are
   ``bucket * SLOTS + slot`` and :func:`jax.sharding.NamedSharding`
   gives shard ``k`` the contiguous row range ``[k*cap/D, (k+1)*cap/D)``,
   i.e. a contiguous *bucket* range — ownership is a layout fact, so
   candidate routing becomes a sharding constraint the compiler lowers
   to all-to-all/all-gather instead of a hand-scheduled collective),
   queue/candidate buffers sharded along the frontier dimension, and
   every counter/flag replicated.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("host", "chip")


ENV_MESH = "STATERIGHT_TPU_MESH"


def resolve_mesh_flag(mode, devices):
    """Resolve the mesh-engine request: ``(enabled, n_devices)``.  An
    explicit builder setting wins (``CheckerBuilder.mesh()``); otherwise
    the ``STATERIGHT_TPU_MESH`` env knob — ``1`` arms the engine over
    every local device, an integer ``N > 1`` bounds it to N devices, ``0``
    /unset leaves it off.  Anything else warns LOUDLY and is ignored: a
    typo'd knob must never masquerade as "the mesh engine buys
    nothing"."""
    import os
    import sys

    if mode is not None:
        return bool(mode), devices
    raw = os.environ.get(ENV_MESH, "")
    if raw in ("", "0"):
        return False, None
    if raw == "1":
        return True, None
    try:
        n = int(raw)
        if n > 1:
            return True, n
    except ValueError:
        pass
    print(
        f"stateright-tpu: ignoring malformed {ENV_MESH}={raw!r} "
        "(expected 1, 0, or a device count > 1; docs/mesh.md)",
        file=sys.stderr,
    )
    return False, None


# -- mesh construction -------------------------------------------------------

def build_mesh(n_devices: Optional[int] = None) -> Mesh:
    """The named ``('host', 'chip')`` mesh the engine partitions over:
    ``1 x N`` over the first ``n_devices`` local devices (all of them
    when unset)."""
    devs = jax.local_devices()
    if n_devices is not None:
        if int(n_devices) > len(devs):
            raise ValueError(
                f"mesh engine asked for {n_devices} devices but only "
                f"{len(devs)} are visible (force more with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)"
            )
        devs = devs[: int(n_devices)]
    return Mesh(np.asarray(devs).reshape(1, len(devs)), MESH_AXES)


def replicated(mesh: Mesh) -> NamedSharding:
    """The fully replicated placement (counters, flags, packed stats)."""
    return NamedSharding(mesh, P())


# -- partition rules ---------------------------------------------------------

# First match wins (the match_partition_rules contract).  The visited
# table shards by bucket owner; queue/frontier buffers shard along the
# row dimension; the terminal catch-all replicates counters, discovery
# fingerprints, status/error flags, and every capacity-independent tail
# (POR stats, cartography counters).
WAVEFRONT_CARRY_RULES = (
    (r"^table_", P(MESH_AXES)),
    (r"^q_", P(MESH_AXES)),
    (r".*", P()),
)


class StepPlacement:
    """What the mesh engine asks of the step program INSIDE it
    (``wavefront._build_engine(place=...)``): where values lie, never
    where the carry lies - its in/out shardings are the rules' above, so
    what is live between two device calls is the same bytes a chip
    whatever this class does.

    The queue is sharded by contiguous row range and has ONE head and ONE
    tail.  A ``dynamic_slice`` / ``dynamic_update_slice`` at a traced
    offset of a sharded dimension the partitioner can only do by gathering
    the whole operand on every chip (four 2.4 GB all-gathers a step at
    paxos-6's 512 B rows); the same window read or written BY ROW INDEX it
    splits by shard: every chip gathers / scatters the indices inside its
    own row range, and a read is completed by an all-reduce of the
    window's rows.  No collective is written here: each is the compiler's.
    """

    def __init__(self, mesh: Mesh):
        self._mesh = mesh
        self._lanes = NamedSharding(mesh, P(MESH_AXES))
        self._whole = replicated(mesh)

    def lanes(self, x):
        """``x`` split along its first dimension (a batch's lanes, a
        candidate block's): a chip computes its share.  Replicated where
        the mesh does not divide it, as the rules decide for the carry."""
        if x.ndim == 0 or x.shape[0] % self._mesh.size:
            return self.whole(x)
        return jax.lax.with_sharding_constraint(x, self._lanes)

    def whole(self, x):
        """``x`` on every chip."""
        return jax.lax.with_sharding_constraint(x, self._whole)

    def pop(self, q, head, n: int):
        """Rows ``[head, head + n)`` of queue buffer ``q``, split by lane
        (``dynamic_slice(q, head, n)``'s value: the queue's allocation
        keeps the window in bounds, ``carry.queue_alloc``)."""
        at = head + jax.lax.iota(head.dtype, n)
        window = q.at[at].get(
            indices_are_sorted=True, unique_indices=True,
            mode="promise_in_bounds",
        )
        return self.lanes(self.whole(window))

    def append(self, q, rows, tail):
        """``q`` with ``rows`` written at ``[tail, tail + len(rows))``
        (``dynamic_update_slice(q, rows, tail)``'s value), every chip
        holding all of ``rows`` to do so: by row index, payload and narrow
        columns alike, so a chip writes the rows that fall in its own range
        and no collective moves more than ``rows``.  An update slice at a
        traced offset of a SHARDED buffer is done by gathering the buffer on
        every chip; for a one-word column that was the cheaper spelling
        while the append wrote one candidate-stack-wide window a step (one
        gather of a column against a scatter's price an index over the whole
        stack), and is not inside ``append_novel``'s loop, where the gather
        would be paid a TRIP and the indices are a chunk's.  Either way the
        buffer stays SHARDED in the carry."""
        at = tail + jax.lax.iota(tail.dtype, rows.shape[0])
        return q.at[at].set(
            self.whole(rows), indices_are_sorted=True, unique_indices=True,
            mode="promise_in_bounds",
        )


def match_partition_rules(rules, names: Sequence[str], avals,
                          mesh: Mesh):
    """Resolve one :class:`NamedSharding` per named buffer.

    ``rules`` is a sequence of ``(pattern, PartitionSpec)`` pairs; the
    first pattern that ``re.search``-matches a buffer's name decides its
    spec (a name no rule matches is an error — rule tables end with a
    catch-all on purpose, so a miss means the table and the carry layout
    drifted apart; so does a name without a buffer).  Two guards override any matched spec:

     - rank-0 buffers are replicated (nothing to shard);
     - a dimension whose global size the product of the spec's mesh axes
       does not divide is replicated instead — jax raises on uneven
       GSPMD shards, and replication is always semantically equivalent.
    """
    out = []
    for name, aval in zip(names, avals, strict=True):
        spec = None
        for pat, rule_spec in rules:
            if re.search(pat, name):
                spec = rule_spec
                break
        if spec is None:
            raise ValueError(
                f"no partition rule matches carry buffer {name!r} — the "
                "rule table and the carry layout drifted apart"
            )
        if getattr(aval, "ndim", 0) == 0:
            spec = P()
        else:
            parts = list(spec)
            for dim, axes in enumerate(parts):
                if axes is None:
                    continue
                axis_names = (axes,) if isinstance(axes, str) else tuple(axes)
                size = int(
                    np.prod([mesh.shape[a] for a in axis_names])
                )
                if dim >= aval.ndim or aval.shape[dim] % size != 0:
                    parts[dim] = None
            spec = P(*parts)
        out.append(NamedSharding(mesh, spec))
    return tuple(out)

"""The multi-device engine — GSPMD partitioning of the wavefront engine
over a named ``('host', 'chip')`` mesh.

The *global* wavefront program (``wavefront.py``, unchanged — same
jaxprs, same counters, same discovery rule) is handed to the compiler
with the carry's placement expressed as ``NamedSharding`` partition
rules (``parallel/partition.py``), and GSPMD inserts the collectives:

 - The visited table shards by bucket owner.  Table positions are
   ``bucket * SLOTS + slot`` and a ``P(('host','chip'))`` sharding of
   the row dimension gives shard ``k`` the contiguous range
   ``[k*cap/D, (k+1)*cap/D)`` — a contiguous *bucket* range, so
   "ownership" is a layout fact and candidate routing becomes the
   all-to-all the compiler lowers for the scatter, not a hand-scheduled
   collective.  With the PR 10 per-channel layout armed the (src,dst)
   channel map makes those destinations static in the jaxpr.
 - Queue/candidate buffers shard along the frontier dimension (when
   divisible; replication otherwise — semantics never depend on it).
 - Counters, discovery fingerprints, and termination state replicate.

Because the program is the single-device engine's own, parity with it is
by construction: counts, verdicts, discovery traces, and kill+resume
snapshots are bit-identical (pinned by tests/test_mesh.py).  No
collective is written by hand anywhere in the package (held by
``test_no_second_engine``): every one is the compiler's.

Placement INSIDE the step (``partition.StepPlacement``, handed to
``wavefront._build_engine(place=...)``) says where the step's values lie:
the popped batch and the candidate block split by lane, so expand,
``slot_canonicalize`` and the row hash run on a 1/D share a chip; the
insert's keys and the novel rows on every chip; the queue read and
written by row index, which the partitioner splits by shard where a
dynamic slice of a sharded dimension gathers the whole queue.  The carry's
own placement - what is live on a chip between two device calls - is the
rules' and nothing else's.

Host-loop mechanics are inherited unchanged: growth, checkpointing, and
resume round-trip the carry through host numpy; re-entry as plain numpy
is fine because ``jax.jit``'s ``in_shardings`` re-shards inputs on the
way in.  The host loop is one controller's: it pulls the carry for
growth and checkpoints, so the mesh must be fully addressable from this
process (``_pre_run_validate`` refuses one that is not; a
``jax.distributed`` run would need a process-spanning host loop,
docs/mesh.md "Multi-host").  A discovery's path and the per-shard load
are read where the table lies (``ops/buckets.parent_chains`` and
``_shard_traffic`` under the table's own sharding): the visited table
never crosses to the host inside a check.

The spill tier stays single-device (the inherited ``_init_common``
rejection).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.buckets import SLOTS, bucket_key
from ..ops.hashing import EMPTY
from ..telemetry.collectives import program_record
from .carry import Carry, leaf_names
from .partition import (
    WAVEFRONT_CARRY_RULES,
    StepPlacement,
    build_mesh,
    match_partition_rules,
    replicated,
)
from .wavefront import TpuChecker


@functools.partial(jax.jit, static_argnames="shards")
def _shard_traffic(table_fp, table_parent, shards: int):
    """``(load[shards], route[shards, shards])`` of a table split in
    ``shards`` equal contiguous ranges, counted where it lies: ``load[d]``
    the occupied slots of range ``d``, ``route[s, d]`` those of them whose
    parent's bucket lies in range ``s``.  ``shards + shards**2`` integers
    come back, whatever the table holds; under the table's own sharding
    every chip counts its range."""
    nbuckets = table_fp.shape[0] // SLOTS
    bucket_bits = nbuckets.bit_length() - 1
    occupied = table_fp != EMPTY
    routed = occupied & (table_parent != jnp.uint64(0))
    parent_bucket = (
        bucket_key(table_parent) >> jnp.uint64(64 - bucket_bits)
    ).astype(jnp.int32)
    # a parent's position is its bucket's first slot (mesh_stats' caveat:
    # below SLOTS slots a shard a bucket straddles shards)
    parent_shard = parent_bucket * SLOTS // (table_fp.shape[0] // shards)

    def by_range(mask):
        return jnp.sum(mask.reshape(shards, -1), axis=1, dtype=jnp.int32)

    route = jnp.stack(
        [by_range(routed & (parent_shard == s)) for s in range(shards)]
    )
    return by_range(occupied), route


class MeshTpuChecker(TpuChecker):
    """Wavefront BFS partitioned over a named device mesh.

    What ``spawn_tpu`` returns whenever more than one device is asked
    for (``CheckerBuilder._mesh_request``: ``devices=N``, ``n_devices=``,
    ``mesh=``, ``.mesh()``, ``--mesh``, ``STATERIGHT_TPU_MESH``).
    Everything but placement is the single-device engine."""

    _engine_tag = "mesh"

    def __init__(
        self,
        options,
        mesh: Optional[Mesh] = None,
        n_devices: Optional[int] = None,
        **kw,
    ):
        self._mesh = mesh if mesh is not None else build_mesh(n_devices)
        self._mesh_stats_cache = None
        super().__init__(options, **kw)

    # -- engine construction -------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def n_devices(self) -> int:
        return int(self._mesh.size)

    def _engine_key(self, cap, qcap, batch, cand) -> tuple:
        # the compiled-run cache lives on the tensor twin and is SHARED
        # with single-device checkers of the same model: mesh entries
        # must never collide with theirs (or with a different mesh's)
        return super()._engine_key(cap, qcap, batch, cand) + (
            ("mesh",) + tuple(d.id for d in self._mesh.devices.flat),
        )

    def _place(self, avals: Carry) -> Carry:
        """One ``NamedSharding`` per carry buffer, by the partition rules
        over the carry's own names.  The memory ledger reads
        ``per_device_bytes`` off the same placement's shard shapes, so it
        cannot drift from ``partition.py`` (telemetry/memory.py)."""
        leaves, treedef = jax.tree.flatten(avals)
        return treedef.unflatten(match_partition_rules(
            WAVEFRONT_CARRY_RULES, leaf_names(avals), leaves, self._mesh
        ))

    def _step_placement(self) -> StepPlacement:
        return StepPlacement(self._mesh)

    def _memory_extra(self) -> dict:
        return {**super()._memory_extra(), "devices": self.n_devices}

    def _build(self, cap, qcap, batch, cand):
        """The single-device engine's own programs (the step's values
        placed by :meth:`_step_placement`), re-jitted with the carry's
        partition rules as in/out shardings.  GSPMD inserts the
        cross-shard collectives; what is computed — hence every count,
        verdict, and discovery — is untouched."""
        init_fn, run_fn = super()._build(cap, qcap, batch, cand)
        shardings = self._place(self._avals(cap, qcap, batch))
        rep = replicated(self._mesh)
        mesh_init = jax.jit(init_fn, out_shardings=(shardings, rep))
        mesh_run = jax.jit(
            run_fn,
            in_shardings=(shardings,),
            out_shardings=(shardings, rep),
            donate_argnums=(0,),
        )
        return mesh_init, mesh_run

    def _compiles_ahead(self) -> bool:
        # the recorder wants the executable too: its collectives
        return super()._compiles_ahead() or self.flight_recorder is not None

    def _acquire_engine(self, cache, key, cap, qcap, batch, cand,
                        kind: str, span_ctx) -> tuple:
        eng, source = super()._acquire_engine(
            cache, key, cap, qcap, batch, cand, kind, span_ctx
        )
        rec = self.flight_recorder
        if rec is not None and isinstance(eng[1], jax.stages.Compiled):
            # one ``mesh.program`` record a compiled mesh step program
            # (telemetry/collectives.py): what GSPMD put between the chips
            try:
                rec.record(
                    "mesh.program", cap=cap, qcap=qcap, batch=batch,
                    cand=cand, devices=self.n_devices,
                    **program_record(eng[1]),
                )
            except Exception:  # noqa: BLE001 - a readout never fails a run
                pass
        return eng, source

    def _pre_run_validate(self) -> None:
        super()._pre_run_validate()
        local = {d.id for d in jax.local_devices()}
        if not all(d.id in local for d in self._mesh.devices.flat):
            raise NotImplementedError(
                "the mesh holds devices this process cannot address: the "
                "host loop pulls the carry for growth and checkpoints, "
                "so the mesh engine runs one process's devices "
                "(docs/mesh.md 'Multi-host')"
            )

    # -- per-shard load / routing imbalance ----------------------------------

    def mesh_stats(self) -> Optional[dict]:
        """Per-shard visited-table load, the parent-owner -> child-owner
        routing matrix, and the imbalance summary
        (``ops/cartography.shard_imbalance``).  None while the run is
        in flight.

        Ownership is derived from the final table exactly as the
        partition rules place it: position ``p`` belongs to shard
        ``p // (cap/D)``; a parent's position is its bucket
        (``ops/buckets.bucket_key``'s high bits) times ``SLOTS``.
        ``route[s][d]`` counts unique states owned by shard ``d`` whose
        parent is owned by shard ``s`` (init states, parent fingerprint 0,
        are in ``shard_load`` but route nowhere).  Counted on the device,
        under the table's own sharding (:func:`_shard_traffic`): ``D +
        D x D`` integers cross to the host, never the table."""
        if not self._done.is_set() or self._final_carry is None:
            return None
        cached = self._mesh_stats_cache
        if cached is not None and cached[0] is self._final_carry:
            return dict(cached[1])
        from ..ops.cartography import shard_imbalance

        tfp = self._final_carry.table_fp
        d = self.n_devices
        # a table the mesh does not divide is replicated: one owner
        # (the partition rules' guard, match_partition_rules)
        owners = d if tfp.shape[0] % d == 0 else 1
        counted = _shard_traffic(
            tfp, self._final_carry.table_parent, shards=owners
        )
        load = np.zeros(d, np.int64)
        route = np.zeros((d, d), np.int64)
        load[:owners], route[:owners, :owners] = map(np.asarray, counted)
        out = {
            "devices": d,
            "axes": {k: int(v) for k, v in self._mesh.shape.items()},
            "shard_load": [int(v) for v in load],
            "imbalance": shard_imbalance(load),
            "route_matrix": [[int(v) for v in row] for row in route],
            "routed_states": int(route.sum()),
        }
        self._mesh_stats_cache = (self._final_carry, out)
        return out

    def _run_impl(self):
        super()._run_impl()
        # the imbalance readout rides the results + the cartography block
        # (keys shard_load / shard_imbalance / route_matrix)
        try:
            stats = self.mesh_stats() if self._results is not None else None
        except Exception:  # noqa: BLE001 - a readout must never fail a run
            stats = None
        if stats is None:
            return
        self._results["mesh"] = stats
        cart = self._results.get("cartography")
        if isinstance(cart, dict):
            cart.setdefault("shard_load", stats["shard_load"])
            cart.setdefault("shard_imbalance", stats["imbalance"])
            cart.setdefault("route_matrix", stats["route_matrix"])
        if self.flight_recorder is not None:
            self.flight_recorder.record(
                "mesh", devices=stats["devices"],
                shard_load=stats["shard_load"],
                imbalance=stats["imbalance"],
                routed_states=stats["routed_states"],
            )

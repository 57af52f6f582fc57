"""HBM ledger & capacity planning: where the memory goes, and when it
runs out.

The observability triad's third axis (docs/telemetry.md "Memory ledger"):
the flight recorder answers *where time goes* (PR 2), cartography/health
*how the search is going* (PR 5) — this module answers *where the memory
goes*.  GPUexplore's scalability study (PAPERS.md) shows device memory,
not compute, is the binding constraint for explicit-state checking at
scale; the ROADMAP's billion-state spill tier cannot be built before the
stack can *measure* memory.

Two reconciling views, deliberately separated:

 - **Analytic footprint model** — exact bytes-per-buffer for every
   device-resident carry buffer (visited table fp/parent, queue/frontier
   rows, cartography counters, POR tensors, scalars), derived from the
   engines' dtypes and shapes at the current capacity AND at every future
   growth rung.  Computable and testable on CPU: the wavefront specs are
   derived from the engine's own ``carry_avals`` (the same signature the
   prewarm AOT path compiles against, so agreement is already pinned),
   and ``tests/test_memory.py`` pins analytic bytes == the live engine
   buffers' ``nbytes`` EXACTLY, on one device and on a mesh.
 - **Live device readings** — ``device.memory_stats()`` bytes/peak where
   the backend supports them (TPU; CPU returns nothing and every
   consumer degrades to the analytic path), and
   ``compiled.memory_analysis()`` temp/argument/output bytes captured at
   compile time for fresh, prewarm, and persistent-cache executables
   (backfilled onto ``compile`` ring records via the existing ``amend()``
   path).

On top of the ledger:

 - a **growth-transient forecast**: growth migration holds the old AND
   new carry live across the swap (the host rehashes into fresh buffers
   while the old ones are still referenced), so the next rung's peak is
   ``total(rung) + total(rung+1)`` — and the max reachable capacity on a
   device is the largest rung whose *transient* fits, not whose steady
   state does;
 - a ``growth_oom_risk`` health condition (``telemetry/health.py``):
   the table load is approaching the growth trigger and the forecast
   says the next rung's transient does not fit;
 - a **preflight capacity guard** in ``spawn_tpu`` (``parallel/_base``):
   warn — flag-gated error via ``STATERIGHT_TPU_CAPACITY_GUARD=error`` —
   when the requested capacity analytically exceeds device memory,
   before any compile is paid.

Contract, mirroring telemetry/checked/prededup/cartography: the ledger
adds ZERO ops to the step jaxpr — it is pure host-side accounting over
shapes the engines already know — so ledger off (and on!) leaves the run
program bit-identical (pinned by test).  Enabled via
``.telemetry(memory=True)`` (implied by ``.report()``); the device
budget can be overridden/simulated with ``STATERIGHT_TPU_DEVICE_BYTES``
(bytes), which is also how CPU tests exercise the guard.
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Optional

import numpy as np

# memory snapshot / ring-record schema version
MEMORY_V = 1

# table load at which the growth forecast becomes a live risk: half-way
# to the engines' 25% growth trigger — the run WILL grow soon, and if the
# next rung's transient does not fit, the operator should know before it
# happens (health.py reads this)
OOM_RISK_LOAD = 0.125

ENV_DEVICE_BYTES = "STATERIGHT_TPU_DEVICE_BYTES"
ENV_CAPACITY_GUARD = "STATERIGHT_TPU_CAPACITY_GUARD"

# engines grow the visited table when unique * 4 > capacity, so a rung of
# ``cap`` slots holds at most cap/4 unique states before the NEXT rung's
# transient must fit (ops/buckets.py Poisson tail rationale)
GROWTH_LOAD_DENOM = 4


class BufferSpec:
    """One device-resident buffer: name, shape, dtype, exact bytes — and
    the bytes ONE device holds of it, which differ from ``nbytes`` only
    where ``sharding`` (the buffer's own ``NamedSharding`` on the mesh
    engine) splits it."""

    __slots__ = ("name", "shape", "dtype", "nbytes", "per_device_nbytes")

    def __init__(self, name: str, shape: tuple, dtype, sharding=None) -> None:
        self.name = name
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)

        def nbytes(shape) -> int:
            return int(np.prod(shape, dtype=np.int64)) * self.dtype.itemsize

        self.nbytes = nbytes(self.shape)
        self.per_device_nbytes = (
            self.nbytes if sharding is None
            else nbytes(sharding.shard_shape(self.shape))
        )

    def __repr__(self) -> str:  # debugging ergonomics only
        return (
            f"BufferSpec({self.name!r}, {self.shape}, "
            f"{self.dtype.name}, {self.nbytes}B)"
        )


def total_bytes(specs: list) -> int:
    return int(sum(s.nbytes for s in specs))


def buffers_dict(specs: list) -> dict:
    """JSON-facing ``{name: nbytes}`` map (insertion = carry order)."""
    return {s.name: s.nbytes for s in specs}


# -- per-engine analytic models ----------------------------------------------

def carry_specs(avals, shardings=None) -> list:
    """Per-buffer specs of the wavefront carry ``avals`` describes (a
    ``parallel/carry.Carry`` of shapes): the carry's own buffers in its
    own order under the ledger's names, so the analytic bytes reconcile
    EXACTLY against the live buffers' nbytes.  ``shardings`` is the mesh
    engine's placement of the same carry."""
    import jax

    from ..parallel.carry import ledger_name, leaf_names

    leaves = jax.tree.leaves(avals)
    placed = (
        [None] * len(leaves) if shardings is None
        else jax.tree.leaves(shardings)
    )
    return [
        BufferSpec(ledger_name(n), a.shape, a.dtype, sh)
        for n, a, sh in zip(leaf_names(avals), leaves, placed, strict=True)
    ]


def wavefront_specs(
    tensor, n_props: int, cap: int, qcap: int, batch: int,
    *, checked: bool = False, cartography: bool = False, por: bool = False,
    spill=None,
) -> list:
    """:func:`carry_specs` of the single-device carry at these capacities
    (``parallel/carry.carry_avals``, the prewarm-AOT contract).
    ``spill`` is the spill-tier config ``(bloom_bits, pend_cap)`` when
    the tier is armed: the Bloom filter and pending buffers are
    device-resident and count against the budget like any carry buffer
    (the HOST/DISK tier contents deliberately do not — they are what the
    budget is being traded against)."""
    from ..parallel.carry import carry_avals

    return carry_specs(carry_avals(
        tensor, n_props, cap, qcap, batch, checked, cartography, por,
        spill,
    ))


# -- live device readings ----------------------------------------------------


def device_memory_stats(device=None) -> Optional[dict]:
    """Live ``memory_stats()`` of ``device`` (default: the first JAX
    device), normalized to JSON-safe ints, or None when the backend does
    not expose them (CPU) — every consumer must degrade to the analytic
    path, never crash."""
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats()
    except Exception:  # noqa: BLE001 - absent/unsupported backend
        return None
    if not stats:
        return None
    out = {"platform": str(getattr(dev, "platform", "?"))}
    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_free_block_bytes"):
        v = stats.get(k)
        if v is not None:
            out[k] = int(v)
    return out


def device_budget(device=None) -> tuple:
    """``(bytes, src)`` for capacity planning: the env override
    ``STATERIGHT_TPU_DEVICE_BYTES`` wins (simulated budgets — also how
    CPU tests exercise the guard), then the live ``bytes_limit``; both
    absent ⇒ ``(None, None)`` and planners print the analytic table
    without a verdict."""
    env = os.environ.get(ENV_DEVICE_BYTES, "").strip()
    if env:
        try:
            return int(env), "env"
        except ValueError:
            pass
    stats = device_memory_stats(device)
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"]), "device"
    return None, None


def exec_memory(compiled) -> Optional[dict]:
    """``compiled.memory_analysis()`` normalized to JSON-safe ints —
    the temp/argument/output byte breakdown XLA computed at compile time
    — or None when the backend/executable does not expose it."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - not all runtimes implement it
        return None
    if ma is None:
        return None
    out = {}
    for attr, key in (
        ("temp_size_in_bytes", "temp_bytes"),
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            try:
                out[key] = int(v)
            except (TypeError, ValueError):
                continue
    return out or None


# -- growth-transient forecast + capacity plan -------------------------------


def next_rung_block(spec_fn: Callable, caps: dict) -> dict:
    """The analytic forecast for the NEXT table-doubling rung: steady
    bytes and the migration transient (old + new carry live across the
    growth swap).  An upper bound since PR 48: a growth on the device
    holds the old and new TABLE beside one queue, then the planes of the
    queue it slides; the spill tier and the mesh engine still hold this."""
    cur_total = total_bytes(spec_fn(caps))
    nxt = dict(caps)
    nxt["cap"] = int(caps["cap"]) * 2
    nxt_total = total_bytes(spec_fn(nxt))
    return {
        "capacity": int(nxt["cap"]),
        "total_bytes": nxt_total,
        "transient_bytes": cur_total + nxt_total,
    }


def capacity_plan(
    spec_fn: Callable, caps: dict, *, budget: Optional[int] = None,
    rungs: int = 24, spill: bool = False,
    spill_host_bytes: Optional[int] = None,
) -> dict:
    """The capacity ladder from ``caps`` upward: per rung, steady bytes,
    the migration transient (previous rung + this rung live), and —
    when a ``budget`` is known — whether it fits.  ``max_unique`` is the
    planning headline: the largest rung whose TRANSIENT fits holds at
    most ``capacity / 4`` unique states before the next (unfitting)
    migration, i.e. "on this device the run reaches ~N states before
    spilling".

    ``spill=True`` plans WITH the spill tier armed (docs/spill.md): the
    ladder still caps the HOT tier at the largest affordable rung, but
    ``max_unique`` no longer stops at HBM/4 — it extends by the host
    tier's reach (``spill_host_bytes`` / ``STATERIGHT_TPU_HOST_BYTES`` /
    half of physical RAM, at 16 bytes per spilled state) with the mmap'd
    disk tier unbounded behind it, reported in the ``spill`` block."""
    ladder = []
    cur = dict(caps)
    prev_total = None
    max_unique = None
    for _ in range(rungs):
        total = total_bytes(spec_fn(cur))
        transient = total if prev_total is None else prev_total + total
        fits = None if budget is None else transient <= budget
        ladder.append({
            "capacity": int(cur["cap"]),
            "total_bytes": total,
            "transient_bytes": transient,
            **({} if fits is None else {"fits": fits}),
        })
        if fits:
            max_unique = int(cur["cap"]) // GROWTH_LOAD_DENOM
        if fits is False:
            break
        prev_total = total
        cur = dict(cur)
        cur["cap"] = int(cur["cap"]) * 2
    out = {
        "v": MEMORY_V,
        "rungs": ladder,
        "budget_bytes": budget,
    }
    if max_unique is not None:
        out["max_unique"] = max_unique
    if spill and budget is not None and max_unique is not None:
        from ..spill.store import BYTES_PER_ENTRY, default_host_budget

        hb = (
            int(spill_host_bytes)
            if spill_host_bytes is not None
            else default_host_budget()
        )
        block: dict = {
            "hot_max_unique": max_unique,
            "bytes_per_spilled": BYTES_PER_ENTRY,
            "host_budget_bytes": hb,
            "disk": "unbounded (mmap tier; bounded by disk capacity)",
        }
        if hb:
            block["host_max_unique"] = hb // BYTES_PER_ENTRY
            out["max_unique"] = max_unique + block["host_max_unique"]
        out["spill"] = block
    return out


def fmt_bytes(n: Optional[int]) -> str:
    """Human bytes (``1.5GB``); '-' for unknown."""
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024
    return f"{n:.1f}TB"  # pragma: no cover - unreachable


# -- the ledger --------------------------------------------------------------


class MemoryLedger:
    """Host-side memory accounting for one engine run.

    ``spec_fn(caps) -> [BufferSpec]`` is the engine's analytic model;
    ``caps`` dicts must carry at least ``cap`` (table slots — the
    doubling edge the growth forecast walks).  The ledger recomputes the
    footprint only when the capacity rung changes, pushes every snapshot
    into the flight recorder (``rec.set_memory`` — which also feeds the
    health model's ``growth_oom_risk`` guard), and emits ``memory`` ring
    records at growth boundaries plus periodic watermark samples
    (``every`` host syncs; live ``peak_bytes_in_use`` is the watermark).
    Zero device ops — everything here is host arithmetic over shapes the
    engine already knows."""

    def __init__(
        self,
        engine: str,
        spec_fn: Callable,
        recorder=None,
        *,
        every: int = 0,
        extra: Optional[dict] = None,
    ) -> None:
        self.engine = engine
        self.spec_fn = spec_fn
        self.recorder = recorder
        self.every = int(every)
        # engine-shape annotations for the snapshot (queue_capacity /
        # devices), refreshed per observe
        self.extra = dict(extra or {})
        self._caps: Optional[dict] = None
        self._snap: Optional[dict] = None
        self._observes = 0
        self._exec: Optional[dict] = None
        budget, src = device_budget()
        self._budget, self._budget_src = budget, src

    # -- feeding -------------------------------------------------------------

    def attach_exec(self, compiled) -> Optional[dict]:
        """Record the latest executable's compile-time memory analysis
        (folded into the snapshot's ``exec`` block); returns the
        normalized dict for the caller to amend onto its ``compile``
        ring record."""
        mem = exec_memory(compiled)
        if mem is not None:
            self._exec = mem
            if self._snap is not None:
                self._snap = dict(self._snap)
                self._snap["exec"] = mem
                if self.recorder is not None:
                    self.recorder.set_memory(self._snap)
        return mem

    def observe(self, caps: dict, *, at: Optional[str] = None,
                extra: Optional[dict] = None) -> dict:
        """One host-sync observation.  Recomputes the analytic block when
        the capacity rung changed (emitting a ``memory`` ring record
        tagged ``growth`` unless ``at`` overrides), else emits only the
        periodic watermark sample when due.  Returns the live snapshot."""
        caps = dict(caps)
        if extra:
            self.extra.update(extra)
        self._observes += 1
        rung_changed = caps != self._caps
        if rung_changed:
            self._caps = caps
            self._snap = self._build_snapshot(caps)
            if self.recorder is not None:
                self.recorder.set_memory(self._snap)
        due = self.every and self._observes % self.every == 0
        if at is not None or rung_changed or due:
            tag = at
            if tag is None:
                tag = "growth" if self._observes > 1 else "init"
                if not rung_changed:
                    tag = f"sample{self._observes}"
            self._record(tag)
        return self._snap

    def finalize(self) -> Optional[dict]:
        """Close the memory time series with a ``final`` record (fresh
        live stats — the run's peak watermark)."""
        if self._caps is None:
            return None
        self._record("final")
        return self.snapshot()

    # -- reading -------------------------------------------------------------

    def snapshot(self) -> Optional[dict]:
        """The latest full block (analytic + live device fields), or
        None before the first observe."""
        return dict(self._snap) if self._snap else None

    def analytic_block(self) -> Optional[dict]:
        """The DETERMINISTIC subset for the run report: analytic bytes
        only — no live device stats, no machine-local budget (the report
        body must stay byte-stable across runs and machines)."""
        snap = self.snapshot()
        if snap is None:
            return None
        return {
            k: snap[k]
            for k in ("v", "engine", "capacity", "queue_capacity",
                      "devices", "buffers",
                      "total_bytes", "per_device_bytes", "next_rung")
            if k in snap
        }

    # -- internals -----------------------------------------------------------

    def _build_snapshot(self, caps: dict) -> dict:
        specs = self.spec_fn(caps)
        snap: dict = {
            "v": MEMORY_V,
            "engine": self.engine,
            "capacity": int(caps["cap"]),
            **self.extra,
            "buffers": buffers_dict(specs),
            "total_bytes": total_bytes(specs),
            "next_rung": next_rung_block(self.spec_fn, caps),
        }
        if self.extra.get("devices"):
            snap["per_device_bytes"] = int(
                sum(s.per_device_nbytes for s in specs)
            )
        if self._budget is not None:
            snap["budget_bytes"] = self._budget
            snap["budget_src"] = self._budget_src
        if self._exec is not None:
            snap["exec"] = self._exec
        return snap

    def _record(self, at: str) -> None:
        if self.recorder is None or self._snap is None:
            return
        rec_fields = {
            k: v for k, v in self._snap.items() if k != "v"
        }
        live = device_memory_stats()
        if live is not None:
            rec_fields["device"] = live
            # refresh the live view consumers poll (watch/Explorer)
            self._snap = dict(self._snap)
            self._snap["device"] = live
            self.recorder.set_memory(self._snap)
        self.recorder.record("memory", v=MEMORY_V, at=at, **rec_fields)


# -- preflight capacity guard ------------------------------------------------


def guard_mode() -> str:
    """``warn`` (default) | ``error`` | ``off`` from
    ``STATERIGHT_TPU_CAPACITY_GUARD``."""
    mode = os.environ.get(ENV_CAPACITY_GUARD, "").strip().lower()
    if mode in ("error", "raise"):
        return "error"
    if mode in ("0", "off", "skip"):
        return "off"
    return "warn"


class CapacityError(RuntimeError):
    """Raised by the preflight guard (``STATERIGHT_TPU_CAPACITY_GUARD=
    error``) when the requested capacities analytically exceed device
    memory — before any compile is paid."""


def preflight_guard(
    context: str, total: int, *, warn_once_obj=None
) -> None:
    """Warn (flag-gated error) when an analytic STEADY footprint exceeds
    the device budget — the requested capacities cannot even sit on the
    device.  (Whether future growth TRANSIENTS fit is a forecast, not a
    precondition — a space that fits the first rung may never grow — so
    that lives in the runtime ``growth_oom_risk`` signal and the
    ``capacity`` plan, not here.)  Silent when no budget is known (CPU)
    or the guard is off; ``warn_once_obj`` suppresses repeated prints
    per model (the audit-warning discipline)."""
    mode = guard_mode()
    if mode == "off":
        return
    budget, src = device_budget()
    if budget is None or total <= budget:
        return
    msg = (
        f"stateright-tpu: capacity guard: {context}: analytic "
        f"steady footprint {fmt_bytes(total)} exceeds the device budget "
        f"{fmt_bytes(budget)} ({src}); shrink capacity=/queue_capacity= "
        "or run the `capacity` verb for a plan (docs/telemetry.md)"
    )
    if mode == "error":
        raise CapacityError(msg)
    if warn_once_obj is not None:
        if getattr(warn_once_obj, "_capacity_warn_printed", False):
            return
        try:
            object.__setattr__(warn_once_obj, "_capacity_warn_printed", True)
        except Exception:  # noqa: BLE001 - __slots__ models
            pass
    print(msg, file=sys.stderr)


def snapshot_fits_guard(snap: dict, context: str) -> None:
    """Resume-time guard (rides ``_check_snapshot_sig``): the snapshot's
    recorded analytic footprint (``footprint_bytes``, written by the
    manifest satellite; summed array bytes for older snapshots) must fit
    the target device — warn/flag-gated-error BEFORE any compile."""
    mode = guard_mode()
    if mode == "off":
        return
    budget, src = device_budget()
    if budget is None:
        return
    total = snap.get("footprint_bytes")
    if total is None:
        # HOT TIER ONLY: spill_* manifest arrays are host-resident tier
        # contents (docs/spill.md) and never compete for device memory
        total = sum(
            int(v.nbytes) for k, v in snap.items()
            if isinstance(v, np.ndarray) and not str(k).startswith("spill_")
        )
    total = int(total)
    if total <= budget:
        return
    msg = (
        f"stateright-tpu: capacity guard: {context}: the resume "
        f"snapshot's footprint {fmt_bytes(total)} exceeds this device's "
        f"budget {fmt_bytes(budget)} ({src}) — the resumed run cannot "
        "hold the snapshot"
    )
    if mode == "error":
        raise CapacityError(msg)
    print(msg, file=sys.stderr)

"""Roofline view: the static cost ledger against the device's ceilings.

``analysis/costmodel.py`` produces the per-stage FLOPs/bytes ledger;
this module combines it with

 - a small **device-spec table** (peak scalar-op throughput + HBM
   bandwidth per known backend, overridable/simulatable with
   ``STATERIGHT_TPU_DEVICE_SPEC=PEAK_FLOPS:HBM_BYTES_PER_SEC``) to
   classify each pipeline stage **memory-bound vs compute-bound**: a
   stage whose arithmetic intensity (FLOPs per byte moved) sits below
   the ridge point ``peak_flops / hbm_bw`` cannot be compute-limited —
   more FLOPs per byte (the MXU recasts the JX4xx findings name) is the
   only way up;
 - the PR-4 **stage wall-clock attribution**
   (``FlightRecorder.stages()``) to estimate achieved bytes/s and
   FLOPs/s against those ceilings — the "achieved-vs-ceiling fraction"
   that answers "a bytes-moved roofline estimate per state, or a
   written proof the current rate is memory-bound".

On CPU (or any backend without a known spec) everything degrades to
arithmetic-intensity-only: intensities and verdict-free stage tables,
never a crash — pinned by test, the ``telemetry/memory.py``
degradation discipline.

Contract (the family's strongest form, pinned): the ledger is pure
host-side analysis over RE-TRACED kernels — roofline on or off leaves
the engine's step jaxpr bit-identical and the engine cache unkeyed.
Enabled via ``.telemetry(roofline=True)``; surfaces as
``checker.roofline()``, the run report's deterministic ``roofline``
block (static costs only — wall-clock ceilings render in the markdown
section), the Explorer's ``/.metrics`` + stage-roofline panel, the
``costmodel`` CLI verb, bench's ``tpu_*_roofline`` keys, and the
``regress.py --roofline`` gate.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

# roofline ring-record / block schema version
ROOFLINE_V = 1

ENV_DEVICE_SPEC = "STATERIGHT_TPU_DEVICE_SPEC"

# peak dense-compute FLOPs per device kind — TWO ceilings, because a
# stage is only entitled to the one its op mix can actually reach: the
# bf16 MXU peak (what the JX4xx dot recasts chase) and the scalar/VPU
# peak (what gather/scatter/elementwise pipelines top out at; a
# recast-free stage judged against the MXU ridge would look absurdly
# memory-bound, and a dot-recast stage judged against the VPU ridge
# would claim compute-bound with the MXU still idle — the two-peak
# split exists to stop both wrong verdicts).  MXU + HBM numbers are
# public datasheets; VPU peaks are order-of-magnitude estimates
# (vector lanes x clock), good enough for a ridge-side verdict.  The
# env override wins for anything unlisted or for what-if planning.
#
# (needle, name, mxu_peak_flops, vpu_peak_flops, hbm_bytes_per_sec)
DEVICE_SPECS = (
    ("v6 lite", "tpu-v6e", 918e12, 9.2e12, 1640e9),
    ("v6e", "tpu-v6e", 918e12, 9.2e12, 1640e9),
    ("v5 lite", "tpu-v5e", 197e12, 3.2e12, 819e9),
    ("v5e", "tpu-v5e", 197e12, 3.2e12, 819e9),
    ("v5p", "tpu-v5p", 459e12, 9e12, 2765e9),
    ("v4", "tpu-v4", 275e12, 4.3e12, 1228e9),
    ("v3", "tpu-v3", 123e12, 4e12, 900e9),
    ("v2", "tpu-v2", 45e12, 3e12, 700e9),
)

# a stage "is" dot-class when dot ops carry at least half its FLOPs:
# then the MXU ridge is the honest ceiling, else the VPU's
DOT_DOMINANCE = 0.5


def _spec_dict(name: str, mxu_peak: float, vpu_peak: float, bw: float,
               src: str) -> dict:
    """Normalized spec: both peaks, both ridges.  ``peak_flops``/
    ``ridge`` keep the pre-split meaning (the MXU ceiling) so stored
    artifacts and older consumers read unchanged."""
    return {
        "name": name,
        "peak_flops": mxu_peak,  # back-compat alias of mxu_peak
        "mxu_peak": mxu_peak,
        "vpu_peak": vpu_peak,
        "hbm_bytes_per_sec": bw,
        "ridge": mxu_peak / bw,  # back-compat alias of mxu_ridge
        "mxu_ridge": mxu_peak / bw,
        "vpu_ridge": vpu_peak / bw,
        "src": src,
    }


def device_spec(device=None) -> Optional[dict]:
    """``{name, mxu_peak, vpu_peak, hbm_bytes_per_sec, mxu_ridge,
    vpu_ridge, src}`` (plus the pre-split ``peak_flops``/``ridge``
    aliases of the MXU pair) for the first JAX device (or ``device``),
    the env override winning; None when nothing is known (CPU) —
    consumers degrade to arithmetic-intensity-only, never crash."""
    env = os.environ.get(ENV_DEVICE_SPEC, "").strip()
    if env:
        parts = env.split(":")
        try:
            peak, bw = float(parts[0]), float(parts[1])
            vpu = float(parts[3]) if len(parts) > 3 else peak / 64.0
            if peak > 0 and bw > 0 and vpu > 0:
                return _spec_dict(
                    parts[2] if len(parts) > 2 and parts[2]
                    else "env-override",
                    peak, vpu, bw, "env",
                )
        except (IndexError, ValueError):
            pass
        print(
            "stateright-tpu: roofline: ignoring malformed "
            f"{ENV_DEVICE_SPEC}={env!r} (want PEAK_FLOPS:HBM_BYTES_PER_SEC"
            "[:NAME[:VPU_PEAK_FLOPS]], e.g. 1.97e14:8.19e11:tpu-v5e:"
            "3.2e12; VPU peak defaults to PEAK/64)",
            file=sys.stderr,
        )
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        platform = str(getattr(dev, "platform", "")).lower()
        kind = str(getattr(dev, "device_kind", "")).lower()
    except Exception:  # noqa: BLE001 - no backend: no spec
        return None
    if platform != "tpu":
        return None
    for needle, name, peak, vpu, bw in DEVICE_SPECS:
        if needle in kind:
            return _spec_dict(name, peak, vpu, bw, "device")
    # an unlisted TPU gets no peaks rather than a neighbour's: say so
    print(
        f"stateright-tpu: roofline: no peak table entry for TPU kind "
        f"{kind!r}; achieved-vs-ceiling is omitted (add a DEVICE_SPECS "
        f"row or set {ENV_DEVICE_SPEC})",
        file=sys.stderr,
    )
    return None


def stage_dot_dominated(stage: dict) -> bool:
    """Does the stage's op mix earn the MXU ridge?  True when dot-class
    ops carry at least :data:`DOT_DOMINANCE` of its FLOPs (from the
    static block's per-class split) — the recast stages the JX4xx round
    produces.  A stage with no FLOPs at all is never dot-dominated."""
    classes = stage.get("classes") or {}
    dot = (classes.get("dot") or {}).get("flops") or 0
    total = stage.get("flops") or 0
    return total > 0 and dot / total >= DOT_DOMINANCE


def classify_stages(static: dict, spec: Optional[dict]) -> dict:
    """Per-stage roofline verdict from the static block's intensities:
    ``memory-bound`` below the stage's ridge point, ``compute-bound``
    above, ``unknown`` without a spec (CPU degradation) or without
    bytes.  Each stage is judged against the ridge its op mix can
    actually reach: the MXU ridge when dot-class ops dominate its FLOPs
    (the ``--mxu`` recasts), else the VPU ridge — one shared peak would
    hand a recast stage the wrong verdict (pinned with a synthetic
    dot-heavy stage in tests)."""
    out = {}
    for name, s in (static.get("stages") or {}).items():
        ai = s.get("intensity")
        dot = stage_dot_dominated(s)
        ridge = None
        if spec:
            ridge = (
                spec.get("mxu_ridge", spec.get("ridge"))
                if dot
                else spec.get("vpu_ridge", spec.get("ridge"))
            )
        if ai is None or ridge is None:
            verdict = "unknown"
        else:
            verdict = "memory-bound" if ai < ridge else "compute-bound"
        entry = {"intensity": ai, "verdict": verdict}
        if ridge is not None:
            entry["ridge"] = round(ridge, 3)
            entry["ridge_kind"] = "mxu" if dot else "vpu"
        out[name] = entry
    return out


def achieved_block(
    static: dict, spec: Optional[dict], stages_secs: Optional[dict],
    unique: int, batch: int,
) -> Optional[dict]:
    """Achieved-vs-ceiling estimate from the PR-4 wall-clock attribution:
    per-step analytic bytes/FLOPs x the estimated device-step count
    over the attributed device seconds.  The static costs price the
    whole step program (a mesh run's step is the same program, its
    ``batch`` rows spread over the devices).  An estimate by construction
    (growth replays and property-hit early exits shift it a few
    percent), which is why it lives in the live/markdown surfaces,
    never the deterministic report body."""
    if not stages_secs:
        return None
    dev_secs = stages_secs.get("device_secs")
    if not dev_secs or dev_secs <= 0 or batch <= 0 or unique <= 0:
        return None
    steps = max((int(unique) + int(batch) - 1) // int(batch), 1)
    totals = static.get("totals") or {}
    bts, fls = totals.get("bytes"), totals.get("flops")
    if not bts:
        return None
    out = {
        "device_secs": dev_secs,
        "est_device_steps": steps,
        "bytes_per_sec": round(bts * steps / dev_secs, 1),
        "flops_per_sec": round((fls or 0) * steps / dev_secs, 1),
    }
    if spec:
        out["frac_of_hbm_ceiling"] = round(
            out["bytes_per_sec"] / spec["hbm_bytes_per_sec"], 6
        )
        out["frac_of_flops_ceiling"] = round(
            out["flops_per_sec"] / spec["peak_flops"], 6
        )
    return out


class RooflineLedger:
    """Host-side roofline accounting for one engine run.

    ``cost_fn() -> CostReport | None`` is the engine's analytic model
    (``costmodel.wavefront_costs`` at the run's capacities, cached on
    the twin); ``engine`` names the engine that runs it in the block.  Built once at spawn — re-tracing
    the pipeline kernels plus one small XLA compile per stage for the
    reconciliation — and pushed into the flight recorder as the
    versioned ``roofline`` ring record + live snapshot.  Zero device
    ops, zero engine-program impact (pinned)."""

    def __init__(self, engine: str, cost_fn, recorder=None) -> None:
        self.engine = engine
        self.recorder = recorder
        self._report = None
        self._static: Optional[dict] = None
        self._recon: Optional[dict] = None
        self._spec = device_spec()
        try:
            self._report = cost_fn()
        except Exception:  # noqa: BLE001 - accounting must never break
            self._report = None  # a run (the memory-ledger discipline)
        if self._report is not None:
            self._static = {
                **self._report.static_block(), "engine": engine,
            }
            self._recon = self._report.recon_block()
            if recorder is not None:
                recorder.set_roofline(self.snapshot())
                recorder.record(
                    "roofline", v=ROOFLINE_V, at="init",
                    engine=self._static["engine"],
                    stages={
                        k: {
                            "flops": v["flops"],
                            "bytes": v["bytes_read"] + v["bytes_written"],
                        }
                        for k, v in self._static["stages"].items()
                    },
                    totals=dict(self._static["totals"]),
                    reconciled=bool(self._recon["ok"]),
                )

    @property
    def ok(self) -> bool:
        return self._static is not None

    def findings(self) -> list:
        """The JX4xx MXU-candidate findings (audit-report machinery)."""
        return list(self._report.findings) if self._report else []

    def static_block(self) -> Optional[dict]:
        """The DETERMINISTIC block for the run report: the analytic walk
        only — no XLA numbers, no device spec, no wall clock."""
        return dict(self._static) if self._static else None

    def snapshot(self) -> Optional[dict]:
        """The live block (Explorer/bench/watch): static + the
        reconciliation verdict + the resolved device spec + per-stage
        verdicts."""
        if self._static is None:
            return None
        out = dict(self._static)
        out["reconciliation"] = (
            dict(self._recon) if self._recon else None
        )
        if self._spec:
            out["device_spec"] = dict(self._spec)
        out["verdicts"] = classify_stages(self._static, self._spec)
        return out

    def live_block(self, stages_secs: Optional[dict], unique: int,
                   batch: Optional[int] = None) -> Optional[dict]:
        """snapshot() + the achieved-vs-ceiling estimate once wall-clock
        attribution exists (``checker.roofline()``'s default view).
        ``batch`` defaults to the static block's own (the engine's
        expansion width)."""
        snap = self.snapshot()
        if snap is None:
            return None
        if not batch:
            batch = int(self._static.get("batch", 0) or 0)
        ach = achieved_block(
            self._static, self._spec, stages_secs, unique, batch
        )
        if ach is not None:
            snap["achieved"] = ach
        return snap

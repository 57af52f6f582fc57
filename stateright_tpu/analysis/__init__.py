"""Preflight static analysis for device-checked models.

Four passes over a model *before* any device launch — the static
counterpart to the engines' runtime poison/growth diagnostics:

 - :mod:`.jaxpr_audit` — abstractly trace a ``TensorModel``'s
   ``step_rows``/``property_masks`` and walk the jaxpr for purity, dtype,
   shape-contract, and retrace-stability violations (plus a FLOPs/bytes
   perf preflight);
 - :mod:`.sanitizer` (over :mod:`.interval`) — value-level soundness:
   interval abstract interpretation proving gather/scatter indices stay on
   their axes (JX201/JX202) and packed fields inside their widths (JX203),
   with the ``checkify``-instrumented checked execution mode as the
   dynamic guard for what the domain can't decide;
 - :mod:`.handler_lint` — AST-lint actor handlers for nondeterminism and
   in-place mutation, and probe one bounded step of the tabulation
   closure for unbounded (ballot-style) field domains;
 - :mod:`.audit` — the driver: twin resolution, config-drift checks, and
   the per-model report cache;
 - :mod:`.costmodel` — the roofline cost ledger (docs/roofline.md):
   per-op FLOPs/bytes attribution of the engine pipeline, reconciled
   against XLA's ``cost_analysis()``, with the JX4xx MXU-candidate
   ranking (the ``costmodel`` verb and ``.telemetry(roofline=True)``).

Surfaces: ``model.checker().audit()`` (and the automatic ``spawn_tpu``
preflight — errors abort before launch, ``skip_audit()`` overrides),
``python -m stateright_tpu.models._cli audit`` over the example fleet,
and the Explorer's ``/.status``.  Rule catalogue: ``docs/analysis.md``.
"""

from .audit import audit_model, config_signature
from .costmodel import CostReport, wavefront_costs
from .footprint import extract_footprints
from .independence import (
    IndependenceReport,
    PorPlan,
    por_plan,
    run_independence,
)
from .report import AuditError, AuditFinding, AuditReport, Severity
from .sanitizer import (
    CheckedExecutionError,
    checkify_kernels,
    localize_checked_failure,
    run_sanitizer,
)

__all__ = [
    "AuditError",
    "AuditFinding",
    "AuditReport",
    "CheckedExecutionError",
    "CostReport",
    "IndependenceReport",
    "PorPlan",
    "Severity",
    "audit_model",
    "checkify_kernels",
    "config_signature",
    "extract_footprints",
    "localize_checked_failure",
    "por_plan",
    "run_independence",
    "run_sanitizer",
    "wavefront_costs",
]

"""stateright_tpu — a TPU-native explicit-state model checker.

A brand-new framework with the capabilities of the Stateright model checker
(reference mounted at ``/root/reference``; see ``SURVEY.md``), re-designed
TPU-first: states serialize to fixed-width ``uint64`` rows, frontier expansion
runs as a jit-compiled batched transition function, visited-set deduplication
and property evaluation run on-device, and multi-chip scaling places the
same program's table and queue over a ``jax.sharding.Mesh``, the compiler
inserting the collectives.

Layers (bottom-up, mirroring the reference's layer map in SURVEY.md §1):

 - :mod:`.fingerprint`, :mod:`.utils` — stable hashing + state containers.
 - :mod:`.core` — ``Model`` / ``Property`` abstraction.
 - :mod:`.checker` — CPU BFS/DFS oracle checkers, paths, visitors.
 - :mod:`.symmetry` — symmetry reduction (``Representative`` / ``RewritePlan``).
 - :mod:`.parallel` — the TPU wavefront engine (``spawn_tpu``).
 - :mod:`.ops` — device kernels: row hashing, dedup, hash tables.
 - :mod:`.actor` — actor DSL, network semantics, actor model, UDP runtime.
 - :mod:`.semantics` — linearizability / sequential consistency testers.
 - :mod:`.models` — example systems (2PC, Paxos, registers, counters).
 - :mod:`.explorer` — web UI for interactive state-space browsing.
 - :mod:`.checkpoint`, :mod:`.supervisor`, :mod:`.testing` — crash-safe
   autosave generations, supervised runs with retry/backoff, and the
   deterministic fault-injection layer (docs/robustness.md).
"""

from .core import Expectation, Model, Property
from .checker import (
    Checker,
    CheckerBuilder,
    Path,
    PathRecorder,
    StateRecorder,
)
from .fingerprint import fingerprint, stable_hash
from .analysis import AuditError, AuditFinding, AuditReport, audit_model
from .supervisor import supervise

__version__ = "0.1.0"

__all__ = [
    "Expectation",
    "Model",
    "Property",
    "Checker",
    "CheckerBuilder",
    "Path",
    "PathRecorder",
    "StateRecorder",
    "fingerprint",
    "stable_hash",
    "AuditError",
    "AuditFinding",
    "AuditReport",
    "audit_model",
    "supervise",
]

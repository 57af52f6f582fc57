"""The inside of the engine-acquisition seam, from the program's own spans.

A check on a model object of its own acquires every program again: the
first call of each traces it in Python, lowers it to MLIR and asks the
backend to compile or load it, all inside the call's ``dispatch`` span.
The program lays JAX's own monitoring events down as children of that
span (``telemetry/spans.py``): a ``program.lower`` per module lowered, a
``program.load`` per program that reached the backend (``hit``,
``retrieved_s``).  ``dispatch`` also says how many jaxprs it traced
(``jaxprs_traced``), and its SELF time — its duration minus what its
children cover, by ``parent_id`` — is the Python tracing plus the enqueue:
what no persistent cache saves.

A program that does not split the seam (no ``dispatch`` span, or one that
does not say what it traced) gives nothing to read; a check on resident
engines reads its enqueues and zeros.
"""

from __future__ import annotations

from typing import Optional

DISPATCH = "dispatch"
LOWER = "program.lower"
LOAD = "program.load"
# what a check's split holds, in seconds but for the count
KEYS = ("trace_s", "lower_s", "load_s", "retrieved_s", "programs")


def split(records: list) -> Optional[dict]:
    """One check's acquisition seam: ``trace_s`` (the self time of its
    ``dispatch`` spans), ``lower_s`` and ``load_s`` (its ``program.lower`` /
    ``program.load`` spans, whichever span paid for them), ``retrieved_s``
    (the cache retrievals inside the loads) and ``programs`` (how many
    programs reached the backend).  None where the seam is not split."""
    spans = [r for r in records if r.get("kind") == "span"]
    dispatches = {r["span_id"]: float(r["dur"]) for r in spans
                  if r["name"] == DISPATCH and "jaxprs_traced" in r}
    if not dispatches:
        return None
    covered = sum(float(r["dur"]) for r in spans
                  if r.get("parent_id") in dispatches)
    loads = [r for r in spans if r["name"] == LOAD]
    return {
        "trace_s": sum(dispatches.values()) - covered,
        "lower_s": sum(float(r["dur"]) for r in spans if r["name"] == LOWER),
        "load_s": sum(float(r["dur"]) for r in loads),
        "retrieved_s": sum(float(r.get("retrieved_s", 0.0)) for r in loads),
        "programs": float(len(loads)),
    }


def warmup(ctx: dict, key: str) -> Optional[float]:
    """``key`` of the warm-up check's split (it lies in ``setup_s``)."""
    got = split(ctx.get("warmup_records", []))
    return None if got is None else float(got[key])


def per_check(ctx: dict, key: str) -> Optional[float]:
    """``key`` of a WINDOW check's split, median over the window's checks.
    On resident engines that is what it is: no program loaded, nothing
    lowered, and the ``dispatch`` spans' self time the enqueues (the
    manifest lists these metrics for the ``cold`` loop only; an existing
    test of the harness lists them for a closed cell and needs a number).
    None where no window check splits the seam."""
    splits = [split(c.get("records", [])) for c in ctx.get("checks", [])]
    splits = [s for s in splits if s is not None]
    if not splits:
        return None
    return float(ctx["median"]([s[key] for s in splits]))

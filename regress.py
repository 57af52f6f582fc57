"""Perf-regression gate: a fresh bench run's summary vs the stored baseline.

A run that measured nothing on a TPU must never pass for a result, and a
slower run must never pass silently.  This gate makes both LOUD:

    python regress.py [RUN.json] [--baseline=BENCH_VALIDATED.json]
                      [--tolerance=0.85] [--allow-stale] [--sanitize]
                      [--stages] [--cartography] [--independence]
                      [--memory] [--spill] [--roofline] [--mxu]
                      [--sweep] [--fleet] [--mesh] [--diff] [--live]

``RUN.json`` (default ``docs/bench-last-details.json``) is a bench details
artifact — any JSON object with ``fresh`` and ``*_states_per_sec`` keys
(a driver ``BENCH_rNN.json`` whose ``parsed`` field holds the headline
object works too: the object is unwrapped).

Checks, in order:

 1. **Freshness** — ``fresh`` must be true: a run that landed no number
    on a TPU is not a measurement.  Exit 2 (unless ``--allow-stale``, for
    comparing two stored artifacts).  A run whose device phase ran on
    another backend (bench labels it ``platform: cpu`` and stores its
    keys as ``xlacpu_*``; CI smokes the harness that way) is compared
    with nothing, but the gates on its own blocks below still apply.
    A missing baseline file means "no baseline recorded yet": same rule.
 2. **Throughput** — every ``tpu_*_states_per_sec`` key present in BOTH
    the run and the baseline must reach ``tolerance`` × baseline
    (default 0.85).  Exit 1 on any miss.
 3. **Soundness** (``--sanitize``) — the example fleet must pass the
    interval/bounds sanitizer (``python -m stateright_tpu.models._cli
    sanitize``; docs/analysis.md JX2xx): a perf number measured by an
    engine whose kernels may silently clamp indices is not a
    measurement either.  Adds a ``sanitizer`` section to the verdict;
    an unclean fleet exits 1.  Opt-in because it imports and traces the
    whole fleet (~tens of seconds); the stale-artifact rules above are
    unchanged by it.

The verdict prints as one JSON line: ``{ok, fresh, regressed: [...],
improved: [...], checked: N[, sanitizer: {...}]}`` — ``regressed``
entries carry the config tag, both rates, and the ratio.  Exit 0 only
when fresh and clean.
"""

from __future__ import annotations

import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_RUN = os.path.join(_HERE, "docs", "bench-last-details.json")
DEFAULT_BASELINE = os.path.join(_HERE, "BENCH_VALIDATED.json")
DEFAULT_TOLERANCE = 0.85


def load_run(path: str) -> dict:
    """A bench summary object from a details file or a driver artifact
    (``{"parsed": {...}}`` wrappers are unwrapped)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object")
    return doc


def compare(run: dict, baseline: dict,
            tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Pure comparison (no I/O): the verdict dict described in the module
    docstring.  ``ok`` is freshness AND no regression."""
    regressed, improved, checked = [], [], 0
    for key, base in sorted(baseline.items()):
        if not key.endswith("_states_per_sec") or not key.startswith("tpu_"):
            continue
        cur = run.get(key)
        if cur is None or not base:
            continue
        checked += 1
        ratio = round(cur / base, 3)
        entry = {"config": key, "run": cur, "baseline": base, "ratio": ratio}
        if cur < tolerance * base:
            regressed.append(entry)
        elif cur > base:
            improved.append(entry)
    fresh = bool(run.get("fresh"))
    return {
        "ok": fresh and not regressed,
        "fresh": fresh,
        "tolerance": tolerance,
        "checked": checked,
        "regressed": regressed,
        "improved": improved,
    }


def sanitizer_verdict(fleet=None) -> dict:
    """Run the fleet soundness sanitizer and summarize for the verdict
    JSON.  ``fleet`` overrides the runner for tests (any callable
    returning the fleet exit code)."""
    import io

    if fleet is None:
        from stateright_tpu.models._cli import fleet_sanitize as fleet
    buf = io.StringIO()
    try:
        rc = fleet(stream=buf)
    except Exception as e:  # noqa: BLE001 - an import/trace crash is a
        # gate failure, not a gate skip
        return {"clean": False, "error": f"{type(e).__name__}: {e}"}
    tail = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    return {
        "clean": rc == 0,
        "verdict": tail[-1] if tail else "",
    }


def independence_verdict(run: dict, fleet=None) -> dict:
    """``--independence``: the static-independence section
    (docs/analysis.md JX3xx).

    Runs the fleet independence gate (every bundled example must produce
    a well-formed conflict matrix with no ERROR-level finding — the same
    contract as the CI verb), and, when the run artifact carries the
    flag-gated POR legs, checks them: ``tpu_paxos3_por`` must be a
    well-formed dict with an ``enabled`` bool plus matching unique
    counts when both legs ran (the slot-multiset paxos twin must never
    reduce — all-dependent matrix), and ``tpu_paxos2_por_channel`` (the
    per-channel reduction leg) must carry ``encoding == "per-channel"``
    and a ``reduction_ratio`` in ``(0, 1]`` consistent with its
    unique/full_unique counts.  Stale/pre-POR/pre-channel baselines
    never gate (the ``--sanitize``/``--cartography`` rule); ``fleet``
    overrides the runner for tests."""
    import io

    if fleet is None:
        from stateright_tpu.models._cli import fleet_independence as fleet
    buf = io.StringIO()
    try:
        rc = fleet(stream=buf)
    except Exception as e:  # noqa: BLE001 - an import/trace crash is a
        # gate failure, not a gate skip
        return {"clean": False, "error": f"{type(e).__name__}: {e}"}
    tail = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    out = {"clean": rc == 0, "verdict": tail[-1] if tail else ""}
    leg_error = run.get("tpu_paxos3_por_error")
    if leg_error:
        # a POR leg that crashed is a gate failure, not a gate skip —
        # the same discipline as the fleet-runner crash above
        out["clean"] = False
        out["por_leg"] = {"ok": False, "problems": [f"leg crashed: {leg_error}"]}
        return out
    leg = run.get("tpu_paxos3_por")
    if leg is not None:
        problems = []
        if not isinstance(leg, dict) or "enabled" not in leg:
            problems.append("tpu_paxos3_por block malformed")
        u_por = run.get("tpu_paxos3_por_unique")
        u_full = run.get("tpu_paxos3_unique")
        if (
            isinstance(u_por, int) and isinstance(u_full, int)
            and u_por != u_full
        ):
            problems.append(
                f"por unique {u_por} != full unique {u_full} "
                "(paxos must not reduce: all-dependent matrix)"
            )
        out["por_leg"] = {"ok": not problems}
        if problems:
            out["clean"] = False
            out["por_leg"]["problems"] = problems
    # the per-channel reduction leg (BENCH_POR=1; bench.py): well-formed
    # block + ratio sanity.  Stale/pre-channel artifacts carry neither
    # the block nor the error key and never trip; a crashed leg fails.
    ch_error = run.get("tpu_paxos2_por_channel_error")
    if ch_error:
        out["clean"] = False
        out["por_channel_leg"] = {
            "ok": False, "problems": [f"leg crashed: {ch_error}"],
        }
        return out
    ch = run.get("tpu_paxos2_por_channel")
    if ch is not None:
        problems = []
        if not isinstance(ch, dict) or "enabled" not in ch:
            problems.append("tpu_paxos2_por_channel block malformed")
        elif ch.get("encoding") != "per-channel":
            problems.append(
                f"per-channel leg ran encoding {ch.get('encoding')!r}"
            )
        u_por = run.get("tpu_paxos2_por_channel_unique")
        u_full = run.get("tpu_paxos2_por_channel_full_unique")
        ratio = run.get("tpu_paxos2_por_channel_reduction_ratio")
        if not (isinstance(u_por, int) and isinstance(u_full, int)
                and u_full > 0):
            problems.append("per-channel unique/full_unique missing")
        else:
            if u_por > u_full:
                problems.append(
                    f"reduced unique {u_por} EXCEEDS full {u_full} — a "
                    "reduction can only shrink the explored space"
                )
            if not (
                isinstance(ratio, (int, float)) and 0 < ratio <= 1
                and abs(ratio - u_por / u_full) < 1e-3
            ):
                problems.append(
                    f"reduction_ratio {ratio!r} out of (0, 1] or "
                    f"inconsistent with {u_por}/{u_full}"
                )
        out["por_channel_leg"] = {"ok": not problems}
        if ratio is not None:
            out["por_channel_leg"]["reduction_ratio"] = ratio
        if problems:
            out["clean"] = False
            out["por_channel_leg"]["problems"] = problems
    return out


def cartography_verdict(run: dict, baseline: dict) -> dict:
    """``--cartography``: the search-cartography section
    (docs/telemetry.md).

    A FRESH run must carry a WELL-FORMED ``tpu_paxos3_cartography`` block
    — versioned, with non-empty depth/action histograms whose totals
    reconcile against the run's own headline counters when those are
    present (``sum(depth_hist) == fresh_inserts`` and, when the run
    carries ``tpu_paxos3_unique``, ``fresh_inserts`` equals it).  The
    baseline's block is attached for comparison when present but NEVER
    gates: stored baselines predating the cartography round have none,
    and stale artifacts must not trip a fresh run (exactly the
    ``--stages`` rule)."""
    cart = run.get("tpu_paxos3_cartography")
    out: dict = {"present": bool(cart)}
    problems = []
    if not cart:
        problems.append("run carries no tpu_paxos3_cartography block")
    else:
        if not isinstance(cart.get("v"), int):
            problems.append("missing schema version v")
        depth = cart.get("depth_hist") or []
        actions = cart.get("action_hist") or []
        if not depth or not all(
            isinstance(x, int) and x >= 0 for x in depth
        ):
            problems.append("depth_hist empty or malformed")
        if not actions or not all(
            isinstance(x, int) and x >= 0 for x in actions
        ):
            problems.append("action_hist empty or malformed")
        fresh = cart.get("fresh_inserts")
        if not isinstance(fresh, int):
            problems.append("missing fresh_inserts")
        elif depth and sum(depth) != fresh:
            problems.append(
                f"sum(depth_hist)={sum(depth)} != fresh_inserts={fresh}"
            )
        unique = run.get("tpu_paxos3_unique")
        if isinstance(fresh, int) and unique is not None and fresh != unique:
            problems.append(
                f"fresh_inserts={fresh} != tpu_paxos3_unique={unique}"
            )
        out["summary"] = {
            "v": cart.get("v"),
            "depth_bins": len(depth),
            "actions": len(actions),
            "fresh_inserts": fresh,
            "duplicate_hits": cart.get("duplicate_hits"),
        }
    out["ok"] = not problems
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_paxos3_cartography"))
    return out


def memory_verdict(run: dict, baseline: dict) -> dict:
    """``--memory``: the HBM-ledger section (docs/telemetry.md "Memory
    ledger").

    A FRESH run must carry a WELL-FORMED ``tpu_paxos3_memory`` block —
    versioned, with a non-empty per-buffer byte map whose sum reconciles
    exactly against ``total_bytes``, and a growth forecast whose
    migration transient is at least the steady footprint (old + new
    carry live).  A perf number without its memory story cannot drive
    the billion-state capacity tier.  The baseline's block is attached
    for comparison when present but NEVER gates: stored baselines
    predating the memory round have none, and stale artifacts must not
    trip a fresh run (exactly the ``--stages``/``--cartography`` rule)."""
    mem = run.get("tpu_paxos3_memory")
    out: dict = {"present": bool(mem)}
    problems = []
    if not mem:
        problems.append("run carries no tpu_paxos3_memory block")
    else:
        if not isinstance(mem.get("v"), int):
            problems.append("missing schema version v")
        buffers = mem.get("buffers")
        total = mem.get("total_bytes")
        if not isinstance(buffers, dict) or not buffers:
            problems.append("buffers map empty or malformed")
        elif not all(
            isinstance(v, int) and v >= 0 for v in buffers.values()
        ):
            problems.append("buffers map carries negative/non-int bytes")
        if not isinstance(total, int) or total <= 0:
            problems.append("missing/non-positive total_bytes")
        elif isinstance(buffers, dict) and buffers:
            # int-only sum here AND in the message: a mixed-type map
            # (already flagged above) must yield a verdict, not a
            # TypeError from the f-string's unfiltered sum
            bsum = sum(
                v for v in buffers.values() if isinstance(v, int)
            )
            if bsum != total:
                problems.append(
                    f"sum(buffers)={bsum} != total_bytes={total}"
                )
        nxt = mem.get("next_rung")
        if not isinstance(nxt, dict):
            problems.append("missing next_rung forecast")
        else:
            tb, trans = nxt.get("total_bytes"), nxt.get("transient_bytes")
            if not isinstance(tb, int) or not isinstance(trans, int):
                problems.append("next_rung bytes malformed")
            elif isinstance(total, int) and trans < max(tb, total):
                problems.append(
                    f"next_rung transient {trans} below steady bytes "
                    "(migration holds old+new carry live)"
                )
        out["summary"] = {
            "v": mem.get("v"),
            "total_bytes": total,
            "buffers": len(buffers) if isinstance(buffers, dict) else 0,
            "next_transient_bytes": (
                (mem.get("next_rung") or {}).get("transient_bytes")
            ),
        }
    out["ok"] = not problems
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_paxos3_memory"))
    return out


def spill_verdict(run: dict, baseline: dict) -> dict:
    """``--spill``: the spill-tier section (docs/spill.md).

    The spill leg is FLAG-gated (``BENCH_SPILL=1``), so absence never
    trips — stale artifacts and pre-spill baselines pass untouched (the
    POR-leg rule).  When the run carries one, it must be WELL-FORMED: a
    versioned block with non-negative integer tier bytes and tallies,
    at least one eviction (the leg's budget exists to force one), and —
    when the unconstrained leg also ran — bit-identical unique counts
    (the tier's core contract).  A crashed leg
    (``tpu_2pc7_spill_error``) is a gate failure, not a skip."""
    out: dict = {}
    leg_error = run.get("tpu_2pc7_spill_error")
    if leg_error:
        out["present"] = False
        out["ok"] = False
        out["problems"] = [f"leg crashed: {leg_error}"]
        return out
    leg = run.get("tpu_2pc7_spill")
    out["present"] = bool(leg)
    if leg is None:
        out["ok"] = True  # flag-gated: absence is not a failure
        out["baseline_present"] = bool(baseline.get("tpu_2pc7_spill"))
        return out
    problems = []
    if not isinstance(leg, dict) or not isinstance(leg.get("v"), int):
        problems.append("tpu_2pc7_spill block malformed (missing v)")
    else:
        for k in ("evictions", "spilled_fps", "host_bytes", "disk_bytes",
                  "resolved_dups", "resolved_novel"):
            v = leg.get(k)
            if not isinstance(v, int) or v < 0:
                problems.append(f"tpu_2pc7_spill.{k} missing/negative")
        if isinstance(leg.get("evictions"), int) and leg["evictions"] < 1:
            problems.append(
                "spill leg ran without a single eviction — the simulated "
                "budget did not constrain the run"
            )
    u_sp = run.get("tpu_2pc7_spill_unique")
    u_full = run.get("tpu_2pc7_unique")
    if isinstance(u_sp, int) and isinstance(u_full, int) and u_sp != u_full:
        problems.append(
            f"spill unique {u_sp} != unconstrained unique {u_full} "
            "(the tier must not change counts)"
        )
    out["ok"] = not problems
    if problems:
        out["problems"] = problems
    out["summary"] = {
        "evictions": leg.get("evictions") if isinstance(leg, dict) else None,
        "spilled_fps": (
            leg.get("spilled_fps") if isinstance(leg, dict) else None
        ),
        "host_bytes": leg.get("host_bytes") if isinstance(leg, dict) else None,
        "disk_bytes": leg.get("disk_bytes") if isinstance(leg, dict) else None,
    }
    out["baseline_present"] = bool(baseline.get("tpu_2pc7_spill"))
    return out


def roofline_verdict(run: dict, baseline: dict) -> dict:
    """``--roofline``: the roofline cost-ledger section
    (docs/roofline.md).

    A FRESH run must carry a WELL-FORMED ``tpu_paxos3_roofline`` block —
    versioned, with a non-empty per-stage map of non-negative integer
    FLOPs/bytes whose sums reconcile against the block's own totals, and
    an XLA-reconciliation verdict that PASSED (``reconciliation.ok``):
    a perf number whose cost model disagrees with XLA's own analysis
    cannot drive the MXU round.  The baseline's block is attached for
    comparison when present but NEVER gates: stored baselines predating
    the roofline round have none, and stale artifacts must not trip a
    fresh run (the ``--stages``/``--cartography``/``--memory`` rule)."""
    roof = run.get("tpu_paxos3_roofline")
    out: dict = {"present": bool(roof)}
    problems = []
    if not roof:
        problems.append("run carries no tpu_paxos3_roofline block")
    else:
        if not isinstance(roof.get("v"), int):
            problems.append("missing schema version v")
        stages = roof.get("stages")
        totals = roof.get("totals")
        if not isinstance(stages, dict) or not stages:
            problems.append("stages map empty or malformed")
        else:
            fl_sum = by_sum = 0
            for name, s in stages.items():
                if not isinstance(s, dict):
                    problems.append(f"stage {name} malformed")
                    continue
                for k in ("flops", "bytes_read", "bytes_written"):
                    v = s.get(k)
                    if not isinstance(v, int) or v < 0:
                        problems.append(f"stage {name}.{k} missing/negative")
                fl_sum += s.get("flops") or 0
                by_sum += (s.get("bytes_read") or 0) + (
                    s.get("bytes_written") or 0
                )
            if isinstance(totals, dict):
                if totals.get("flops") != fl_sum:
                    problems.append(
                        f"sum(stage flops)={fl_sum} != totals.flops="
                        f"{totals.get('flops')}"
                    )
                if totals.get("bytes") != by_sum:
                    problems.append(
                        f"sum(stage bytes)={by_sum} != totals.bytes="
                        f"{totals.get('bytes')}"
                    )
            else:
                problems.append("missing totals block")
        recon = roof.get("reconciliation")
        if not isinstance(recon, dict):
            problems.append("missing XLA reconciliation block")
        elif not recon.get("ok"):
            problems.append(
                "XLA reconciliation FAILED (analytic totals outside the "
                "pinned tolerance bands)"
            )
        out["summary"] = {
            "v": roof.get("v"),
            "stages": sorted(stages) if isinstance(stages, dict) else [],
            "totals": totals if isinstance(totals, dict) else None,
            "reconciled": bool(
                isinstance(recon, dict) and recon.get("ok")
            ),
            "mxu_candidates": len(roof.get("mxu_candidates") or []),
        }
    out["ok"] = not problems
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_paxos3_roofline"))
    return out


# the --mxu payoff bar (ISSUE 14 acceptance): with coalescing on,
# paxos-3's expand charged bytes must drop by at least this fraction vs
# the same run's unflagged ledger (33.2% at the bench's capacities).  The
# expand stage alone: the queue stage is one program on both sides, a
# constant that would only dilute what the flag changes
MXU_EXPAND_DROP = 0.30


def _stage_of(roof, name: str):
    """One stage dict of a roofline block (None when the block, its
    stages map, or the stage is missing or malformed — injected
    artifacts are arbitrary JSON, so every level is checked)."""
    if not isinstance(roof, dict):
        return None
    stages = roof.get("stages")
    st = stages.get(name) if isinstance(stages, dict) else None
    return st if isinstance(st, dict) else None


def _stage_bytes(roof: dict, name: str):
    """Charged bytes of one stage of a roofline block (None when the
    block/stage is missing or malformed)."""
    st = _stage_of(roof, name)
    if st is None:
        return None
    br, bw = st.get("bytes_read"), st.get("bytes_written")
    if not isinstance(br, int) or not isinstance(bw, int):
        return None
    return br + bw


def mxu_verdict(run: dict, baseline: dict) -> dict:
    """``--mxu``: the MXU-recast legs (docs/roofline.md "Executing the
    hot-spot list").

    The legs are FLAG-gated (``BENCH_MXU=1``), so absence never trips —
    stale artifacts and pre-mxu baselines pass untouched (the spill-leg
    rule; unit-tested with injected artifacts).  When a fresh run
    carries them, the round's acceptance bars apply:

     - a crashed leg (``tpu_paxos3_mxu_error``/``tpu_2pc7_mxu_error``)
       is a gate failure, not a skip;
     - count parity: ``tpu_paxos3_mxu_unique == tpu_paxos3_unique`` and
       ``tpu_2pc7_mxu_unique == tpu_2pc7_unique`` whenever both sides
       exist (a recast that changes counts is not a recast);
     - measured payoff, against the SAME RUN's unflagged roofline
       blocks: paxos-3's expand charged bytes/step must drop by
       >= ``MXU_EXPAND_DROP`` under the flag, and 2pc-7's flagged
       dedup-insert stage must carry a dot-class op with raised
       arithmetic intensity (the BLEST probe actually landed on the
       MXU's op class).
    """
    out: dict = {}
    problems = []
    present = False
    for leg in ("tpu_paxos3_mxu", "tpu_2pc7_mxu"):
        err = run.get(f"{leg}_error")
        if err:
            present = True
            problems.append(f"leg crashed: {leg}: {err}")
    # count parity whenever both sides exist
    for flagged, plain in (
        ("tpu_paxos3_mxu_unique", "tpu_paxos3_unique"),
        ("tpu_2pc7_mxu_unique", "tpu_2pc7_unique"),
    ):
        u_m, u_p = run.get(flagged), run.get(plain)
        if isinstance(u_m, int):
            present = True
            if isinstance(u_p, int) and u_m != u_p:
                problems.append(
                    f"{flagged}={u_m} != {plain}={u_p} (the recasts must "
                    "not change counts)"
                )
    # paxos-3 bytes-moved payoff vs the same-run unflagged block
    roof_m = run.get("tpu_paxos3_mxu_roofline")
    if roof_m is not None:
        present = True
        roof_p = run.get("tpu_paxos3_roofline")
        after = _stage_bytes(roof_m, "expand")
        before = _stage_bytes(roof_p, "expand") if roof_p else None
        if after is None:
            problems.append(
                "tpu_paxos3_mxu_roofline expand stage malformed"
            )
        elif before is None:
            problems.append(
                "no same-run unflagged tpu_paxos3_roofline to compare "
                "the flagged ledger against"
            )
        else:
            drop = 1.0 - after / before if before else 0.0
            out["paxos3_expand_bytes"] = {
                "unflagged": before, "mxu": after,
                "drop": round(drop, 4),
            }
            if drop < MXU_EXPAND_DROP:
                problems.append(
                    f"paxos-3 expand charged bytes dropped only "
                    f"{drop:.1%} under --mxu (< "
                    f"{MXU_EXPAND_DROP:.0%} bar): coalescing "
                    "did not execute the hot-spot list"
                )
    # 2pc-7 probe payoff: a genuine dot-class dedup-insert op
    roof7_m = run.get("tpu_2pc7_mxu_roofline")
    if roof7_m is not None:
        present = True
        st = _stage_of(roof7_m, "dedup-insert") or {}
        classes = st.get("classes")
        dot = classes.get("dot") if isinstance(classes, dict) else None
        dot = dot if isinstance(dot, dict) else {}
        if not isinstance(dot.get("flops"), int) or dot["flops"] <= 0:
            problems.append(
                "tpu_2pc7_mxu_roofline dedup-insert carries no dot-class "
                "op (the BLEST probe did not land)"
            )
        else:
            out["tpu_2pc7_dedup_dot_flops"] = dot["flops"]
            ai_m = st.get("intensity")
            ai_p = (
                _stage_of(run.get("tpu_2pc7_roofline"), "dedup-insert")
                or {}
            ).get("intensity")
            if (
                isinstance(ai_m, (int, float))
                and isinstance(ai_p, (int, float))
                and not ai_m > ai_p
            ):
                problems.append(
                    f"dedup-insert arithmetic intensity did not rise "
                    f"under --mxu ({ai_p} -> {ai_m})"
                )
            elif isinstance(ai_m, (int, float)):
                out["tpu_2pc7_dedup_intensity"] = {
                    "unflagged": ai_p, "mxu": ai_m,
                }
    out["present"] = present
    out["ok"] = not problems  # flag-gated: absence is not a failure
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(
        baseline.get("tpu_paxos3_mxu_roofline")
        or baseline.get("tpu_paxos3_mxu_unique")
    )
    return out


def sweep_verdict(run: dict, baseline: dict) -> dict:
    """``--sweep``: the hyper-batched instance-sweep leg (docs/sweep.md).

    The leg is FLAG-gated (``BENCH_SWEEP=1``), so absence never trips —
    stale artifacts and pre-sweep baselines pass untouched (the
    spill/mxu rule; unit-tested with injected artifacts).  When a fresh
    run carries it:

     - a crashed leg (``tpu_sweep_error``) is a gate failure, not a
       skip;
     - the block must be WELL-FORMED: positive instance/cohort/compile
       counts, a per-instance map whose uniques are positive ints;
     - count parity must have held (``parity == "IDENTICAL"`` — the leg
       asserts per-instance unique/total equality against sequential
       oracle runs of the same family);
     - the amortization must be real: ``engine_compiles`` must equal
       ``cohorts`` (one compiled program per shape cohort; the leg
       pre-sizes, so growth recompiles indicate a broken sizing) and be
       STRICTLY below ``sequential_engine_compiles`` whenever the sweep
       spans fewer cohorts than instances.
    """
    out: dict = {}
    problems = []
    err = run.get("tpu_sweep_error")
    blk = run.get("tpu_sweep")
    present = bool(err) or blk is not None
    if err:
        problems.append(f"leg crashed: tpu_sweep: {err}")
    if blk is not None and not isinstance(blk, dict):
        problems.append("tpu_sweep block is not an object")
        blk = None
    if isinstance(blk, dict):
        ints = {}
        for k in ("instances", "cohorts", "engine_compiles",
                  "sequential_engine_compiles"):
            v = blk.get(k)
            if not isinstance(v, int) or v <= 0:
                problems.append(f"tpu_sweep.{k} missing/malformed: {v!r}")
            else:
                ints[k] = v
        per = blk.get("per_instance")
        if not isinstance(per, dict) or not per:
            problems.append("tpu_sweep.per_instance missing/empty")
        else:
            bad = sorted(
                k for k, v in per.items()
                if not isinstance(v, dict)
                or not isinstance(v.get("unique"), int)
                or v["unique"] <= 0
            )
            if bad:
                problems.append(
                    f"tpu_sweep.per_instance malformed for {bad}"
                )
        if blk.get("parity") != "IDENTICAL":
            problems.append(
                f"tpu_sweep.parity={blk.get('parity')!r} (per-instance "
                "counts must reconcile IDENTICAL against the sequential "
                "oracles)"
            )
        if {"instances", "cohorts", "engine_compiles",
                "sequential_engine_compiles"} <= set(ints):
            out["amortization"] = {
                "cohorts": ints["cohorts"],
                "engine_compiles": ints["engine_compiles"],
                "sequential": ints["sequential_engine_compiles"],
            }
            if ints["engine_compiles"] != ints["cohorts"]:
                problems.append(
                    f"tpu_sweep.engine_compiles={ints['engine_compiles']}"
                    f" != cohorts={ints['cohorts']} (one compiled "
                    "program per shape cohort is the contract; growth "
                    "recompiles mean the leg's pre-sizing broke)"
                )
            if (
                ints["cohorts"] < ints["instances"]
                and not ints["engine_compiles"]
                < ints["sequential_engine_compiles"]
            ):
                problems.append(
                    "sweep paid as many engine compiles as the "
                    "sequential runs — no amortization"
                )
    out["present"] = present
    out["ok"] = not problems  # flag-gated: absence is not a failure
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_sweep"))
    return out


def fleet_verdict(run: dict, baseline: dict) -> dict:
    """``--fleet``: the multi-tenant fleet-scheduler leg (docs/fleet.md).

    The leg is FLAG-gated (``BENCH_FLEET=1``), so absence never trips —
    stale artifacts and pre-fleet baselines pass untouched (the
    spill/mxu/sweep rule; unit-tested with injected artifacts).  When a
    fresh run carries it:

     - a crashed leg (``tpu_fleet_error``) is a gate failure, not a
       skip;
     - the block must be WELL-FORMED: positive job/slot/compile counts
       and a non-negative preemption count;
     - every job must have completed (``completed == jobs`` — a refused
       or failed tenant voids the serving measurement);
     - count parity must have held (``parity == "IDENTICAL"`` — the leg
       asserts per-job unique/total equality against solo oracle runs);
     - when any jobs were cohort-packed, the amortization must be real:
       ``engine_compiles`` STRICTLY below ``sequential_engine_compiles``.
    """
    out: dict = {}
    problems = []
    err = run.get("tpu_fleet_error")
    blk = run.get("tpu_fleet")
    present = bool(err) or blk is not None
    if err:
        problems.append(f"leg crashed: tpu_fleet: {err}")
    if blk is not None and not isinstance(blk, dict):
        problems.append("tpu_fleet block is not an object")
        blk = None
    if isinstance(blk, dict):
        ints = {}
        for k in ("jobs", "slots", "completed", "engine_compiles",
                  "sequential_engine_compiles"):
            v = blk.get(k)
            if not isinstance(v, int) or v <= 0:
                problems.append(f"tpu_fleet.{k} missing/malformed: {v!r}")
            else:
                ints[k] = v
        pre = blk.get("preemptions")
        if not isinstance(pre, int) or pre < 0:
            problems.append(
                f"tpu_fleet.preemptions missing/malformed: {pre!r}"
            )
        if (
            "jobs" in ints and "completed" in ints
            and ints["completed"] != ints["jobs"]
        ):
            problems.append(
                f"tpu_fleet.completed={ints['completed']} != "
                f"jobs={ints['jobs']} (a refused or failed tenant "
                "voids the serving measurement)"
            )
        if blk.get("parity") != "IDENTICAL":
            problems.append(
                f"tpu_fleet.parity={blk.get('parity')!r} (per-job "
                "counts must reconcile IDENTICAL against the solo "
                "oracles)"
            )
        packed = blk.get("packed")
        if not isinstance(packed, int) or packed < 0:
            problems.append(
                f"tpu_fleet.packed missing/malformed: {packed!r}"
            )
        elif (
            packed > 1
            and {"engine_compiles",
                 "sequential_engine_compiles"} <= set(ints)
        ):
            out["amortization"] = {
                "packed": packed,
                "engine_compiles": ints["engine_compiles"],
                "sequential": ints["sequential_engine_compiles"],
            }
            if not ints["engine_compiles"] \
                    < ints["sequential_engine_compiles"]:
                problems.append(
                    "fleet paid as many engine compiles as the solo "
                    "runs despite packed cohorts — no amortization"
                )
    out["present"] = present
    out["ok"] = not problems  # flag-gated: absence is not a failure
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_fleet"))
    return out


def mesh_verdict(run: dict, baseline: dict) -> dict:
    """``--mesh``: the GSPMD mesh-engine leg (docs/mesh.md).

    The leg is FLAG-gated (``BENCH_MESH=1``), so absence never trips —
    stale artifacts and pre-mesh baselines pass untouched (the
    spill/mxu/sweep/fleet rule; unit-tested with injected artifacts).
    When a fresh run carries it:

     - a crashed leg (``tpu_mesh_error``) is a gate failure, not a
       skip;
     - the block must be WELL-FORMED: positive device/unique/state
       counts with ``states >= unique``;
     - count parity must have held (``parity == "IDENTICAL"`` — the leg
       asserts unique/total equality against a solo single-device
       wavefront oracle of the same model; a partitioning that drifts
       cannot report a win);
     - the imbalance readout must be sound: ``shard_load`` is a
       per-device vector of non-negative ints summing to ``unique``
       (the partition rules place every visited row on exactly one
       shard owner) and ``routed_states`` is an int strictly below
       ``unique`` (init states appear in the load but route nowhere).
    """
    out: dict = {}
    problems = []
    err = run.get("tpu_mesh_error")
    blk = run.get("tpu_mesh")
    present = bool(err) or blk is not None
    if err:
        problems.append(f"leg crashed: tpu_mesh: {err}")
    if blk is not None and not isinstance(blk, dict):
        problems.append("tpu_mesh block is not an object")
        blk = None
    if isinstance(blk, dict):
        ints = {}
        for k in ("devices", "unique", "states"):
            v = blk.get(k)
            if not isinstance(v, int) or v <= 0:
                problems.append(f"tpu_mesh.{k} missing/malformed: {v!r}")
            else:
                ints[k] = v
        if (
            {"unique", "states"} <= set(ints)
            and ints["states"] < ints["unique"]
        ):
            problems.append(
                f"tpu_mesh.states={ints['states']} < "
                f"unique={ints['unique']} (total visits bound uniques)"
            )
        if blk.get("parity") != "IDENTICAL":
            problems.append(
                f"tpu_mesh.parity={blk.get('parity')!r} (mesh counts "
                "must reconcile IDENTICAL against the solo wavefront "
                "oracle)"
            )
        load = blk.get("shard_load")
        if (
            not isinstance(load, list)
            or not load
            or any(not isinstance(v, int) or v < 0 for v in load)
            or ("devices" in ints and len(load) != ints["devices"])
        ):
            problems.append(
                f"tpu_mesh.shard_load missing/malformed: {load!r} "
                "(one non-negative entry per mesh device)"
            )
        elif "unique" in ints and sum(load) != ints["unique"]:
            problems.append(
                f"tpu_mesh.shard_load sums to {sum(load)} != "
                f"unique={ints['unique']} (every visited row has exactly "
                "one shard owner)"
            )
        else:
            out["shard_load"] = load
            imb = blk.get("imbalance")
            ratio = imb.get("ratio") if isinstance(imb, dict) else None
            if isinstance(ratio, (int, float)):
                out["imbalance_ratio"] = ratio
        routed = blk.get("routed_states")
        if not isinstance(routed, int) or routed < 0 or (
            "unique" in ints and routed >= ints["unique"]
        ):
            problems.append(
                f"tpu_mesh.routed_states missing/malformed: {routed!r} "
                "(init states route nowhere, so routed < unique)"
            )
    out["present"] = present
    out["ok"] = not problems  # flag-gated: absence is not a failure
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_mesh"))
    return out


# Telemetry-on overhead ceiling for the --live gate: the live leg samples
# the metrics bus and writes the progress heartbeat only at host syncs
# that already happen, so the instrumented run must stay within this
# fraction of the plain-telemetry run.  0.35 leaves slack for CPU-only CI
# jitter on a sub-second paxos-3 check while still catching a leg that
# re-introduces per-step device round-trips (which costs integer
# multiples, not fractions).
LIVE_OVERHEAD_MAX = 0.35


def live_verdict(run: dict, baseline: dict) -> dict:
    """``--live``: the live-observability leg (docs/observability.md).

    The leg is FLAG-gated (``BENCH_LIVE=1``), so absence never trips —
    stale artifacts and pre-observability baselines pass untouched (the
    spill/mxu/sweep/fleet/mesh rule).  When a fresh run carries it:

     - a crashed leg (``tpu_live_error``) is a gate failure, not a skip;
     - the block must be WELL-FORMED: positive unique/state counts with
       ``states >= unique``;
     - count parity must have held (``parity == "IDENTICAL"`` — the bus
       and heartbeat ride host syncs that already happen; an
       instrumented run that changes counts broke the zero-overhead
       contract outright);
     - the sampling + heartbeat overhead must stay within
       ``LIVE_OVERHEAD_MAX`` of the plain-telemetry run
       (``overhead_frac``);
     - the bus must actually have published (``families`` includes
       ``stateright_states_total``) and the run's terminal heartbeat
       must exist with verdict ``done``.
    """
    out: dict = {}
    problems = []
    err = run.get("tpu_live_error")
    blk = run.get("tpu_live")
    present = bool(err) or blk is not None
    if err:
        problems.append(f"leg crashed: tpu_live: {err}")
    if blk is not None and not isinstance(blk, dict):
        problems.append("tpu_live block is not an object")
        blk = None
    if isinstance(blk, dict):
        ints = {}
        for k in ("unique", "states"):
            v = blk.get(k)
            if not isinstance(v, int) or v <= 0:
                problems.append(f"tpu_live.{k} missing/malformed: {v!r}")
            else:
                ints[k] = v
        if (
            {"unique", "states"} <= set(ints)
            and ints["states"] < ints["unique"]
        ):
            problems.append(
                f"tpu_live.states={ints['states']} < "
                f"unique={ints['unique']} (total visits bound uniques)"
            )
        if blk.get("parity") != "IDENTICAL":
            problems.append(
                f"tpu_live.parity={blk.get('parity')!r} (metrics+heartbeat "
                "instrumentation must not change counts — the bus samples "
                "host syncs that already happen)"
            )
        frac = blk.get("overhead_frac")
        if not isinstance(frac, (int, float)):
            problems.append(
                f"tpu_live.overhead_frac missing/malformed: {frac!r}"
            )
        elif frac > LIVE_OVERHEAD_MAX:
            problems.append(
                f"tpu_live.overhead_frac={frac} exceeds the pinned "
                f"{LIVE_OVERHEAD_MAX} ceiling (bus sampling + heartbeat "
                "writes must stay a fraction of the run, not a multiple)"
            )
        else:
            out["overhead_frac"] = frac
        fams = blk.get("families")
        if (
            not isinstance(fams, list)
            or "stateright_states_total" not in fams
        ):
            problems.append(
                f"tpu_live.families missing stateright_states_total: "
                f"{fams!r} (an instrumented run whose bus never published "
                "measured nothing)"
            )
        hb = blk.get("heartbeat")
        if not isinstance(hb, dict) or hb.get("verdict") != "done":
            problems.append(
                f"tpu_live.heartbeat verdict is not 'done': "
                f"{(hb or {}).get('verdict') if isinstance(hb, dict) else hb!r} "
                "(the terminal forced beat must land)"
            )
    out["present"] = present
    out["ok"] = not problems  # flag-gated: absence is not a failure
    if problems:
        out["problems"] = problems
    out["baseline_present"] = bool(baseline.get("tpu_live"))
    return out


def diff_verdict(run: dict, baseline: dict) -> dict:
    """``--diff``: the contract-aware report diff
    (``telemetry/diff.py``; docs/telemetry.md "Comparing runs").

    Engages only when BOTH the fresh run and the stored baseline carry an
    embedded ``tpu_paxos3_report`` — stale artifacts and pre-registry
    baselines never trip (the ``--stages`` rule).  When both exist, the
    pair must not classify DIVERGENT: a fresh round whose counts drift
    from the validated history under a count-identical contract is
    exactly the regression this gate exists to catch.  Incomparable
    pairs (e.g. a prefix run against the stored full enumeration —
    different instance target) are disclosed and skipped: nothing to
    gate."""
    rep = run.get("tpu_paxos3_report")
    base = baseline.get("tpu_paxos3_report")
    out: dict = {"present": bool(rep), "baseline_present": bool(base)}
    if not rep or not base:
        out["ok"] = True
        out["skipped"] = (
            "run and/or baseline carries no embedded tpu_paxos3_report "
            "(pre-registry artifacts never trip)"
        )
        return out
    try:
        from stateright_tpu.telemetry.diff import diff_reports

        d = diff_reports(base, rep)
    except Exception as e:  # noqa: BLE001 - a diff crash is a gate
        # failure, not a gate skip
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    out["verdict"] = d["verdict"]
    out["contract"] = d["contract"]
    if d["violations"]:
        out["violations"] = d["violations"]
    if d["contract"] == "incomparable":
        out["ok"] = True
        out["skipped"] = (
            "configs incomparable (different model/instance — e.g. a "
            "prefix run vs the stored full enumeration); nothing to gate"
        )
        return out
    out["ok"] = d["verdict"] != "DIVERGENT"
    return out


def stage_verdict(run: dict, baseline: dict) -> dict:
    """``--stages``: the per-stage attribution section (docs/perf.md).

    A FRESH run must carry a well-formed ``tpu_paxos3_stages`` breakdown
    (every value a non-negative number) — a perf round without attribution
    is exactly the blind spot the attribution work closed.  The baseline's
    breakdown is attached for comparison when present but NEVER gates:
    stored baselines predating the attribution round (or measured on
    different hardware) have no stages, and stale numbers must not trip a
    fresh run (the same principle as the throughput gate's
    present-in-BOTH rule)."""
    rstages = run.get("tpu_paxos3_stages")
    out: dict = {"present": bool(rstages)}
    if not rstages:
        out["ok"] = False
        out["error"] = "run carries no tpu_paxos3_stages breakdown"
    else:
        bad = sorted(
            k for k, v in rstages.items()
            if not isinstance(v, (int, float)) or v < 0
        )
        out["ok"] = not bad
        if bad:
            out["malformed"] = bad
        out["run"] = rstages
    out["baseline"] = baseline.get("tpu_paxos3_stages")
    return out


def main(argv=None, fleet=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    run_path, baseline_path = DEFAULT_RUN, DEFAULT_BASELINE
    tolerance, allow_stale, sanitize = DEFAULT_TOLERANCE, False, False
    stages = cartography = independence = memory = spill = False
    roofline = diff = mxu = sweep = fleet_gate = mesh_gate = False
    live_gate = False
    pos = []
    for a in argv:
        if a.startswith("--baseline="):
            baseline_path = a[len("--baseline="):]
        elif a.startswith("--tolerance="):
            tolerance = float(a[len("--tolerance="):])
        elif a == "--allow-stale":
            allow_stale = True
        elif a == "--sanitize":
            sanitize = True
        elif a == "--stages":
            stages = True
        elif a == "--cartography":
            cartography = True
        elif a == "--independence":
            independence = True
        elif a == "--memory":
            memory = True
        elif a == "--spill":
            spill = True
        elif a == "--roofline":
            roofline = True
        elif a == "--mxu":
            mxu = True
        elif a == "--sweep":
            sweep = True
        elif a == "--fleet":
            fleet_gate = True
        elif a == "--mesh":
            mesh_gate = True
        elif a == "--live":
            live_gate = True
        elif a == "--diff":
            diff = True
        else:
            pos.append(a)
    if pos:
        run_path = pos[0]
    try:
        run = load_run(run_path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(json.dumps({"ok": False, "error": f"cannot load run: {e}"}))
        return 2
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except FileNotFoundError:
        # no full TPU bench run has been recorded yet: nothing to compare
        # against, but the gates on the run's own blocks still apply
        baseline = {}
    except (OSError, json.JSONDecodeError) as e:
        print(json.dumps({"ok": False,
                          "error": f"cannot load baseline: {e}"}))
        return 2
    # bench stores what a non-TPU device phase measured under the
    # platform's own key prefix (xlacpu_*), so no rate is ever read as a
    # chip number.  Such a run is not stale — its blocks are its own and
    # the block gates apply — but its rates are compared with nothing.
    platform = run.get("platform")
    off_chip = platform not in (None, "tpu")
    pfx = run.get("device_key_prefix")
    if off_chip and pfx:
        run = {
            ("tpu" + k[len(pfx):] if k.startswith(pfx + "_") else k): v
            for k, v in run.items()
        }
    verdict = compare(run, {} if off_chip else baseline, tolerance)
    if platform is not None:
        verdict["platform"] = platform
    if not baseline:
        verdict["baseline"] = "no baseline recorded yet"
    measured = verdict["fresh"] or off_chip
    if off_chip:
        verdict["ok"] = True  # until a block gate below says otherwise
    # staleness exits 2 regardless of soundness, so don't pay the fleet
    # import+trace for an artifact that can never validate
    if sanitize and (measured or allow_stale):
        verdict["sanitizer"] = sanitizer_verdict(fleet=fleet)
        verdict["ok"] = verdict["ok"] and verdict["sanitizer"]["clean"]
    # same staleness economics as --sanitize: only fresh runs (or explicit
    # stale comparisons) pay the fleet import+trace, and stale/pre-POR
    # baselines never trip the gate
    if independence and (measured or allow_stale):
        verdict["independence"] = independence_verdict(run, fleet=fleet)
        verdict["ok"] = verdict["ok"] and verdict["independence"]["clean"]
    if stages:
        verdict["stages"] = stage_verdict(run, baseline)
        # only a FRESH run is required to carry attribution — a stored/
        # stale artifact predating the attribution round must not trip
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["stages"]["ok"]
    if cartography:
        verdict["cartography"] = cartography_verdict(run, baseline)
        # same freshness rule as --stages: pre-cartography baselines and
        # stale artifacts never trip
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["cartography"]["ok"]
    if memory:
        verdict["memory"] = memory_verdict(run, baseline)
        # same freshness rule again: stale artifacts and pre-memory
        # baselines never trip
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["memory"]["ok"]
    if spill:
        verdict["spill"] = spill_verdict(run, baseline)
        # flag-gated leg: absence passes; a present-but-malformed (or
        # crashed, or count-drifting) leg trips fresh runs only
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["spill"]["ok"]
    if roofline:
        verdict["roofline"] = roofline_verdict(run, baseline)
        # same freshness rule as --stages/--cartography/--memory:
        # stale artifacts and pre-roofline baselines never trip
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["roofline"]["ok"]
    if mxu:
        verdict["mxu"] = mxu_verdict(run, baseline)
        # flag-gated legs: absence passes; a present-but-crashed,
        # count-drifting, or payoff-missing leg trips fresh runs only
        # (stale/pre-mxu baselines never trip — the spill rule)
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["mxu"]["ok"]
    if sweep:
        verdict["sweep"] = sweep_verdict(run, baseline)
        # flag-gated leg: absence passes; a present-but-crashed,
        # parity-breaking, or unamortized leg trips fresh runs only
        # (stale/pre-sweep baselines never trip — the spill/mxu rule)
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["sweep"]["ok"]
    if fleet_gate:
        verdict["fleet"] = fleet_verdict(run, baseline)
        # flag-gated leg: absence passes; a present-but-crashed,
        # parity-breaking, incomplete, or unamortized leg trips fresh
        # runs only (stale/pre-fleet baselines never trip — the
        # spill/mxu/sweep rule)
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["fleet"]["ok"]
    if mesh_gate:
        verdict["mesh"] = mesh_verdict(run, baseline)
        # flag-gated leg: absence passes; a present-but-crashed,
        # parity-breaking, or load-vector-inconsistent leg trips fresh
        # runs only (stale/pre-mesh baselines never trip — the
        # spill/mxu/sweep/fleet rule)
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["mesh"]["ok"]
    if live_gate:
        verdict["live"] = live_verdict(run, baseline)
        # flag-gated leg: absence passes; a present-but-crashed,
        # parity-breaking, or over-budget leg trips fresh runs only
        # (stale/pre-observability baselines never trip — the
        # spill/mxu/sweep/fleet/mesh rule)
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["live"]["ok"]
    if diff:
        verdict["diff"] = diff_verdict(run, baseline)
        # same freshness rule: stale artifacts and pre-registry
        # baselines (no embedded report) never trip
        if measured:
            verdict["ok"] = verdict["ok"] and verdict["diff"]["ok"]
    print(json.dumps(verdict))
    if off_chip:
        sys.stderr.write(
            f"regress: the run's device phase ran on platform="
            f"{platform!r} — no throughput was compared, only the gates "
            "on the run's own blocks applied\n"
        )
    if not measured and not allow_stale:
        sys.stderr.write(
            "regress: RUN IS NOT FRESH — the artifact carries no number "
            "measured on a TPU by this run. Refusing to validate it.\n"
        )
        return 2
    if verdict["regressed"]:
        sys.stderr.write(
            f"regress: {len(verdict['regressed'])} config(s) below "
            f"{tolerance}x of the stored baseline (see stdout JSON)\n"
        )
        return 1
    if "sanitizer" in verdict and not verdict["sanitizer"]["clean"]:
        sys.stderr.write(
            "regress: the example fleet FAILS the soundness sanitizer "
            "(JX2xx; see stdout JSON) — throughput from kernels with "
            "out-of-range indexing is not a valid measurement\n"
        )
        return 1
    if "independence" in verdict and not verdict["independence"]["clean"]:
        sys.stderr.write(
            "regress: the static-independence gate FAILED (JX3xx fleet "
            "matrix or the POR leg; see stdout JSON) — a reduction whose "
            "matrix is malformed or whose counts drift is not sound\n"
        )
        return 1
    if (
        "stages" in verdict
        and measured
        and not verdict["stages"]["ok"]
    ):
        sys.stderr.write(
            "regress: fresh run carries no (or malformed) per-stage "
            "attribution (tpu_paxos3_stages) — an unattributed perf "
            "number cannot drive the >=1M states/s chase (docs/perf.md)\n"
        )
        return 1
    if (
        "cartography" in verdict
        and measured
        and not verdict["cartography"]["ok"]
    ):
        sys.stderr.write(
            "regress: fresh run carries no (or malformed) search "
            "cartography (tpu_paxos3_cartography) — a perf number without "
            "the search shape behind it cannot be interpreted "
            "(docs/telemetry.md)\n"
        )
        return 1
    if (
        "memory" in verdict
        and measured
        and not verdict["memory"]["ok"]
    ):
        sys.stderr.write(
            "regress: fresh run carries no (or malformed) memory-ledger "
            "block (tpu_paxos3_memory) — a perf number without its HBM "
            "footprint cannot drive the capacity tier "
            "(docs/telemetry.md)\n"
        )
        return 1
    if (
        "spill" in verdict
        and measured
        and not verdict["spill"]["ok"]
    ):
        sys.stderr.write(
            "regress: the spill leg is malformed, crashed, or drifted "
            "its counts (tpu_2pc7_spill; see stdout JSON) — a spill tier "
            "that changes counts is not a capacity tier (docs/spill.md)\n"
        )
        return 1
    if (
        "roofline" in verdict
        and measured
        and not verdict["roofline"]["ok"]
    ):
        sys.stderr.write(
            "regress: fresh run carries no (or malformed, or "
            "non-XLA-reconciling) roofline block (tpu_paxos3_roofline) — "
            "a perf number without its cost ledger cannot drive the MXU "
            "round (docs/roofline.md)\n"
        )
        return 1
    if (
        "mxu" in verdict
        and measured
        and not verdict["mxu"]["ok"]
    ):
        sys.stderr.write(
            "regress: the MXU-recast legs failed their payoff/parity "
            "bars (tpu_*_mxu_*; see stdout JSON) — a recast that drifts "
            "counts or moves no fewer bytes did not execute the hot-spot "
            "list (docs/roofline.md)\n"
        )
        return 1
    if (
        "sweep" in verdict
        and measured
        and not verdict["sweep"]["ok"]
    ):
        sys.stderr.write(
            "regress: the sweep leg is malformed, crashed, drifted its "
            "per-instance counts, or paid per-instance compiles "
            "(tpu_sweep; see stdout JSON) — a sweep that does not "
            "amortize compiles or reconcile per instance is not a sweep "
            "(docs/sweep.md)\n"
        )
        return 1
    if (
        "fleet" in verdict
        and measured
        and not verdict["fleet"]["ok"]
    ):
        sys.stderr.write(
            "regress: the fleet leg is malformed, crashed, drifted its "
            "per-job counts, left tenants unfinished, or paid per-job "
            "compiles despite packing (tpu_fleet; see stdout JSON) — a "
            "scheduler that drifts or drops tenants is not a serving "
            "tier (docs/fleet.md)\n"
        )
        return 1
    if (
        "mesh" in verdict
        and measured
        and not verdict["mesh"]["ok"]
    ):
        sys.stderr.write(
            "regress: the mesh leg is malformed, crashed, drifted its "
            "counts, or carries an inconsistent shard-load/routing "
            "readout (tpu_mesh; see stdout JSON) — a partitioned engine "
            "that drifts or cannot account for its own placement is not "
            "an A/B (docs/mesh.md)\n"
        )
        return 1
    if (
        "live" in verdict
        and measured
        and not verdict["live"]["ok"]
    ):
        sys.stderr.write(
            "regress: the live-observability leg is malformed, crashed, "
            "drifted its counts, or blew the pinned telemetry-on overhead "
            "ceiling (tpu_live; see stdout JSON) — a metrics bus that "
            "changes the run it observes is not observability "
            "(docs/observability.md)\n"
        )
        return 1
    if (
        "diff" in verdict
        and measured
        and not verdict["diff"]["ok"]
    ):
        sys.stderr.write(
            "regress: the fresh run's report DIVERGES from the validated "
            "baseline's under the contract-aware diff (see stdout JSON) — "
            "counts drifting across rounds under a count-identical "
            "contract is a correctness regression, not noise "
            "(docs/telemetry.md \"Comparing runs\")\n"
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

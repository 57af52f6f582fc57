"""MXU recast round: bytes-moved reduction knobs (docs/roofline.md).

PR 11's roofline ledger proved every pipeline stage memory-bound at
0.008-0.099 FLOPs/byte and ranked the hot spots (the JX4xx catalogue).
This module holds the execution half's shared pieces — the resolved
flag configuration and the BLEST one-hot membership probe — for the
two flag-gated step-program transforms (the queue append in
``batch``-sized chunks gated on ``n_new`` is no flag's: it is the engine's
ONE append, ``wavefront.append_novel``):

 - **expand-scatter coalescing** (``coalesce``): the hand-twin and
   per-channel step kernels assemble each action piece's packed-field
   write-backs as ONE word-assembled block (``tensor_model.FieldWriter``)
   instead of one ``.at[..., word].set`` scatter per field — the
   paxos-3 ledger charged 37 such sites at 109 MB/step, each paying a
   full-array slice read on top of its scatter;
 - **BLEST one-hot probe** (``probe``): the bucket membership/occupancy
   reductions recast as one blocked bitmapped ``dot_general`` over the
   candidate x slot comparison tile (:func:`blest_probe`), giving the
   dedup-insert stage a genuine dot-class op (the JX400 #1 target on
   2pc-7).

Contract (the family's strongest form, pinned by tests): every knob off
leaves the step jaxpr bit-identical and the engine cache unkeyed; on,
unique/total counts, verdicts, and discovery traces are bit-identical —
the transforms move the same bytes' worth of INFORMATION through
cheaper shapes, never different information.

Armed via ``CheckerBuilder.mxu()`` / ``--mxu`` / ``STATERIGHT_TPU_MXU=1``
(both components; keyword arguments select a subset).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

ENV_MXU = "STATERIGHT_TPU_MXU"


class MxuConfig(NamedTuple):
    """The resolved MXU-recast component set (all off = never built:
    engines carry ``None`` instead, keeping caches unkeyed)."""

    coalesce: bool = True
    probe: bool = True

    def key(self) -> tuple:
        """Engine-cache key suffix — appended ONLY when armed, so the
        off-path cache key is exactly the pre-MXU tuple (the spill
        discipline, ``wavefront._engine_key``)."""
        return ("mxu", self.coalesce, self.probe)


def resolve_mxu(opts: Optional[dict]) -> Optional[MxuConfig]:
    """Builder options -> the armed config, or None (off).

    ``opts`` is ``CheckerBuilder.mxu_opts`` (a dict of component booleans,
    or None = unset); unset falls back to the ``STATERIGHT_TPU_MXU=1``
    env knob, which arms both components.  A config with every
    component off resolves to None — indistinguishable from never asking.
    """
    if opts is None:
        if os.environ.get(ENV_MXU, "") == "1":
            return MxuConfig()
        return None
    cfg = MxuConfig(
        coalesce=bool(opts.get("coalesce", True)),
        probe=bool(opts.get("probe", True)),
    )
    if not (cfg.coalesce or cfg.probe):
        return None
    return cfg


def has_coalesced_step(tensor) -> bool:
    """Does ``tensor`` have a REAL coalesced expand kernel?  A twin may
    define ``step_rows_coalesced`` yet fall back internally for some
    configurations (the slot-multiset compiled twin) — such twins
    advertise the truth via a ``has_coalesced_step`` attribute, which
    wins over mere method presence."""
    flag = getattr(tensor, "has_coalesced_step", None)
    if flag is not None:
        return bool(flag() if callable(flag) else flag)
    return getattr(tensor, "step_rows_coalesced", None) is not None


def coalesced_step_fn(tensor, mxu: Optional[MxuConfig]):
    """The expand kernel the engines should trace: the twin's coalesced
    step when the knob is armed AND the twin provides a real one
    (:func:`has_coalesced_step`), else the plain ``step_rows``.  Twins
    without a coalesced form (slot-multiset compiled twins, exotic hand
    twins) silently keep the plain kernel — the flag then still buys the
    probe recast, and counts stay identical either way."""
    if mxu is not None and mxu.coalesce and has_coalesced_step(tensor):
        return tensor.step_rows_coalesced
    return tensor.step_rows


def effective_mxu(tensor, mxu: Optional[MxuConfig]) -> Optional[MxuConfig]:
    """The config as it actually lands on ``tensor``: ``coalesce``
    downgrades when the twin provides no coalesced kernel (the
    :func:`coalesced_step_fn` fallback), so landed-recast bookkeeping
    (``costmodel.mxu_candidates``) never silences a JX400 finding the
    flag did not actually move."""
    if mxu is None or not mxu.coalesce:
        return mxu
    if not has_coalesced_step(tensor):
        return mxu._replace(coalesce=False)
    return mxu


def blest_probe(lines, wfp, empty):
    """Membership + occupancy of one gathered bucket-line window via ONE
    blocked bitmapped matmul (the BLEST one-hot trick, PAPERS.md).

    ``lines`` is the gathered ``[W, L]`` uint64 bucket window (since PR
    38 ``L`` is ``buckets.ROW_LANES``: the table row that holds the
    candidate's bucket, the row's other buckets masked to ``empty``),
    ``wfp`` the ``[W]`` candidate fingerprints.  The comparison tile
    ``[W, 2*L]`` — membership bits next to occupancy bits — is
    contracted against a static ``[2*L, 2]`` block-diagonal
    accumulator on the MXU: column 0 sums the membership lane, column 1
    the occupancy lane, so one ``dot_general`` replaces the
    ``reduce_or``/``reduce_sum`` pair.  Exactness: the tile holds only
    0.0/1.0 and row sums are <= 2*L, exactly representable in
    float32, so ``(present, base)`` are bit-identical to the reduction
    pair's — pinned against ``bucket_insert`` in tests/test_buckets.py.
    Timed alone on one v5e (PR 38; ms an insert at a 2^23-slot table,
    window 2,048 / 4,096): 0.7355 / 1.3184 with the dot, 0.7372 / 1.3197
    with the reductions — the tile's eight times more lanes than before
    PR 38 do not show, and neither does the MXU.

    Returns ``(present bool[W], base int32[W])``.
    """
    import jax
    import jax.numpy as jnp

    slots = lines.shape[-1]
    eq = (lines == wfp[:, None]).astype(jnp.float32)
    occ = (lines != empty).astype(jnp.float32)
    tile = jnp.concatenate([eq, occ], axis=-1)  # [W, 2*SLOTS]
    acc = jnp.concatenate(
        [
            jnp.concatenate(
                [jnp.ones((slots, 1), jnp.float32),
                 jnp.zeros((slots, 1), jnp.float32)], axis=1
            ),
            jnp.concatenate(
                [jnp.zeros((slots, 1), jnp.float32),
                 jnp.ones((slots, 1), jnp.float32)], axis=1
            ),
        ],
        axis=0,
    )  # [2*SLOTS, 2] block-diagonal ones
    out = jax.lax.dot_general(
        tile, acc, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [W, 2]
    present = out[:, 0] > 0.5
    base = out[:, 1].astype(jnp.int32)
    return present, base

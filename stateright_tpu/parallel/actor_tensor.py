"""Device encoding of the in-model network: a sorted-slot multiset.

The reference's unordered non-duplicating network is a multiset of envelopes
(``src/actor/network.rs:188-190``).  The tensor form (SURVEY §7.3(1): the
hardest encoding problem) packs each *distinct* envelope into one ``uint64``
slot word::

    slot = envelope_code << COUNT_BITS | count      (EMPTY = 2^64-1 if free)

and keeps the slot array sorted ascending, so equal multisets produce equal
words in equal positions — the canonical-order property the reference gets
for free from order-insensitive hashing (``src/util.rs:124-145``).  Because
``envelope_code`` occupies the high bits and equal multisets have equal
counts per code, sorting by the whole word is sorting by code.

Two row layouts share this slot-word format
(``parallel/actor_compiler.py``):

 - the default **slot multiset** — one global sorted region for every
   envelope, simplest and narrowest, but a delivery's destination is
   message DATA, so the independence analysis cannot confine its writes
   (finding ``JX302``) and partial-order reduction gets nothing;
 - the opt-in **per-channel layout** — one region per directed
   ``(src, dst)`` channel, sorted per region, sized to that channel's
   envelope universe.  A delivery's writes are then statically confined
   to its own channel's words (plus the recipient's packed fields and
   the statically-known send-target regions), which is what turns the
   ample-set machinery into real reduction on the consensus fleet
   (``docs/analysis.md`` "Per-channel encoding").

The batched ops below are region-agnostic: they operate on whatever slot
region the caller slices out, so both layouts reuse them.

Device ops (all pure, jittable, batched over leading axes):

 - :func:`slot_deliver` — decrement count at a slot index; free at zero.
 - :func:`slot_send` — increment an existing code's count or claim a free
   slot (the caller re-sorts once per step via :func:`slot_canonicalize`).
 - :func:`slot_canonicalize` — re-sort so EMPTY slots sink to the end.
 - :func:`slot_send_ordered` — ordered networks, slot-multiset layout: claim
   a free slot at the TAIL of the code's directed flow (count bits = 1-based
   rank in the flow).  The caller carries each slot's flow id beside the
   slot word (``slot_pair``) and passes the sent code's (``code_pair``); the
   kernel returns the updated ids, so K sends in a row cost no look-up.
 - :func:`region_send_ordered` — ordered networks, per-channel layout: the
   region IS one flow, so the rank is its occupancy; no flow ids.

Each device op runs under the ``twin.net`` named scope
(``telemetry/spans.py``), so a profile splits ``sr.expand`` into the
network encoding and the rest, for compiled and hand-written twins alike.

Host-side, :class:`SlotCodec` mirrors the packing for ``encode_state`` /
``decode_state`` bridges.
"""

from __future__ import annotations

from typing import Callable, Iterable

import jax
import jax.numpy as jnp

from ..fingerprint import MASK64
from ..telemetry.spans import TWIN_NET

COUNT_BITS = 6
COUNT_MASK = (1 << COUNT_BITS) - 1
SLOT_EMPTY = MASK64


class SlotCodec:
    """Host-side slot packing over an envelope⇄code bijection."""

    def __init__(
        self,
        n_slots: int,
        encode_env: Callable,  # Envelope -> int code
        decode_env: Callable,  # int code -> Envelope
    ):
        self.n_slots = n_slots
        self.encode_env = encode_env
        self.decode_env = decode_env

    def pack(self, env_counts: Iterable[tuple]) -> tuple:
        """``[(envelope, count), ...] -> sorted slot words``."""
        words = []
        for env, count in env_counts:
            if not 1 <= count <= COUNT_MASK:
                raise ValueError(f"count {count} out of range for {env!r}")
            words.append((self.encode_env(env) << COUNT_BITS) | count)
        if len(words) > self.n_slots:
            raise ValueError(
                f"{len(words)} distinct envelopes exceed {self.n_slots} slots"
            )
        words.sort()
        words += [SLOT_EMPTY] * (self.n_slots - len(words))
        return tuple(words)

    def unpack(self, words) -> list[tuple]:
        """``slot words -> [(envelope, count), ...]``"""
        out = []
        for w in words:
            w = int(w)
            if w == SLOT_EMPTY:
                continue
            out.append((self.decode_env(w >> COUNT_BITS), w & COUNT_MASK))
        return out


def slot_counts(slots):
    return slots & jnp.uint64(COUNT_MASK)


def slot_codes(slots):
    return slots >> jnp.uint64(COUNT_BITS)


def slot_occupied(slots):
    return slots != jnp.uint64(SLOT_EMPTY)


@jax.named_scope(TWIN_NET)
def slot_deliver(slots, index: int):
    """Consume one instance of the envelope in slot ``index`` (static index;
    batched over leading axes).  Caller must ensure the slot is occupied.
    Returns un-canonicalized slots."""
    w = slots[..., index]
    count = w & jnp.uint64(COUNT_MASK)
    neww = jnp.where(
        count <= jnp.uint64(1), jnp.uint64(SLOT_EMPTY), w - jnp.uint64(1)
    )
    return slots.at[..., index].set(neww)


@jax.named_scope(TWIN_NET)
def slot_send(slots, code, enable, set_semantics: bool = False):
    """Add one instance of ``code`` (uint64[...]) where ``enable`` (bool[...]).

    Existing code -> count+1; else claim the first free slot (one-hot
    scatter, so repeated sends compose without re-sorting in between; the
    caller canonicalizes once per step).  Returns (slots, overflow):
    ``overflow`` is True where enable is set but no slot was available, or
    the matched slot's count field is saturated (a count+1 there would carry
    into the envelope-code bits and silently corrupt the row — the device
    analogue of ``SlotCodec.pack``'s count range check).

    ``set_semantics`` models a *duplicating* network's envelope SET
    (reference ``network.rs:203-205``): sending an already-present code is a
    no-op instead of a count bump, and cannot overflow the count field.
    """
    n = slots.shape[-1]
    match = slot_occupied(slots) & (slot_codes(slots) == code[..., None])
    exists = jnp.any(match, axis=-1)
    if set_semantics:
        maxed = jnp.zeros_like(exists)
        bumped = slots
    else:
        maxed = jnp.any(
            match & (slot_counts(slots) == jnp.uint64(COUNT_MASK)), axis=-1
        )
        bumped = jnp.where(
            match & (enable & ~maxed)[..., None], slots + jnp.uint64(1), slots
        )

    free = ~slot_occupied(slots)
    first_free = jnp.argmax(free, axis=-1)  # 0 if none free; gated below
    any_free = jnp.any(free, axis=-1)
    claim = enable & ~exists & any_free
    onehot = (
        jnp.arange(n) == first_free[..., None]
    ) & claim[..., None]
    neww = (code << jnp.uint64(COUNT_BITS)) | jnp.uint64(1)
    claimed = jnp.where(onehot, neww[..., None], bumped)
    overflow = enable & ((~exists & ~any_free) | maxed)
    return claimed, overflow


@jax.named_scope(TWIN_NET)
def slot_send_ordered(slots, slot_pair, code, code_pair, enable):
    """Append ``code`` at the TAIL of its directed flow (ordered networks):
    the claimed slot's count bits get rank ``1 + |in-flight same-flow
    envelopes|``.  No dedup — ordered flows hold duplicates at distinct
    ranks.

    The flow ids are CARRIED beside the slot words, not derived from them:
    ``slot_pair`` (int32, the shape of ``slots``) holds each slot's flow id
    and ``code_pair`` (int32, the shape of ``code``) the sent code's.  The
    contract, which the caller establishes once and every call keeps (with
    ``env_pair`` the caller's code -> flow table)::

        slot_pair == where(slot_occupied(slots), env_pair[slot_codes(slots)], -1)

    Returns ``(slots, slot_pair, overflow)``; overflow = no free slot, or
    the flow is already ``COUNT_MASK`` deep (rank would corrupt the code
    bits)."""
    n = slots.shape[-1]
    occ = slot_occupied(slots)
    in_flow = occ & (slot_pair == code_pair[..., None])
    depth = jnp.sum(in_flow, axis=-1).astype(jnp.uint64)

    free = ~occ
    first_free = jnp.argmax(free, axis=-1)
    any_free = jnp.any(free, axis=-1)
    too_deep = depth >= jnp.uint64(COUNT_MASK)
    claim = enable & any_free & ~too_deep
    onehot = (jnp.arange(n) == first_free[..., None]) & claim[..., None]
    neww = (code << jnp.uint64(COUNT_BITS)) | (depth + jnp.uint64(1))
    claimed = jnp.where(onehot, neww[..., None], slots)
    claimed_pair = jnp.where(onehot, code_pair[..., None], slot_pair)
    overflow = enable & (~any_free | too_deep)
    return claimed, claimed_pair, overflow


@jax.named_scope(TWIN_NET)
def slot_canonicalize(slots):
    """Sort slots ascending; EMPTY (all-ones) sinks to the end."""
    return jnp.sort(slots, axis=-1)


@jax.named_scope(TWIN_NET)
def region_send_ordered(reg, code, enable):
    """Ordered append for the PER-CHANNEL packing: ``reg`` is one directed
    channel's slot region, which under the per-channel layout IS a single
    FIFO flow — no flow ids needed (contrast :func:`slot_send_ordered`,
    which tells flows apart inside the global slot multiset by the ids it
    carries).  Appends ``code`` at the tail: the claimed slot's
    count bits get rank ``1 + |occupied slots in the region|``.  Returns
    ``(reg, overflow)``; overflow = no free slot, or the flow is already
    ``COUNT_MASK`` deep (the rank would corrupt the code bits)."""
    n = reg.shape[-1]
    occ = slot_occupied(reg)
    depth = jnp.sum(occ, axis=-1).astype(jnp.uint64)
    free = ~occ
    first_free = jnp.argmax(free, axis=-1)
    any_free = jnp.any(free, axis=-1)
    too_deep = depth >= jnp.uint64(COUNT_MASK)
    claim = enable & any_free & ~too_deep
    onehot = (jnp.arange(n) == first_free[..., None]) & claim[..., None]
    neww = (code << jnp.uint64(COUNT_BITS)) | (depth + jnp.uint64(1))
    claimed = jnp.where(onehot, neww[..., None], reg)
    overflow = enable & (~any_free | too_deep)
    return claimed, overflow

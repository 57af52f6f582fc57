"""Tensor form of a model: fixed-width u64 rows + jittable batched transition.

A :class:`TensorModel` is the device twin of an object-form
:class:`~stateright_tpu.core.Model` (reference trait: ``src/lib.rs:155-237``).
Where the reference enumerates actions dynamically per state
(``src/actor/model.rs:214-239``), the tensor form declares a *static maximum
action arity* ``max_actions`` and returns a validity mask — the shape XLA
needs to tile the expansion onto the MXU/VPU without dynamic shapes.

Contract (``B`` = batch, ``W`` = width, ``A`` = max_actions, ``P`` = number of
properties, in the object model's ``properties()`` order):

 - ``init_rows() -> uint64[I, W]``  (host-side numpy is fine)
 - ``step_rows(rows: uint64[B, W]) -> (uint64[B, A, W], bool[B, A])``
   pure + jittable.  ``valid[b, a]`` ⟺ action ``a`` is enabled in row ``b``,
   produces a real successor (not a no-op — reference prunes those,
   ``src/actor/model.rs:253-260``), and the successor is within the boundary.
   Invalid successor rows may contain garbage.
 - ``property_masks(rows: uint64[B, W]) -> bool[B, P]`` — condition truth
   per state per property; pure + jittable.
 - ``encode_state(state) -> tuple[int, ...]`` / ``decode_state(row) -> state``
   host-side bridge to the object form.  ``fingerprint(encode_state(s))`` via
   :func:`~stateright_tpu.fingerprint.hash_words` must equal the device
   ``row_hash`` of the same row — guaranteed by construction since both hash
   the same W words.

Equivalence between the two forms (same successors, same fingerprints) is a
test obligation; see ``tests/test_tensor_models.py``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

from ..fingerprint import hash_words


def twin_or_none(model):
    """The model's device twin with host-fallback semantics: None when the
    model declares no twin OR its construction fails for any reason
    (CompileError, unsupported config, ...).  Shared by ``spawn_auto`` and
    the CLI ``report`` fallback; the device spawn path itself resolves the
    twin directly so construction errors surface there instead."""
    try:
        cached = getattr(model, "_tensor_cached", None)
        return (
            cached()
            if cached is not None
            else getattr(model, "tensor_model", lambda: None)()
        )
    except Exception:  # noqa: BLE001 - any twin failure: host fallback
        return None


class TensorModel:
    """Base class for device twins of object-form models."""

    width: int  # u64 words per state row
    max_actions: int  # static action arity A
    model: Any  # the object-form Model (properties, display, re-execution)

    # -- host-side bridge ----------------------------------------------------

    def init_rows(self) -> np.ndarray:
        raise NotImplementedError

    def encode_state(self, state) -> tuple:
        raise NotImplementedError

    def decode_state(self, row) -> Any:
        raise NotImplementedError

    def fingerprint_of(self, state) -> int:
        """Host fingerprint that matches the device ``row_hash`` bit-for-bit."""
        return hash_words(self.encode_state(state))

    # -- device-side ---------------------------------------------------------

    def step_rows(self, rows):
        raise NotImplementedError

    def property_masks(self, rows):
        raise NotImplementedError


class TensorBackedModel:
    """Mixin for object-form models that have a tensor twin.

    Overrides ``fingerprint_state`` to the row hash so every backend (CPU
    BFS/DFS, TPU wavefront, Explorer URLs) agrees on state identity, the way
    the reference's single stable hash does (``src/lib.rs:302-344``).

    ``tensor_model()`` may return None for configurations without a device
    twin (e.g. an unsupported network semantics); fingerprints then fall back
    to the base model's structural hash.  The verdict (and hence the
    fingerprint scheme) is cached on first fingerprint; configuration
    mutations after that point would silently mix fingerprint schemes, so
    they raise instead (builder methods report via ``_config_mutated``).
    """

    _TENSOR_UNRESOLVED = "unresolved"

    def tensor_model(self) -> Optional[TensorModel]:
        raise NotImplementedError

    def fingerprint_state(self, state) -> int:
        tm = self._tensor_cached()
        if tm is None:
            return super().fingerprint_state(state)
        return hash_words(tm.encode_state(state))

    def _config_mutated(self) -> None:
        if getattr(self, "_tensor_fp_used", False):
            raise RuntimeError(
                "model configuration changed after states were fingerprinted; "
                "the tensor-twin eligibility (and fingerprint scheme) is "
                "frozen at first use — configure the model fully before "
                "checking or fingerprinting"
            )
        # not fingerprinted yet: safe to re-derive eligibility later
        if hasattr(self, "_tensor_model_cache"):
            object.__delattr__(self, "_tensor_model_cache")

    def _tensor_cached(self) -> Optional[TensorModel]:
        tm = getattr(self, "_tensor_model_cache", self._TENSOR_UNRESOLVED)
        if tm is self._TENSOR_UNRESOLVED:
            tm = self.tensor_model()
            object.__setattr__(self, "_tensor_model_cache", tm)
            # Snapshot the configuration surface at resolution time: the
            # preflight auditor compares it against the live config and
            # flags drift (direct attribute writes bypass the builder's
            # _config_mutated hook entirely) as CF301 *before* a run can
            # mix fingerprint schemes.  See analysis/audit.py.
            from ..analysis.audit import config_signature

            object.__setattr__(
                self, "_tensor_config_sig", config_signature(self)
            )
        object.__setattr__(self, "_tensor_fp_used", True)
        return tm


class RowDomain:
    """Declared value bounds for a tensor row encoding — the seed of the
    sanitizer's interval abstract interpretation
    (``stateright_tpu/analysis/interval.py``).

    A twin that defines ``row_domain() -> RowDomain`` tells the static
    sanitizer what each row word (and each packed field) can actually
    hold; without it the pass falls back to field *widths* discovered from
    a :class:`BitPacker` attribute, which is correct but looser (a 3-bit
    field bounding 5 state codes proves ``< 8``, not ``< 5``).  Sentinel
    words (``EMPTY``-when-free network slots) declare ``may_empty`` so the
    domain is ``[0, hi] ∪ {EMPTY}`` rather than collapsing to top.
    """

    _EMPTY = (1 << 64) - 1

    def __init__(self, width: int):
        self.width = int(width)
        # per word: (hi, may_empty); None = top (nothing declared)
        self._words: list = [None] * self.width
        # (word, off, bits) -> hi for packed-field refinement
        self._fields: dict = {}

    def declare_word(self, word: int, hi: int,
                     may_empty: bool = False) -> "RowDomain":
        self._words[word] = (int(hi), bool(may_empty))
        return self

    def declare_field(self, word: int, off: int, bits: int,
                      hi: int) -> "RowDomain":
        """Bound bits ``[off, off+bits)`` of ``word`` to ``[0, hi]``
        (tighter than the field width when the domain doesn't fill it)."""
        self._fields[(int(word), int(off), int(bits))] = int(hi)
        return self

    @classmethod
    def from_packer(cls, packer: "BitPacker",
                    field_bounds: Optional[dict] = None,
                    width: Optional[int] = None) -> "RowDomain":
        """Word + field bounds from a :class:`BitPacker` layout; optional
        ``field_bounds`` (name -> hi) tighten individual fields below
        their width.  ``width`` over-allocates for rows with a non-packed
        tail (network slot words), which stays undeclared (top) until
        ``declare_word``."""
        dom = cls(width or packer.width)
        word_hi = [0] * packer.width
        for name, (word, off, bits) in packer.layout.items():
            hi = (1 << bits) - 1
            if field_bounds and name in field_bounds:
                hi = min(hi, int(field_bounds[name]))
            dom.declare_field(word, off, bits, hi)
            word_hi[word] |= hi << off
        for w, hi in enumerate(word_hi):
            dom.declare_word(w, hi)
        return dom

    # -- interpreter-facing --------------------------------------------------

    def field_hi(self, word: int, off: int, bits: int) -> Optional[int]:
        return self._fields.get((int(word), int(off), int(bits)))

    def words_ival(self, start: int, limit: int):
        """IVal covering words ``[start, limit)`` (a last-axis slice of the
        input rows): join of the declared word bounds, with the EMPTY
        sentinel carried as an exact outlier; single-word slices keep field
        provenance."""
        from ..analysis.interval import IVal

        los, his, empty = [], [], False
        for w in range(start, min(limit, self.width)):
            decl = self._words[w]
            if decl is None:
                return IVal(0, self._EMPTY)  # an undeclared word: top
            hi, me = decl
            los.append(0)
            his.append(hi)
            empty = empty or me
        if not his:
            return IVal(0, self._EMPTY)
        out = IVal(
            0, max(his),
            frozenset({self._EMPTY}) if empty and max(his) < self._EMPTY
            else frozenset(),
        )
        if limit - start == 1:
            from dataclasses import replace as _replace

            out = _replace(out, word=start, shift=0)
        return out


class FieldWriter:
    """Packed-field write accumulator over a :class:`BitPacker` block —
    the expand-scatter coalescing seam (``ops/mxu.py``, docs/roofline.md).

    The step kernels build successor packed words by applying one
    ``pk.set`` per written field, and each traces to a full-block slice
    read + a one-word scatter: the paxos-3 roofline ledger charged the
    37 such sites at 109 MB/step, the #1 ranked expand hot spot (JX400).
    This writer gives the kernels one seam with two materializations:

    - **eager** (``coalesce=False``, the default): every ``set``/
      ``or_field`` applies through ``pk.set`` / the OR-scatter at call
      time — op-for-op the pre-writer trace, so refactored kernels keep
      their step jaxpr bit-identical (pinned by test);
    - **coalesced** (``coalesce=True``): writes accumulate per word and
      :meth:`done` assembles the output block with ONE concatenate of
      per-word columns — modified words rebuilt elementwise from the
      base word column, untouched words passed through — so the
      per-field scatters (and their full-block slice reads) vanish from
      the traced program.

    Field semantics are identical either way (same masks, same
    precedence: writes apply in call order), which is what makes the
    engine-level counts bit-identical under the flag — pinned by the
    whole-space successor-parity tests.
    """

    def __init__(self, pk: "BitPacker", base, coalesce: bool = False):
        self.pk = pk
        self.base = base
        self.coalesce = bool(coalesce)
        self.cur = base  # eager running block
        # coalesced bookkeeping: word -> ordered op list, name -> value
        self._word_ops: dict[int, list] = {}
        self._pending: dict[str, object] = {}
        # name -> field-level OR flags, so get() after or_field matches
        # eager mode (which reads the running block) bit-for-bit
        self._or_pending: dict[str, list] = {}

    def set(self, name: str, value) -> "FieldWriter":
        """Write field ``name`` (uint64[...] matching the block's leading
        shape)."""
        if not self.coalesce:
            self.cur = self.pk.set(self.cur, name, value)
            return self
        word, off, bits = self.pk.layout[name]
        self._word_ops.setdefault(word, []).append(("set", off, bits, value))
        self._pending[name] = value
        # a set supersedes earlier ORs into the same field (done()
        # already applies ops in call order; get() must agree)
        self._or_pending.pop(name, None)
        return self

    def get(self, name: str):
        """Current value of field ``name``: the pending write when one
        exists, else the base block's field (eager mode reads the running
        block, exactly as the pre-writer kernels did)."""
        import jax.numpy as jnp

        if not self.coalesce:
            return self.pk.get(self.cur, name)
        v = self._pending.get(name)
        if v is None:
            v = self.pk.get(self.base, name)
        else:
            _w, _off, bits = self.pk.layout[name]
            v = (
                v.astype(jnp.uint64)
                if hasattr(v, "astype")
                else jnp.uint64(v)
            )
            if bits < 64:
                v = v & jnp.uint64((1 << bits) - 1)
        for flag in self._or_pending.get(name, ()):
            v = v | flag
        return v

    def or_field(self, name: str, flag) -> "FieldWriter":
        """OR ``flag`` (bool[...]) into the 1-bit packed field ``name``
        WITHOUT reading it back through ``pk.get``: the lane stays an
        identity of its own word with one OR-accumulated bit, which the
        footprint pass classifies as an accumulator write (monotone, so
        two actions' poison writes commute; docs/analysis.md)."""
        import jax.numpy as jnp

        word, off, _bits = self.pk.layout[name]
        v = flag.astype(jnp.uint64)
        if off:
            v = v << jnp.uint64(off)
        if not self.coalesce:
            self.cur = self.cur.at[..., word].set(self.cur[..., word] | v)
            return self
        self._word_ops.setdefault(word, []).append(("or", v))
        self._or_pending.setdefault(name, []).append(
            flag.astype(jnp.uint64)
        )
        return self

    def done(self):
        """Materialize the written block.  Eager: the running block.
        Coalesced: one concatenate of per-word columns."""
        if not self.coalesce:
            return self.cur
        import jax.numpy as jnp

        cols = []
        for w in range(self.pk.width):
            col = self.base[..., w]
            for op in self._word_ops.get(w, ()):
                if op[0] == "set":
                    _, off, bits, v = op
                    mask = jnp.uint64(((1 << bits) - 1) << off)
                    v = (
                        v.astype(jnp.uint64)
                        if hasattr(v, "astype")
                        else jnp.uint64(v)
                    )
                    if off:
                        v = v << jnp.uint64(off)
                    col = (col & ~mask) | (v & mask)
                else:  # ("or", v)
                    col = col | op[1]
            cols.append(col[..., None])
        return jnp.concatenate(cols, axis=-1)


class BitPacker:
    """Packs named bit fields into u64 words; fields never straddle words.

    Host side packs/unpacks Python ints (no jax import); device side extracts
    and rebuilds fields with shifts and masks on ``uint64`` arrays.  Word
    alignment costs a few wasted bits but keeps device field access to a
    single shift+mask.
    """

    def __init__(self, fields: Sequence[tuple[str, int]]):
        self.fields = list(fields)
        self.layout: dict[str, tuple[int, int, int]] = {}  # name -> (word, off, bits)
        word, off = 0, 0
        for name, bits in self.fields:
            if not 1 <= bits <= 64:
                raise ValueError(f"field {name!r}: bits must be in 1..64")
            if off + bits > 64:
                word, off = word + 1, 0
            self.layout[name] = (word, off, bits)
            off += bits
        self.width = word + 1

    # -- host ----------------------------------------------------------------

    def pack(self, **values: int) -> tuple:
        words = [0] * self.width
        for name, (word, off, bits) in self.layout.items():
            v = values.pop(name, 0)
            if not 0 <= v < (1 << bits):
                raise ValueError(f"field {name!r}={v} out of range ({bits} bits)")
            words[word] |= v << off
        if values:
            raise ValueError(f"unknown fields: {sorted(values)}")
        return tuple(words)

    def unpack(self, row) -> dict[str, int]:
        return {
            name: (int(row[word]) >> off) & ((1 << bits) - 1)
            for name, (word, off, bits) in self.layout.items()
        }

    # -- device --------------------------------------------------------------

    def get(self, rows, name: str):
        """Extract field ``name``: ``uint64[..., W] -> uint64[...]``."""
        import jax.numpy as jnp

        word, off, bits = self.layout[name]
        v = rows[..., word]
        if off:
            v = v >> jnp.uint64(off)
        if bits < 64:
            v = v & jnp.uint64((1 << bits) - 1)
        return v

    def set(self, rows, name: str, value):
        """Return rows with field ``name`` replaced by ``value`` (uint64[...])."""
        import jax.numpy as jnp

        word, off, bits = self.layout[name]
        mask = jnp.uint64(((1 << bits) - 1) << off)
        cleared = rows[..., word] & ~mask
        v = value.astype(jnp.uint64) if hasattr(value, "astype") else jnp.uint64(value)
        if off:
            v = v << jnp.uint64(off)
        return rows.at[..., word].set(cleared | (v & mask))


def select_along_axis(cols, idx):
    """``cols[..., idx]`` along the LAST axis, for an axis of a few entries:
    ``jnp.take_along_axis(cols, idx, axis=-1)`` on in-range indices, written
    as a chain of ``n - 1`` selects instead of an element gather.

    ``cols`` is ``[..., n]`` (one column per actor: a server field, a client
    phase, a small table), ``idx`` is ``[..., A]`` with every entry in
    ``[0, n)``; the result is ``[..., A]`` of ``cols``' dtype.  An element
    gather costs ~8 ns a lane on a v5e whatever it gathers from (0.3065 s /
    302 steps / 122,880 lanes in ``paxos3-presized``; ledger, PR 30); the
    selects are vector work that fuses into their consumers (alone on the
    chip 0.002 ns a lane at n = 3 and 0.15-0.23 at n = 106, where a one-hot
    sum reads 0.088: PERF.md section 6, PR 31).  Indices outside ``[0, n)``
    are the caller's to clip: they read column ``n - 1`` here, where
    ``take_along_axis`` wraps or fills."""
    import jax.numpy as jnp

    n = cols.shape[-1]
    out = jnp.broadcast_to(
        cols[..., n - 1 :], jnp.broadcast_shapes(cols.shape[:-1] + (1,), idx.shape)
    )
    for k in range(n - 2, -1, -1):
        out = jnp.where(idx == k, cols[..., k : k + 1], out)
    return out


def stable_rank(keys):
    """Where each of ``n`` key columns lands in their stable ascending sort:
    ``rank[i] = #{j : keys[j] < keys[i]} + #{j < i : keys[j] == keys[i]}``,
    the old -> new mapping ``argsort(argsort(stack(keys, -1), stable=True))``
    (``jnp.argsort(..., stable=True)`` itself is the inverse, new -> old).

    ``keys`` is a LIST of ``n`` equally shaped arrays — one per actor, the
    candidates in the lanes — never one ``[..., n]`` array: a minor axis of
    13 pads to 128 lanes on a v5e.  One compare a pair, ``n (n - 1) / 2`` in
    all, elementwise over the lanes; no ``sort`` and no ``gather``.  Returns
    ``n`` int32 arrays of the keys' shape, a permutation of ``0 .. n - 1`` in
    every lane."""
    import jax.numpy as jnp

    n = len(keys)
    ranks = [jnp.full(keys[0].shape, i, jnp.int32) for i in range(n)]
    for i in range(n):
        for j in range(i + 1, n):
            # the later column sorts first only on a strictly smaller key
            swap = (keys[j] < keys[i]).astype(jnp.int32)
            ranks[i] = ranks[i] + swap
            ranks[j] = ranks[j] - swap
    return ranks


def place_by_rank(cols, ranks):
    """Permute ``n`` columns to their ranks: ``out[p]`` is the ``cols[i]``
    whose ``ranks[i] == p`` — ``take_along_axis(stack(cols, -1),
    argsort(keys, stable=True), -1)[..., p]`` for ``ranks =
    stable_rank(keys)`` — as ``n`` select chains over the lanes instead of
    an element gather (which costs 9-16 ns a lane on a v5e whatever it
    gathers from; PERF.md section 6, PR 31).  ``ranks`` must be a
    permutation in every lane.  Returns a list of ``n`` arrays."""
    import jax.numpy as jnp

    n = len(cols)
    out = []
    for p in range(n):
        placed = cols[n - 1]
        for i in range(n - 2, -1, -1):
            placed = jnp.where(ranks[i] == p, cols[i], placed)
        out.append(placed)
    return out


def pack_by_rank(cols, ranks, bits):
    """``place_by_rank`` for columns that are ``bits``-wide fields of one
    packed word: ``OR_i cols[i] << (bits * ranks[i])`` — each field shifted
    straight to its rank's position, ``n`` variable shifts and no selects.
    ``cols`` hold values below ``2 ** bits``.  The word is a ``uint32`` where
    ``n * bits`` fits one and a ``uint64`` beyond: a variable shift of a u64
    costs more than one of a u32 (PERF.md section 6, PR 34)."""
    import jax.numpy as jnp

    dtype = jnp.uint32 if len(cols) * bits <= 32 else jnp.uint64
    word = jnp.zeros(cols[0].shape, dtype)
    for c, r in zip(cols, ranks):
        word = word | (c.astype(dtype) << (r * bits).astype(dtype))
    return word

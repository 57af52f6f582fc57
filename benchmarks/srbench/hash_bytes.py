"""The bytes the hash stage of one whole check NEEDS to move through HBM,
from the check's counts: every generated successor's row is read once and
its 8-byte key written once —

    generated * (width * 8 + 8)

A function of the row width and the pinned count only (as ``necessary.py``
is for the whole step program and ``expand_bytes.py`` for expand), never of
how ``sr.hash`` is written: the masked candidate lanes, a canonicaliser's
gathers under ``.symmetry()``, the pre-dedup and the broadcasts are all
what the stage costs BEYOND hashing its rows, so removing them raises the
share instead of moving the yardstick.
"""

from __future__ import annotations

from srbench.necessary import ROW_WORD_BYTES

KEY_BYTES = 8  # one uint64 fingerprint a generated row


def hash_bytes(width: int, generated: int) -> int:
    if width < 1 or generated < 0:
        raise ValueError("width >= 1 and a non-negative count required")
    return generated * (width * ROW_WORD_BYTES + KEY_BYTES)


def hash_roofline_pct(width: int, generated: int,
                      hbm_bytes_per_s: float, hash_s: float) -> float:
    """Share (%) of the HBM roofline of the hash stage alone: the least
    time the chip could take for :func:`hash_bytes` over the device self
    time of the ``sr.hash`` operations.  Integer work, so bandwidth is
    the bound that applies."""
    if hash_s <= 0:
        raise ValueError("the hash stage's device time must be positive")
    return 100.0 * (hash_bytes(width, generated) / hbm_bytes_per_s) / hash_s

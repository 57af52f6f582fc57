"""The bytes the expand stage of one whole check NEEDS to move through
HBM, from the check's counts: every unique state's row is read once (it
is popped and expanded once) and every generated successor's row is
written once —

    (unique + generated) * width * 8

A function of the row width and the pinned counts only (as
``necessary.py`` is for the whole step program, of which this is the
expand stage's share), never of how a twin's ``step_rows`` is written: a
change that stops materialising masked lanes raises the share instead of
moving the yardstick.
"""

from __future__ import annotations

from srbench.necessary import ROW_WORD_BYTES


def expand_bytes(width: int, generated: int, unique: int) -> int:
    if width < 1 or generated < 0 or unique < 0:
        raise ValueError("width >= 1 and non-negative counts required")
    return (unique + generated) * width * ROW_WORD_BYTES


def expand_roofline_pct(width: int, generated: int, unique: int,
                        hbm_bytes_per_s: float, expand_s: float) -> float:
    """Share (%) of the HBM roofline of the expand stage alone: the least
    time the chip could take for :func:`expand_bytes` over the device
    self time of the ``sr.expand`` operations.  Integer work, so
    bandwidth is the bound that applies."""
    if expand_s <= 0:
        raise ValueError("the expand stage's device time must be positive")
    least_s = expand_bytes(width, generated, unique) / hbm_bytes_per_s
    return 100.0 * least_s / expand_s

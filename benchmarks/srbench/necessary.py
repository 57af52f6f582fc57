"""The bytes one whole check NEEDS to move through HBM, from its counts.

A function of the row width and the check's pinned counts only — never of
how the step program happens to be written — so a PR that removes wasted
traffic raises the roofline share instead of moving the yardstick:

 - every generated successor row is written once by expand and read once
   by the hash: ``2 * generated * width * 8``
 - every unique row is appended to the queue once and popped once:
   ``2 * unique * width * 8``
 - every generated state probes the visited set once: one 16-byte slot
   (8-byte fingerprint + 8-byte parent payload): ``16 * generated``
"""

from __future__ import annotations

ROW_WORD_BYTES = 8  # rows are uint64 words
PROBE_BYTES = 16  # fingerprint + parent payload of one table slot


def necessary_bytes(width: int, generated: int, unique: int) -> int:
    if width < 1 or generated < 0 or unique < 0:
        raise ValueError("width >= 1 and non-negative counts required")
    row = width * ROW_WORD_BYTES
    return 2 * generated * row + 2 * unique * row + PROBE_BYTES * generated


def roofline_pct(width: int, generated: int, unique: int,
                 hbm_bytes_per_s: float, busy_s: float) -> float:
    """Share (%) of the HBM roofline: the least time the chip could take
    for the necessary bytes over the time its operations actually ran.
    The step program does integer work only, so bandwidth is the bound."""
    if busy_s <= 0:
        raise ValueError("device busy time must be positive")
    least_s = necessary_bytes(width, generated, unique) / hbm_bytes_per_s
    return 100.0 * least_s / busy_s

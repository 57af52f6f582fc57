"""Seconds per check in the checker's constructor hashing one init row on
the device with EAGER operations, to prove the host's fingerprints are the
device's: the program's ``fingerprint_bridge`` span (a flight-recorder
``span`` record; ``sr/fingerprint_bridge`` in the profiler's trace), median
over the window's checks.  Nothing to read where no check recorded it."""

UNIT = "s"
LAYER = "engine set-up"
MOVES = "check_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xstages

    return xstages.span_seconds(
        ctx, "fingerprint_bridge", marker="fingerprint_bridge"
    )

"""Bytes of the look-up tables a compiled actor twin's step program holds
on the device (transition, send, poison, envelope and property tables):
the ``table_bytes`` attribute of the program's ``twin_compile`` span.  0
where the warm-up recorded no such span: a hand-written twin has none."""

UNIT = "bytes"
LAYER = "compiled actor twin"
MOVES = "peak_hbm"
SOURCE = "program_counter"


def read(ctx):
    from srbench import xtwin

    if not ctx.get("warmup_records"):
        return None
    span = xtwin.compile_span(ctx)
    return float(span.get("table_bytes", 0)) if span else 0.0

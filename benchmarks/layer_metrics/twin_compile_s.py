"""Seconds the actor compiler took to turn the actor model into its device
twin (the reachable closure per actor, the transition / send / property
tables): the program's ``twin_compile`` span, closed by
``compile_actor_model`` and recorded by the warm-up check, the first
checker to adopt the twin.  0 where the warm-up recorded none: a
hand-written twin compiles nothing."""

UNIT = "s"
LAYER = "compiled actor twin"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from srbench import xtwin

    if not ctx.get("warmup_records"):
        return None
    span = xtwin.compile_span(ctx)
    return float(span["dur"]) if span else 0.0

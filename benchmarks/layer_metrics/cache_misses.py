"""Persistent compile-cache misses (= fresh backend compiles) over the whole
run, from JAX's monitoring events: 0 on every run after a cell's first."""

UNIT = "count"
LAYER = "engine set-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return int(ctx["compiles"]["persistent_misses"])

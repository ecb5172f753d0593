"""Executables the set-up asked the backend for: backend compiles plus
persistent-cache retrievals, counted by the benchmark's own
jax.monitoring listeners."""

LAYER = "compile cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
CELLS = None  # every cell


def read(run):
    return run["compile"]["setup"]["executables"]

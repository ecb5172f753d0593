"""Seconds the set-up spent getting executables from the backend: summed
backend_compile_duration, which is compilation in a first run and cache
retrieval after it."""

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
CELLS = None  # every cell


def read(run):
    return run["compile"]["setup"]["seconds"]

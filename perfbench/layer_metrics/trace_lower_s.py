"""Seconds jax spent tracing and lowering before the window: the summed
``trace_s + lower_s`` of the program's compile account over every record
up to the end of request 1 (the warm-up partition).  Both run before a
cache key exists, so a filled compile cache does not save them;
``compile_s`` is what the backend then took.  Left out where the program
keeps no such account."""

from perfbench.layer_metrics import _setup_account

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
CELLS = None  # every cell


def read(run):
    return _setup_account.read(_setup_account.trace_lower_s)

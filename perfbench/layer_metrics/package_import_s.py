"""Seconds of the program's own top-level import (``kaminpar_tpu``'s
``__init__`` from its first line to its last), as the package stamps
them into its compile account.  jax's import and the runtime's start
come before it (the harness has imported jax by then), and modules the
package imports lazily later are not in it.  Left out where the program
keeps no such account."""

from perfbench.layer_metrics import _setup_account

LAYER = "driver"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
CELLS = None  # every cell


def read(run):
    return _setup_account.read(_setup_account.package_import_s)

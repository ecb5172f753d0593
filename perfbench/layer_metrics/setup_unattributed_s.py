"""What the first request costs that no counter names:
``first_request_s`` less the median wall of the later requests (the
window's, same clock), less request 1's tracing, lowering and backend
seconds.  It guards the compile account as ``phase_attributed_share``
guards the span join: seconds that grow here are set-up the account does
not see (first-touch allocation, a library's load, hashing outside jax's
events).  Left out where the program keeps no such account."""

from perfbench.layer_metrics import _setup_account

LAYER = "driver"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return _setup_account.read(_setup_account.setup_unattributed_s)

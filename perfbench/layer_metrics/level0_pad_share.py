"""Share of the request's own graph's edge slots, as uploaded (level 0),
that hold no edge: 100 * (padded - real) / padded, from the program's
shape account (``kaminpar_tpu/telemetry/request_account.shape_account``:
``level0_edge_slots`` and ``level0_padded_edge_slots``, summed over a
request's uploads).  Every edge-wide pass of level 0 pays for each pad
slot as for an edge.  Median over the window's requests; left out where
the program's account keeps no such counters, as on every commit before
the one that added them."""

from __future__ import annotations

from statistics import median

LAYER = "driver"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "program_counter"
CELLS = None  # every cell

REAL = "level0_edge_slots"
PADDED = "level0_padded_edge_slots"


def read(run):
    try:
        from kaminpar_tpu.telemetry import request_account
    except ImportError:
        return None
    account = getattr(request_account, "shape_account", None)
    if account is None or PADDED not in getattr(account, "counters", ()):
        return None
    summary = account.summary()
    shares = []
    for ordinal in range(2, summary["requests"] + 1):
        counts = summary["by_request"].get(ordinal, {})
        padded = counts.get(PADDED, 0)
        if padded:
            shares.append(100.0 * (padded - counts.get(REAL, 0)) / padded)
    return median(shares) if shares else None

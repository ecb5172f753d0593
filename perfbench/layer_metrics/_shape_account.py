"""What the program decides from shapes alone, on its own account
(``kaminpar_tpu/telemetry/request_account.shape_account``, kept with
telemetry off), in the process that ran the cell, after the window.
The account sums each count under the ordinal of the request it was made
in; the warm-up partition is the process's request 1 and the window's
partitions are the later ones.  A program without the account, as every
commit before the one that added it, gives None and the metric is left
out."""

from __future__ import annotations

from statistics import median


def window_median(counter: str):
    """The median over the window's requests of ``counter`` summed over
    a request: 0 for a request that counted nothing, None where the
    program keeps no account or no request followed the warm-up."""
    try:
        from kaminpar_tpu.telemetry import request_account
    except ImportError:
        return None
    account = getattr(request_account, "shape_account", None)
    if account is None:
        return None
    summary = account.summary()
    by_request = summary["by_request"]
    values = [by_request.get(ordinal, {}).get(counter, 0)
              for ordinal in range(2, summary["requests"] + 1)]
    return median(values) if values else None

"""What the host FM's counters read: the program's own account of its
native k-way FM calls (``kaminpar_tpu/refinement/fm.fm_account``, PR 38,
kept with telemetry off), in the process that ran the cell, after the
window.  The account sums each call's counters under the ordinal of the
request it ran in; the warm-up partition is the process's request 1 and
the window's partitions are the later ones.  A program without the
account, as every commit before PR 38, gives None and the metric is left
out."""

from __future__ import annotations

from statistics import median


def summary():
    try:
        from kaminpar_tpu.refinement import fm
    except ImportError:
        return None
    account = getattr(fm, "fm_account", None)
    return None if account is None else account.summary()


def window_median(counter: str):
    """The median over the window's requests of ``counter`` summed over a
    request's FM calls: 0 for a request without one, None where the
    program keeps no account or no request followed the warm-up."""
    account = summary()
    if account is None:
        return None
    by_request = account["by_request"]
    values = [by_request.get(ordinal, {}).get(counter, 0)
              for ordinal in range(2, account["requests"] + 1)]
    return median(values) if values else None

"""What the four set-up metrics read: the program's own account of its
set-up (``kaminpar_tpu/telemetry/compile_account.summary()``, PR 34), in
the process that ran the cell, after the window.  The warm-up partition
is the process's request 1 and the window's partitions are the later
ones, all on the program's ``time.perf_counter()``; a record is one
executable asked for, with its tracing, lowering and backend (compile or
load) seconds.  A program without the accessor, as every commit before
PR 34, gives None and the metric is left out."""

from __future__ import annotations


def summary():
    try:
        from kaminpar_tpu.telemetry import compile_account
    except ImportError:
        return None
    accessor = getattr(compile_account, "summary", None)
    return None if accessor is None else accessor()


def read(metric):
    """``metric(summary)``, or None where the program keeps no account."""
    account = summary()
    return None if account is None else metric(account)


def trace_lower_s(account: dict):
    """Tracing and lowering seconds of every record up to the end of
    request 1, the records that never reached the backend among them."""
    if account["requests"]["first"] is None:
        return None
    through = account["through_first_request"]
    return through["trace_s"] + through["lower_s"]


def first_request_s(account: dict):
    first = account["requests"]["first"]
    return None if first is None else first["wall_s"]


def setup_unattributed_s(account: dict):
    """Request 1's wall, less what a later request takes (their median),
    less the tracing, lowering and backend seconds booked to request 1:
    what the first request costs that no counter names."""
    first = account["requests"]["first"]
    later = account["requests"]["later_median_wall_s"]
    if first is None or later is None:
        return None
    return (first["wall_s"] - later - first["trace_s"] - first["lower_s"]
            - first["backend_s"])


def package_import_s(account: dict):
    return account["package_import_s"]

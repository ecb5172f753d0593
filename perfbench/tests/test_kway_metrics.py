"""The three readers that came with ``rmat-s16-kway.k64``:
``initial_idle_s`` (idle seconds under ``initial-partitioning`` spans),
``coarsening_levels`` and ``conn_scatter_calls`` (the program's shape
account), on inputs whose answers are known by construction, and on a
program without the account (the parent of the PR that added it):
nothing is read and nothing raises (run by hand)."""

import pytest

from perfbench.tests.test_phase_reduce import _reader

IP = "partitioning.initial-partitioning"


def _phases(*rows):
    return {"phases": {"spans": {path: {"calls": 1, "launches": 0,
                                        "device_s": 0.5, "idle_s": idle}
                                 for path, idle in rows}}}


def test_initial_idle_s_sums_the_spans_at_and_under_the_scope():
    read = _reader("initial_idle_s").read
    run = _phases(("request", 0.25), ("partitioning", 0.5), (IP, 1.0),
                  (IP + ".graph-download", 0.125), (IP + ".ip-native", 2.0),
                  ("partitioning.uncoarsening.extend-partition.ip-native",
                   4.0))
    assert read(run) == 3.125
    assert read(_phases(("request", 0.25))) == 0.0
    assert read({"phases": None}) is None


@pytest.fixture
def account(monkeypatch):
    from kaminpar_tpu.telemetry import compile_account, request_account

    fresh = request_account.RequestAccount(
        request_account.shape_account.counters)
    monkeypatch.setattr(request_account, "shape_account", fresh)
    ordinals = {"open": 0, "begun": 0}
    monkeypatch.setattr(compile_account, "open_request",
                        lambda: ordinals["open"])
    monkeypatch.setattr(compile_account, "requests_begun",
                        lambda: ordinals["begun"])
    return fresh, ordinals


def test_account_readers_take_the_window_median(account):
    fresh, ordinals = account
    levels = _reader("coarsening_levels").read
    scatters = _reader("conn_scatter_calls").read
    assert levels({}) is None and scatters({}) is None  # no request yet
    for ordinal, (n_levels, n_scatters) in enumerate(
            [(7, 3), (7, 2), (7, 2), (6, 0)], start=1):
        ordinals["open"] = ordinals["begun"] = ordinal
        fresh.add({"coarsening_levels": n_levels})
        if n_scatters:
            fresh.add({"conn_scatter_calls": n_scatters})
    ordinals["begun"] = 5  # a window request that counted nothing
    assert levels({}) == 6.5  # median of 7, 7, 6, 0 over requests 2-5
    assert scatters({}) == 1  # median of 2, 2, 0, 0


def test_account_readers_without_the_account(monkeypatch):
    from kaminpar_tpu.telemetry import request_account

    monkeypatch.delattr(request_account, "shape_account")
    assert _reader("coarsening_levels").read({}) is None
    assert _reader("conn_scatter_calls").read({}) is None

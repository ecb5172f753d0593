"""``level0_pad_share``, the reader that came with the input graph's
eighth-octave upload: on accounts whose answers are known by
construction, on a program whose account lacks the level-0 counters
(the parent of the change that added them) and on one without the
account: nothing is read and nothing raises (run by hand)."""

import pytest

from perfbench.tests.test_phase_reduce import _reader


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


def test_level0_pad_share_takes_the_window_median(account):
    fresh, ordinals = account
    read = _reader("level0_pad_share").read
    assert read({}) is None  # no request yet
    for ordinal, (real, padded) in enumerate(
            [(1, 4), (750, 1000), (900, 1000), (500, 1000)], start=1):
        ordinals["open"] = ordinals["begun"] = ordinal
        fresh.add({"level0_edge_slots": real,
                   "level0_padded_edge_slots": padded})
    # the warm-up request (25 % used) is not read: median of 25, 10, 50
    assert read({}) == pytest.approx(25.0)
    # two uploads in one request (rb's top level, a v-cycle) add up
    ordinals["open"] = ordinals["begun"] = 5
    fresh.add({"level0_edge_slots": 100, "level0_padded_edge_slots": 100})
    fresh.add({"level0_edge_slots": 50, "level0_padded_edge_slots": 100})
    assert read({}) == pytest.approx(25.0)  # median of 25, 10, 50, 25


def test_level0_pad_share_on_the_parent(monkeypatch):
    from kaminpar_tpu.telemetry import request_account

    read = _reader("level0_pad_share").read
    parent = request_account.RequestAccount(
        ("coarsening_levels", "conn_scatter_calls"))
    monkeypatch.setattr(request_account, "shape_account", parent)
    assert read({}) is None
    monkeypatch.delattr(request_account, "shape_account")
    assert read({}) is None


def test_level0_pad_share_of_an_upload(account):
    """The upload itself counts: a cycle of 231 nodes has 462 edge
    slots, which pad to 480 of their bucket of 512 (CPU floors)."""
    import numpy as np

    from kaminpar_tpu.graphs.csr import upload_input_graph
    from kaminpar_tpu.graphs.factories import from_edge_list

    fresh, ordinals = account
    ring = np.arange(231)
    g = from_edge_list(231, np.stack([ring, (ring + 1) % 231], axis=1))
    ordinals["open"] = ordinals["begun"] = 2
    dg = upload_input_graph(g)
    assert (g.m, dg.m_pad, dg.m_bucket) == (462, 480, 512)
    assert _reader("level0_pad_share").read({}) == pytest.approx(
        100.0 * 18 / 480)

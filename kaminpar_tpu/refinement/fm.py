"""k-way FM refinement (host).

Analog of kaminpar-shm/refinement/fm/ (FMRefiner + LocalizedFMRefiner,
fm_refiner.cc:48-110): the reference runs parallel localized FM with
thread-local delta partitions and a shared border-node queue.  FM's
priority-queue-driven, one-node-at-a-time control flow has no efficient TPU
mapping (the reference's own Jet paper makes the same observation — Jet is
its bulk-synchronous replacement and runs on device here, ops/jet.py).  FM
therefore stays host-side, behind one entry, `fm_refine_host`, with two
engines:

* **native** (native/fm.cpp, the engine a build with a toolchain runs):
  the reference's *localized batch* FM — regions grown from
  `num_seed_nodes` border seeds against a delta gain overlay, each
  region's best prefix committed — on `threads` workers
  (`ctx.parallel.num_workers`: 1 in every preset but `strong-parallel`;
  more grow a round's regions in parallel and commit them in order with
  exact gains, so the labels do not depend on the thread count).
* **numpy** (`_fm_pass` below; the fallback twin where the library is
  unavailable, and `KAMINPAR_TPU_NO_NATIVE_FM=1`): the reference's
  *sequential* FM structure with a global gain PQ over border nodes,
  best-prefix rollback and the simple stopping rule
  (num_fruitless_moves).  Its per-node gain bookkeeping uses the dense
  gain cache (refinement/gains.HostDenseGainCache, the DenseGainCache
  strategy): an (n, k) connection matrix built once per pass and updated
  incrementally on each move, so best-move queries are O(k) instead of
  O(deg).

Timer scopes (utils/timer.py; each is a profiler span): the caller's
`kway-fm` holds `graph-download` (the level read back), then `fm-native`
or `fm-numpy`, named by the engine that ran (both where the native call
gave up and the twin took over; a native refusal returns from
`fm-native` at once, runs no twin and leaves the partition unchanged),
then `partition-upload` (the padded labels going back to the device).

`fm_account` keeps two of the native engine's counters (`ACCOUNTED`)
for the life of the process, with telemetry off, summed per request
ordinal as `telemetry/compile_account` numbers the requests: what the
benchmark's `fm_cap_refusals` and `fm_undone_moves` read.
"""

from __future__ import annotations

import heapq
import threading
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..context import FMRefinementContext
from ..graphs.csr import DeviceGraph, host_graph_from_device
from ..graphs.host import HostGraph
from ..telemetry import compile_account
from ..telemetry import progress as progress_mod
from ..utils.timer import scoped_timer
from .gains import create_host_gain_cache

_KEEP_REQUESTS = 64  # the last so many requests' sums

#: the native counters the account keeps: what the benchmark's
#: `fm_cap_refusals` and `fm_undone_moves` read
ACCOUNTED = ("cap_refusals", "undone_moves")


class FMAccount:
    """The native FM calls' `ACCOUNTED` counters, summed per request
    ordinal (0 outside any request)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_request: "OrderedDict[int, dict]" = OrderedDict()

    def record(self, stats: dict) -> None:
        ordinal = compile_account.open_request()
        with self._lock:
            entry = self._by_request.get(ordinal)
            if entry is None:
                entry = self._by_request[ordinal] = dict.fromkeys(
                    ACCOUNTED, 0)
                while len(self._by_request) > _KEEP_REQUESTS:
                    self._by_request.popitem(last=False)
            for name in ACCOUNTED:
                entry[name] += stats.get(name, 0)

    def summary(self) -> dict:
        """`{"requests": requests begun, "by_request": {ordinal: {...}}}`;
        a request without an FM call has no entry."""
        with self._lock:
            return {"requests": compile_account.requests_begun(),
                    "by_request": {k: dict(v)
                                   for k, v in self._by_request.items()}}


fm_account = FMAccount()


def fm_refine_host(
    dgraph: DeviceGraph,
    partition,
    k: int,
    max_block_weights,
    ctx: FMRefinementContext,
    seed: int = 0,
    threads: int = 1,
):
    """Refine a device partition with host FM; returns a device partition.

    Runs ctx.num_iterations passes of the native localized batch FM, or
    of the numpy twin where that is unavailable or opted out of: border
    nodes from a global max-gain PQ with best-prefix rollback
    (FMRefiner::refine structure, fm_refiner.cc)."""
    import jax.numpy as jnp

    graph = host_graph_from_device(dgraph)
    n = graph.n
    # explicit copy: jax->numpy views are read-only and the native FM
    # refines the partition in place
    part = np.array(np.asarray(partition)[:n], dtype=np.int32, copy=True)
    max_bw = np.asarray(max_block_weights)[:k].astype(np.int64)

    import os

    @scoped_timer("fm-numpy")
    def _numpy_fm() -> np.ndarray:
        node_w = graph.node_weight_array()
        edge_w = graph.edge_weight_array()
        rng = np.random.default_rng(seed)
        rec = progress_mod.capture()
        t0 = progress_mod.now()
        gains, moves, prefixes = [], [], []
        for _ in range(max(1, ctx.num_iterations)):
            improvement, n_moves, best_prefix = _fm_pass(
                graph, part, node_w, edge_w, max_bw, k, ctx, rng
            )
            if rec:
                gains.append(int(improvement))
                moves.append(int(n_moves))
                prefixes.append(int(best_prefix))
            if improvement <= 0:
                break
        if rec:
            # host algorithm: per-pass series, same stream and shape as
            # the device loops' buffers (gain = committed cut delta,
            # moved = attempted moves, best_prefix = kept moves)
            progress_mod.emit_host(
                "fm",
                {"gain": gains, "moved": moves, "best_prefix": prefixes},
                t0=t0, engine="numpy",
            )
        return part

    if os.environ.get("KAMINPAR_TPU_NO_NATIVE_FM", "") == "1":
        # explicit opt-out, not a degradation: no fallback event
        part = _numpy_fm()
    else:
        from ..resilience import (
            NativeUnavailable,
            RefinerRefused,
            with_fallback,
        )

        def _native_fm() -> np.ndarray:
            from .. import native

            t0 = progress_mod.now()
            # native localized BATCH FM (fm.cpp — the reference's
            # parallel localized scheme: seeded regions grown against a
            # delta gain overlay, best prefixes committed, on `threads`
            # workers); refines `part` in place
            stats = {}
            with scoped_timer("fm-native"):
                improvement = native.fm_refine(
                    graph, part, k, max_bw, ctx, seed, threads=threads,
                    stats=stats,
                )
            if stats:
                fm_account.record(stats)
            if improvement is None:
                raise NativeUnavailable(
                    "native FM library unavailable (build failed or "
                    "no toolchain)"
                )
            if improvement == native.FM_REFUSED:
                # fm_refine already recorded the fm-refused telemetry
                # event; surface the refusal as a structured exception
                # so the policy wrapper routes it — NOT as zero gain
                raise RefinerRefused(
                    f"native FM refused to run at n={graph.n}, k={k}"
                )
            if progress_mod.capture():
                # the C engine reports one total: a single-point series
                # keeps native and numpy runs alignable in the report
                progress_mod.emit_host(
                    "fm", {"gain": [int(improvement)]}, t0=t0,
                    engine="native",
                )
            return part

        def _fm_fallback(exc) -> np.ndarray:
            # a REFUSAL (k above the sparse engine's 16-bit tag limit
            # with the dense table unaffordable) returns the partition
            # unchanged: the numpy pass's dense (n, k) gain cache is
            # unaffordable at exactly these k.  Everything else
            # (unavailable native lib, OOM) runs the numpy FM twin.
            if isinstance(exc, RefinerRefused) and not exc.injected:
                return part
            return _numpy_fm()

        part = with_fallback(_native_fm, _fm_fallback, site="native-fm")

    with scoped_timer("partition-upload"):
        padded = np.zeros(dgraph.n_pad, dtype=np.int32)
        padded[:n] = part
        return jnp.asarray(padded)


def _fm_pass(graph, part, node_w, edge_w, max_bw, k, ctx, rng):
    """One FM pass; returns (committed gain, attempted moves, kept
    best-prefix length) — the per-pass progress triple."""
    n = graph.n
    src = graph.edge_sources()
    bw = np.zeros(k, dtype=np.int64)
    np.add.at(bw, part, node_w)

    # border nodes: incident to a cut edge
    cut_edge = part[src] != part[graph.adjncy]
    border = np.unique(src[cut_edge])
    if len(border) == 0:
        return 0, 0, 0

    cache = create_host_gain_cache(graph, part, k)
    pq = []
    tie = rng.random(n)
    in_pq = np.zeros(n, dtype=bool)
    for u in border:
        mv = cache.best_move(int(u), part, node_w, bw, max_bw)
        if mv is not None:
            heapq.heappush(pq, (-mv[0], tie[u], int(u), mv[1]))
            in_pq[u] = True

    locked = np.zeros(n, dtype=bool)
    moves = []
    cur_delta = 0
    best_delta = 0
    best_len = 0
    fruitless = 0

    while pq:
        negg, _, u, t = heapq.heappop(pq)
        if locked[u]:
            continue
        # gains may be stale: re-query the cache and re-push if changed
        mv = cache.best_move(u, part, node_w, bw, max_bw)
        if mv is None:
            continue
        gain, t = mv
        if -negg != gain:
            heapq.heappush(pq, (-gain, tie[u], u, t))
            continue
        if bw[t] + node_w[u] > max_bw[t]:
            continue

        b = int(part[u])
        part[u] = t
        bw[b] -= node_w[u]
        bw[t] += node_w[u]
        cache.apply_move(u, b, t)
        locked[u] = True
        cur_delta += gain
        moves.append((u, b))
        if cur_delta > best_delta:
            best_delta = cur_delta
            best_len = len(moves)
            fruitless = 0
        else:
            fruitless += 1
            if fruitless >= ctx.num_fruitless_moves:
                break

        # re-queue unlocked neighbors (their cached rows just changed)
        lo, hi = int(graph.xadj[u]), int(graph.xadj[u + 1])
        for v in graph.adjncy[lo:hi]:
            v = int(v)
            if not locked[v]:
                mv = cache.best_move(v, part, node_w, bw, max_bw)
                if mv is not None:
                    heapq.heappush(pq, (-mv[0], tie[v], v, mv[1]))

    # rollback to best prefix
    for u, b in moves[best_len:]:
        t = int(part[u])
        part[u] = b
        bw[t] -= node_w[u]
        bw[b] += node_w[u]
    return best_delta, len(moves), best_len

"""Shared shape-bucketing + bounded-cache policy (ROADMAP item 5's
"refactor unlock").

Two concerns that every scaling direction (hot-kernel fusion, the
serving layer, multi-chip scale-out) shares used to be scattered:

  * the **shape-bucket padding policy** — device arrays are padded to a
    bounded family of shapes so XLA executables are reused across
    levels, graphs, and requests.  ``pad_size`` (previously
    ``utils/math.pad_size``; re-exported there for its existing callers)
    is THE policy: next power of two with a granularity floor, giving
    O(log n) distinct compiled shapes per graph.  The request's own
    graph (level 0) alone rounds finer, to an eighth of that bucket
    (``input_pad_size``), and every path decision reads the bucket
    (``shape_bucket``) rather than the width.  ``bucket_key``
    compacts a request's (n, m, k) into the executable-identity triple
    the jit cache effectively keys on, so executable *reuse* becomes an
    observable hit-rate instead of an invisible property of jax
    internals.
  * the **bounded cache policy** — :class:`BoundedCache`, an LRU with an
    explicit entry cap AND a byte budget, so caches grown by sustained
    traffic (the serving result cache, future plan/executable caches)
    stay bounded instead of OOMing the host after a few hours of load.
    Hit/miss/eviction counters are first-class (`stats()`), and the
    serving layer surfaces them in the run report and the BENCH trend.

Import-light by design (numpy only): the serving layer pulls this in
before any backend exists, and ``utils/math`` re-exports ``pad_size``
from here at interpreter start.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Dict, Hashable, Optional, Tuple


def _ceil2(x: int) -> int:
    """Smallest power of two >= x (utils/math.ceil2's twin; duplicated
    two lines here so this module stays import-cycle-free — utils/math
    re-exports pad_size from HERE)."""
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


# --- pad policy modes (resilience/memory.py's rung-① lever) ---------------
#
# "bucketed" (the default, and the ONLY mode unless a memory-pressure
# governor engages) is the executable-reuse policy below: next power of
# two over a granularity floor.  "tight" trades that reuse for memory:
# shapes round up only to the granularity multiple, so a padded buffer
# carries at most granularity-1 wasted slots instead of up to ~2x.  The
# mode is thread-local (each run's recovery ladder owns its own policy)
# and scope-managed, so the default path is byte-identical to the
# pre-governor behavior — the jaxpr-equality pins rely on that.

_pad_mode = threading.local()

PAD_POLICIES = ("bucketed", "tight")


def pad_policy() -> str:
    """The calling thread's active pad policy ("bucketed" by default)."""
    return getattr(_pad_mode, "mode", "bucketed")


@contextmanager
def pad_policy_scope(mode: str):
    """Run a block under a pad policy (restores the previous mode on
    exit; used by the OOM recovery ladder around each rung attempt)."""
    if mode not in PAD_POLICIES:
        raise ValueError(f"unknown pad policy {mode!r}")
    prev = pad_policy()
    _pad_mode.mode = mode
    try:
        yield
    finally:
        _pad_mode.mode = prev


def pad_size(x: int, granularity: int = 256) -> int:
    """Shape-bucketed padding: next power of two, but at least x rounded up to
    `granularity`.  Bounds the number of distinct compiled shapes per graph to
    O(log n) as the multilevel hierarchy shrinks the graph ~2x per level.

    Under the "tight" pad policy (pad_policy_scope; engaged only by the
    memory-pressure recovery ladder) the power-of-two step is dropped:
    shapes round up to the next `granularity` multiple only — no-headroom
    buckets that trade executable reuse for device bytes."""
    if x <= granularity:
        return granularity
    if pad_policy() == "tight":
        return ((x + granularity - 1) // granularity) * granularity
    return _ceil2(x)


def input_pad_size(x: int, granularity: int = 256) -> int:
    """The shape bucket of the request's own graph (level 0): pad_size's
    power-of-two bucket p cut into eighths, the smallest multiple of
    p // 16 that holds x.  x in (p/2, p] has eight such buckets, so an
    edge-wide pass of level 0 pays for at most p/16 pad slots instead of
    up to p/2, while shape_bucket() of the result is p: every gate and
    row buffer that reads shape_bucket() sees the bucket pad_size gives.
    At or below the floor, and under the "tight" pad policy, this is
    pad_size: a level 0 under the floor shares its floor-wide programs
    with the coarse levels.  Coarse levels, subgraphs and re-uploads
    keep pad_size: the eighths cost one set of executables per eighth
    an input falls in, and only the input graph is uploaded once per
    request."""
    p = pad_size(x, granularity)
    if p <= granularity or pad_policy() == "tight":
        return p
    step = max(p // 16, 1)
    return -(-x // step) * step


def shape_bucket(width: int) -> int:
    """The power-of-two bucket of a padded width: the width itself on
    every pad_size shape, the pad_size bucket of an input_pad_size
    shape.  Path gates and buffer widths read this, never the array
    width, so an eighth-octave level 0 takes the paths and buffers of
    its power-of-two bucket."""
    return _ceil2(width)


def pad_k(k: int) -> int:
    """The block-count bucket: k rounded up to a power of two (>= 2).
    Mirrors ops/segments.pad_k_bucket, which additionally builds the
    zero-capacity phantom block weights on device — this host-side twin
    exists so bucket identity can be computed without importing jax."""
    return max(2, 1 << (int(k) - 1).bit_length())


def bucket_key(n: int, m: int, k: int) -> Tuple[int, int, int]:
    """The executable-identity triple of a request: padded node slots,
    padded edge slots, padded block count.  Two requests with the same
    bucket key drive the device phases through the same compiled
    programs (same shapes, same k tables) — the serving layer counts
    reuse of these keys as its executable-cache hit rate.  The node and
    edge slots are the input graph's (input_pad_size): the request's
    level-0 executables key on them."""
    return (input_pad_size(int(n) + 1), input_pad_size(max(int(m), 1)),
            pad_k(k))


def record_padding(n=None, n_pad=None, m=None, m_pad=None,
                   k=None, k_pad=None) -> None:
    """Report one padded launch shape (real vs padded element counts,
    per axis) to the performance observatory.

    The shape-bucket policy lives here, so this is where every pad site
    (device CSR upload, contraction, subgraph slicing, the k bucket,
    dist shards) reports what fraction of the launch was padding — the
    run report's `perf.pad_waste` rows.  Import-light contract intact:
    telemetry is imported lazily and the call is a no-op (one bool
    check) unless the perf layer is enabled."""
    try:
        from .telemetry import perf
    except Exception:
        return
    if perf.enabled():
        perf.record_padding(
            n=n, n_pad=n_pad, m=m, m_pad=m_pad, k=k, k_pad=k_pad
        )


def record_transfer(direction, nbytes, kind="") -> None:
    """Report one host<->device transfer (direction "h2d" or "d2h",
    payload size, chokepoint kind) to the execution ledger's transfer
    leg — the same lazy-import forwarding contract as record_padding,
    so import-light callers (graphs/csr upload, chunk stores) meter
    their boundary traffic without importing telemetry eagerly."""
    try:
        from .telemetry import ledger
    except Exception:
        return
    ledger.transfer(direction, nbytes, kind=kind)


class BoundedCache:
    """A thread-safe LRU cache with an entry cap and a byte budget.

    ``put(key, value, nbytes)`` evicts least-recently-used entries until
    both bounds hold; a single value larger than the byte budget is
    refused (``stats()['oversize']`` counts these) rather than evicting
    the whole cache for one entry.  ``get`` returns None on miss —
    callers that need to distinguish a cached None should wrap values.
    """

    def __init__(self, max_entries: int = 128,
                 max_bytes: int = 256 << 20) -> None:
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Tuple[Any, int]]" = (
            OrderedDict()
        )
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.oversize = 0
        # eviction-cause split: `capacity` evictions keep the configured
        # bounds (put overflow), `pressure` evictions were demanded by
        # the memory governor (evict_to) — the serving report separates
        # the two so a shrinking cache under HBM pressure is tellable
        # from ordinary LRU turnover
        self.evictions_capacity = 0
        self.evictions_pressure = 0
        # per-window twins (begin_window): a long-lived serving process
        # reports fresh per-window rates instead of lifetime averages
        # that asymptotically freeze under sustained traffic
        self.w_hits = 0
        self.w_misses = 0
        self.w_evictions = 0
        self.w_oversize = 0
        self.w_evictions_capacity = 0
        self.w_evictions_pressure = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                self.w_misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            self.w_hits += 1
            return ent[0]

    def put(self, key: Hashable, value: Any, nbytes: int = 0) -> bool:
        """Insert (replacing any existing entry); returns False when the
        value alone exceeds the byte budget and was refused."""
        nbytes = int(nbytes)
        with self._lock:
            if nbytes > self.max_bytes:
                self.oversize += 1
                self.w_oversize += 1
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (value, nbytes)
            self._bytes += nbytes
            while (
                len(self._entries) > self.max_entries
                or self._bytes > self.max_bytes
            ):
                _, (_, dropped) = self._entries.popitem(last=False)
                self._bytes -= dropped
                self.evictions += 1
                self.w_evictions += 1
                self.evictions_capacity += 1
                self.w_evictions_capacity += 1
            return True

    def evict(self, key: Hashable) -> bool:
        """Drop one entry (the serving-cache fault's forced-evict mode);
        returns True when something was removed."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is None:
                return False
            self._bytes -= ent[1]
            self.evictions += 1
            self.evictions_capacity += 1
            return True

    def evict_to(self, target_bytes: int, cause: str = "pressure") -> int:
        """Shed least-recently-used entries until the cache holds at most
        ``target_bytes`` (0 sheds every byte-carrying entry; zero-byte
        entries hold no device memory and are left alone).  Returns the
        bytes freed.  The
        memory governor's pressure hook and the OOM recovery ladder call
        this with cause="pressure" — those evictions are counted apart
        from ordinary capacity turnover (`stats()['evictions_pressure']`)
        so an operator can see what HBM pressure cost the cache."""
        target_bytes = max(0, int(target_bytes))
        freed = 0
        with self._lock:
            carrying = [k for k, (_, nb) in self._entries.items() if nb > 0]
            for key in carrying:
                if self._bytes <= target_bytes:
                    break
                _, dropped = self._entries.pop(key)
                self._bytes -= dropped
                freed += dropped
                self.evictions += 1
                self.w_evictions += 1
                if cause == "pressure":
                    self.evictions_pressure += 1
                    self.w_evictions_pressure += 1
                else:
                    self.evictions_capacity += 1
                    self.w_evictions_capacity += 1
        return freed

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def begin_window(self) -> None:
        """Zero the per-window counters (lifetime totals are kept) —
        called by the serving layer's `reset_records()` so each exported
        report window carries its own hit rate."""
        with self._lock:
            self.w_hits = 0
            self.w_misses = 0
            self.w_evictions = 0
            self.w_oversize = 0
            self.w_evictions_capacity = 0
            self.w_evictions_pressure = 0

    def stats(self) -> Dict[str, Any]:
        """Counter snapshot (the run report's cache subsections):
        lifetime totals plus the current window's counters."""
        with self._lock:
            lookups = self.hits + self.misses
            w_lookups = self.w_hits + self.w_misses
            return {
                "entries": len(self._entries),
                "bytes": int(self._bytes),
                "max_entries": self.max_entries,
                "max_bytes": int(self.max_bytes),
                "hits": int(self.hits),
                "misses": int(self.misses),
                "evictions": int(self.evictions),
                "evictions_capacity": int(self.evictions_capacity),
                "evictions_pressure": int(self.evictions_pressure),
                "oversize": int(self.oversize),
                "hit_rate": (
                    round(self.hits / lookups, 4) if lookups else 0.0
                ),
                "window": {
                    "hits": int(self.w_hits),
                    "misses": int(self.w_misses),
                    "evictions": int(self.w_evictions),
                    "evictions_capacity": int(self.w_evictions_capacity),
                    "evictions_pressure": int(self.w_evictions_pressure),
                    "oversize": int(self.w_oversize),
                    "hit_rate": (
                        round(self.w_hits / w_lookups, 4)
                        if w_lookups else 0.0
                    ),
                },
            }


class BucketTracker:
    """Executable-reuse accounting over :func:`bucket_key` triples.

    jax's jit cache is the actual executable store; what it never tells
    you is the *reuse rate* under a request stream.  The tracker counts
    the first sighting of a bucket as a miss (a compile) and every later
    sighting as a hit (executable reuse) — the compile-accounting layer
    (telemetry/compile_account.py) confirms the attribution from the
    other side."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seen: Dict[Tuple[int, int, int], int] = {}
        self.hits = 0
        self.misses = 0
        # per-window twins (begin_window) — see BoundedCache
        self.w_hits = 0
        self.w_misses = 0

    def observe(self, n: int, m: int, k: int) -> Tuple[int, int, int]:
        """Record one request's bucket; returns the key."""
        key = bucket_key(n, m, k)
        with self._lock:
            if key in self._seen:
                self._seen[key] += 1
                self.hits += 1
                self.w_hits += 1
            else:
                self._seen[key] = 1
                self.misses += 1
                self.w_misses += 1
        return key

    def begin_window(self) -> None:
        """Zero the per-window counters (bucket sightings and lifetime
        totals are kept)."""
        with self._lock:
            self.w_hits = 0
            self.w_misses = 0

    def per_bucket(self) -> Dict[str, int]:
        """Lifetime sightings per bucket ("n/m/k" string keys) — the
        serving latency rollup joins these with its per-class
        histograms."""
        with self._lock:
            return {
                "/".join(str(x) for x in key): int(count)
                for key, count in self._seen.items()
            }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            lookups = self.hits + self.misses
            w_lookups = self.w_hits + self.w_misses
            return {
                "buckets": len(self._seen),
                "hits": int(self.hits),
                "misses": int(self.misses),
                "hit_rate": (
                    round(self.hits / lookups, 4) if lookups else 0.0
                ),
                "window": {
                    "hits": int(self.w_hits),
                    "misses": int(self.w_misses),
                    "hit_rate": (
                        round(self.w_hits / w_lookups, 4)
                        if w_lookups else 0.0
                    ),
                },
            }


def full_graph_digest(graph) -> str:
    """Exact structural identity of a graph: a hash of the FULL
    adjacency and both weight arrays.  The checkpoint layer's sampling
    ``graph_fingerprint`` is deliberately O(1) — resume only needs to
    catch operator error — but a *result cache* replays stored answers
    to matching keys, so its identity must cover every edge and weight:
    two graphs that differ only in interior edges (beyond the sampled
    head/tail) or in edge weights (which the sampling fingerprint never
    reads) must never share a cached partition.  Compressed containers
    are hashed as their raw encoded byte streams — same bytes, same
    graph — so no decode pass is needed; either way the cost is one
    sequential sweep over host memory, noise next to a partition."""
    import hashlib

    import numpy as np

    # dynamic graph sessions (dynamic/session.py) stamp their evolving
    # delta-chain digest onto the graph object: the chain already covers
    # the base adjacency (hashed once at register) plus every applied
    # DeltaBatch, so a mutate costs O(delta), never a fresh O(m) sweep.
    # The "dyn:" domain prefix keeps chain digests disjoint from the raw
    # hex digests below — a (possibly poisoned) chain hash can never
    # alias the exact digest of a differing plain graph.
    chain = getattr(graph, "_chain_digest", None)
    if chain is not None:
        return str(chain)
    h = hashlib.sha256()

    def _arr(a) -> None:
        if a is None:
            h.update(b"\x00none")
            return
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())

    h.update(f"n={int(graph.n)};m={int(graph.m)};".encode())
    if hasattr(graph, "data") and hasattr(graph, "offsets"):
        # CompressedHostGraph: raw codec streams are the exact identity
        h.update(str(getattr(graph, "codec", "?")).encode())
        for name in ("xadj", "offsets", "data", "node_weights",
                     "edge_weights", "wdata", "woffsets"):
            _arr(getattr(graph, name, None))
    else:
        _arr(np.asarray(graph.xadj, dtype=np.int64))
        _arr(graph.adjncy)
        _arr(getattr(graph, "node_weights", None))
        _arr(getattr(graph, "edge_weights", None))
    return h.hexdigest()[:24]


def result_cache_key(graph, ctx) -> Tuple[str, str]:
    """The (graph identity, ctx fingerprint) a cached result is valid
    for.  The graph identity is the PR-5 sampling ``graph_fingerprint``
    (so the cache and the resume machinery agree on the cheap prefix)
    strengthened with :func:`full_graph_digest` — the sampling
    fingerprint alone ignores edge weights and interior structure, which
    a replaying cache cannot afford.  The ctx fingerprint covers seed,
    k, epsilon, preset and every algorithm knob, and excludes the
    resilience/debug subtrees — a per-request deadline does not fork the
    cache key."""
    from .resilience.checkpoint import ctx_fingerprint, graph_fingerprint

    return (
        graph_fingerprint(graph) + ":" + full_graph_digest(graph),
        ctx_fingerprint(ctx),
    )

"""Device-resident padded CSR graph (the TPU twin of CSRGraph).

Design (SURVEY.md §7 step 1): a pytree of device arrays with *padded, shape-
bucketed* sizes so the multilevel hierarchy (graph shrinks ~2x per level)
re-uses O(log n) compiled executables instead of recompiling per level.
Actual sizes `n`/`m` are traced int32 scalars; pad slots are inert:

  * node pad slots: weight 0, degree 0 (row_ptr clamped to m);
  * edge pad slots: src = dst = n_pad - 1 (a guaranteed-pad node), weight 0.

With that convention most kernels need no explicit masks — zero-weight edges
between pad nodes contribute nothing to ratings, cuts, or contractions.
The builder always pads n to at least n+1 so slot n_pad-1 is never a real
node.

Unlike the reference's lambda-based adjacency iteration
(kaminpar-shm/datastructures/csr_graph.h:171 adjacent_nodes), device kernels
work on the flat COO view (`src`, `dst` = col) — gather/segment programs are
the TPU-native idiom; XLA maps them onto vectorized scatter/sort units rather
than per-node loops.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..caching import pad_size
from ..utils.timer import scoped_timer
from .host import HostGraph

from ..dtypes import ACC_DTYPE, WEIGHT_DTYPE  # int64 under
# KAMINPAR_TPU_64BIT — see kaminpar_tpu/dtypes.py; ids stay int32 like
# the reference's default 32-bit ID build, CMakeLists.txt:67-75

NODE_DTYPE = jnp.int32


@jax.tree_util.register_dataclass
@dataclass
class DeviceGraph:
    """Padded CSR + COO graph on device.

    Fields (all jnp arrays):
      row_ptr : i32[n_pad + 1]  CSR offsets; row_ptr[i] = m for i >= n
      src     : i32[m_pad]      COO edge sources (pad: n_pad - 1)
      dst     : i32[m_pad]      COO edge targets == CSR adjncy (pad: n_pad - 1)
      edge_w  : i32[m_pad]      edge weights (pad: 0)
      node_w  : i32[n_pad]      node weights (pad: 0)
      n, m    : i32 scalars     true counts (traced, not static)
    """

    row_ptr: jax.Array
    src: jax.Array
    dst: jax.Array
    edge_w: jax.Array
    node_w: jax.Array
    n: jax.Array
    m: jax.Array

    @property
    def n_pad(self) -> int:
        return self.node_w.shape[0]

    @property
    def m_pad(self) -> int:
        return self.src.shape[0]

    @property
    def degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    def node_mask(self) -> jax.Array:
        return jnp.arange(self.n_pad, dtype=NODE_DTYPE) < self.n

    def edge_mask(self) -> jax.Array:
        return jnp.arange(self.m_pad, dtype=NODE_DTYPE) < self.m

    def total_node_weight(self) -> jax.Array:
        return jnp.sum(self.node_w.astype(ACC_DTYPE))

    def total_edge_weight(self) -> jax.Array:
        return jnp.sum(self.edge_w.astype(ACC_DTYPE))


def shape_floors() -> tuple[int, int]:
    """(n_floor, m_floor) shape-bucket floors for device graphs.

    Off the CPU every distinct shape bucket costs an XLA compile, and a
    limping coarsening tail (n shrinking ~10% per level) otherwise mints
    a fresh m_pad bucket per level.  Padding every small level into ONE
    floor bucket trades extra warm work per call for one compile per
    avoided bucket; the floors were chosen on an earlier backend and
    have not been re-measured on the current chip (ROADMAP A0d).  CPU
    (tests) keeps small floors so tiny unit-test graphs stay tiny."""
    from ..utils import platform

    try:
        backend = platform.default_backend()
    except Exception:
        backend = "cpu"
    if backend == "cpu":
        return 256, 256
    return 1 << 13, 1 << 20


def device_graph_from_host(
    graph: HostGraph,
    n_pad: Optional[int] = None,
    m_pad: Optional[int] = None,
    device=None,
) -> DeviceGraph:
    """Upload a HostGraph into the padded device layout."""
    # `device-oom` chaos injection point: an allocator-shaped failure at
    # upload propagates to the facade's recovery ladder
    # (resilience/memory.py), which retries at the next rung
    from ..resilience import maybe_inject

    maybe_inject("device-oom")
    from ..caching import record_padding

    n, m = graph.n, graph.m
    n_floor, m_floor = shape_floors()
    n_pad = n_pad if n_pad is not None else pad_size(n + 1, n_floor)
    m_pad = m_pad if m_pad is not None else pad_size(max(m, 1), m_floor)
    if n_pad < n + 1 or m_pad < m:
        raise ValueError("pad sizes too small")
    record_padding(n=n + 1, n_pad=n_pad, m=m, m_pad=m_pad)

    row_ptr = np.full(n_pad + 1, m, dtype=np.int32)
    row_ptr[: n + 1] = graph.xadj.astype(np.int32)

    pad_node = n_pad - 1
    src = np.full(m_pad, pad_node, dtype=np.int32)
    dst = np.full(m_pad, pad_node, dtype=np.int32)
    edge_w = np.zeros(m_pad, dtype=np.dtype(WEIGHT_DTYPE))
    src[:m] = graph.edge_sources()
    dst[:m] = graph.adjncy
    edge_w[:m] = graph.edge_weight_array().astype(np.dtype(WEIGHT_DTYPE))

    node_w = np.zeros(n_pad, dtype=np.dtype(WEIGHT_DTYPE))
    node_w[:n] = graph.node_weight_array().astype(np.dtype(WEIGHT_DTYPE))

    from ..caching import record_transfer

    record_transfer(
        "h2d",
        row_ptr.nbytes + src.nbytes + dst.nbytes + edge_w.nbytes
        + node_w.nbytes,
        kind="csr-upload",
    )
    put = partial(jax.device_put, device=device)
    return DeviceGraph(
        row_ptr=put(row_ptr),
        src=put(src),
        dst=put(dst),
        edge_w=put(edge_w),
        node_w=put(node_w),
        n=put(np.int32(n)),
        m=put(np.int32(m)),
    )


def device_graph_from_compressed(
    cgraph,
    n_pad: Optional[int] = None,
    m_pad: Optional[int] = None,
    chunk_nodes: int = 1 << 18,
) -> DeviceGraph:
    """Upload a CompressedHostGraph into the padded device layout WITHOUT
    ever materializing the full CSR on the host (TeraPart compute parity:
    the reference partitions directly from compressed neighborhoods,
    ref: kaminpar-common/graph_compression/compressed_neighborhoods.h:52-60
    + kaminpar-shm/datastructures/compressed_graph.h:30.  XLA kernels
    need flat device arrays, so "directly" on a TPU means the DECODE
    streams: node-range chunks are decoded (decode_range), uploaded, and
    concatenated ON DEVICE — peak host memory is the compressed streams
    + one chunk + O(n), never the flat edge list).

    The resulting DeviceGraph is bitwise identical to
    device_graph_from_host(cgraph.decode()), so downstream kernels and
    compile caches are untouched."""
    # `compressed-stream` degradation site: a failure here (device OOM
    # mid-stream, injected chaos fault) propagates to the facade's
    # with_fallback wrapper, which decodes to the plain host CSR and
    # re-partitions (kaminpar._partition_core_resilient)
    from ..resilience import maybe_inject

    maybe_inject("compressed-stream")
    n, m = cgraph.n, cgraph.m
    n_floor, m_floor = shape_floors()
    n_pad = n_pad if n_pad is not None else pad_size(n + 1, n_floor)
    m_pad = m_pad if m_pad is not None else pad_size(max(m, 1), m_floor)
    if n_pad < n + 1 or m_pad < m:
        raise ValueError("pad sizes too small")
    from ..caching import record_padding

    record_padding(n=n + 1, n_pad=n_pad, m=m, m_pad=m_pad)
    pad_node = n_pad - 1

    # O(n) arrays come straight from the (uncompressed) offsets
    xadj = np.asarray(cgraph.xadj, dtype=np.int64)
    row_ptr = np.full(n_pad + 1, m, dtype=np.int32)
    row_ptr[: n + 1] = xadj.astype(np.int32)
    node_w = np.zeros(n_pad, dtype=np.dtype(WEIGHT_DTYPE))
    node_w[:n] = cgraph.node_weight_array().astype(np.dtype(WEIGHT_DTYPE))

    src_parts, dst_parts, w_parts = [], [], []
    uploaded_bytes = row_ptr.nbytes + node_w.nbytes
    for v0 in range(0, n, chunk_nodes):
        v1 = min(n, v0 + chunk_nodes)
        xr, adj, ew = cgraph.decode_range(v0, v1)
        deg = np.diff(np.asarray(xr, dtype=np.int64))
        src_c = np.repeat(
            np.arange(v0, v1, dtype=np.int32), deg
        )
        uploaded_bytes += 2 * src_c.nbytes + (
            0 if ew is None
            else len(src_c) * np.dtype(WEIGHT_DTYPE).itemsize
        )
        src_parts.append(jax.device_put(src_c))
        dst_parts.append(jax.device_put(np.asarray(adj, dtype=np.int32)))
        if ew is None:
            w_parts.append(
                jnp.ones(len(src_c), dtype=np.dtype(WEIGHT_DTYPE))
            )
        else:
            w_parts.append(
                jax.device_put(
                    np.asarray(ew, dtype=np.dtype(WEIGHT_DTYPE))
                )
            )
        del xr, adj, ew, src_c  # keep the host high-water at one chunk

    def assemble(parts, fill, dtype):
        tail = jnp.full(m_pad - m, fill, dtype=dtype)
        return jnp.concatenate(list(parts) + [tail]) if m_pad > m else (
            jnp.concatenate(parts)
        )

    src = assemble(src_parts, pad_node, jnp.int32)
    dst = assemble(dst_parts, pad_node, jnp.int32)
    edge_w = assemble(w_parts, 0, np.dtype(WEIGHT_DTYPE))
    from ..caching import record_transfer

    record_transfer("h2d", uploaded_bytes, kind="csr-upload")
    return DeviceGraph(
        row_ptr=jax.device_put(row_ptr),
        src=src,
        dst=dst,
        edge_w=edge_w,
        node_w=jax.device_put(node_w),
        n=jax.device_put(np.int32(n)),
        m=jax.device_put(np.int32(m)),
    )


def host_graph_from_device(graph: DeviceGraph) -> HostGraph:
    """Download a DeviceGraph back into a compact HostGraph (DLPack-free copy;
    used when the coarsest graph moves to the CPU initial partitioner, per
    BASELINE.json's north star)."""
    # a readback scope of its own: the host blocks on the device here,
    # whichever phase asks for the graph
    with scoped_timer("graph-download", sync=True):
        n = int(graph.n)
        m = int(graph.m)
        xadj = np.asarray(graph.row_ptr[: n + 1], dtype=np.int64)
        adjncy = np.asarray(graph.dst[:m], dtype=np.int32)
        edge_w = np.asarray(graph.edge_w[:m], dtype=np.int64)
        node_w = np.asarray(graph.node_w[:n], dtype=np.int64)
    from ..caching import record_transfer

    record_transfer(
        "d2h",
        xadj.nbytes + adjncy.nbytes + edge_w.nbytes + node_w.nbytes,
        kind="csr-download",
    )
    return HostGraph(
        xadj=xadj,
        adjncy=adjncy,
        node_weights=None if (node_w == 1).all() else node_w,
        edge_weights=None if m == 0 or (edge_w == 1).all() else edge_w,
    )


# ---------------------------------------------------------------------------
# CSR invariant checker (debug; the output gate's and the chaos suite's
# structural validator)
# ---------------------------------------------------------------------------

ASSERTS_ENV = "KAMINPAR_TPU_ASSERTS"


class CSRInvariantError(ValueError):
    """csr.validate found a structural violation (message says which)."""


def asserts_enabled() -> bool:
    """KAMINPAR_TPU_ASSERTS=1 turns on the debug invariant sweeps
    (maybe_validate at the output gate and at upload boundaries); heavy
    KAMINPAR_TPU_ASSERTION_LEVEL implies it."""
    import os

    if os.environ.get(ASSERTS_ENV, "") == "1":
        return True
    from ..utils.assertions import heavy_assertions_enabled

    return heavy_assertions_enabled()


def maybe_validate(graph, undirected: bool = True, where: str = "") -> None:
    """validate() gated behind KAMINPAR_TPU_ASSERTS=1 (free otherwise)."""
    if not asserts_enabled():
        return
    try:
        validate(graph, undirected=undirected)
    except CSRInvariantError as e:
        raise CSRInvariantError(
            f"{e}{' (at ' + where + ')' if where else ''}"
        ) from None


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise CSRInvariantError(what)


def validate(graph, undirected: bool = True) -> None:
    """Structural CSR invariants for HostGraph, CompressedHostGraph, or
    DeviceGraph; raises CSRInvariantError naming the violated invariant.

    Checks (the graph_validator.cc analog plus this pipeline's dtype and
    padding policy):
      * offsets: start at 0, non-decreasing (not ragged), end at m;
      * adjacency ids in [0, n);
      * dtype policy: int32 ids, int64 host offsets/weights,
        WEIGHT_DTYPE device weights (dtypes.py);
      * undirected graphs: every edge's reverse twin is present;
      * DeviceGraph padding: pad nodes weightless and degree-free, pad
        edges parked on the guaranteed-pad node with weight 0, src
        consistent with row_ptr.
    """
    from .compressed import CompressedHostGraph
    from .host import HostGraph

    if isinstance(graph, CompressedHostGraph):
        return _validate_host_arrays(
            np.asarray(graph.xadj, dtype=np.int64),
            graph.decode().adjncy,
            graph.n,
            undirected,
        )
    if isinstance(graph, HostGraph):
        xadj = np.asarray(graph.xadj)
        _require(
            np.issubdtype(xadj.dtype, np.integer),
            f"dtype policy: xadj must be integer, got {xadj.dtype}",
        )
        _require(
            graph.adjncy.dtype == np.int32,
            f"dtype policy: adjncy must be int32, got {graph.adjncy.dtype}",
        )
        for name in ("node_weights", "edge_weights"):
            w = getattr(graph, name)
            _require(
                w is None or np.issubdtype(np.asarray(w).dtype, np.integer),
                f"dtype policy: {name} must be integer",
            )
        return _validate_host_arrays(
            xadj.astype(np.int64), graph.adjncy, graph.n, undirected,
            edge_w=None if graph.edge_weights is None
            else np.asarray(graph.edge_weights),
        )
    # DeviceGraph
    _require(
        graph.row_ptr.dtype == jnp.int32
        and graph.src.dtype == jnp.int32
        and graph.dst.dtype == jnp.int32,
        "dtype policy: device ids must be int32",
    )
    wdt = jnp.dtype(WEIGHT_DTYPE)
    _require(
        graph.edge_w.dtype == wdt and graph.node_w.dtype == wdt,
        f"dtype policy: device weights must be {wdt}",
    )
    n, m = int(graph.n), int(graph.m)
    n_pad, m_pad = graph.n_pad, graph.m_pad
    _require(n_pad >= n + 1, "padding: n_pad must exceed n (pad node)")
    row_ptr = np.asarray(graph.row_ptr)
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    _require(
        (row_ptr[n:] == m).all(),
        "padding: row_ptr pad slots must be clamped to m",
    )
    _require(
        (src[m:] == n_pad - 1).all() and (dst[m:] == n_pad - 1).all(),
        "padding: pad edges must be parked on the pad node",
    )
    _require(
        (np.asarray(graph.edge_w)[m:] == 0).all(),
        "padding: pad edges must have weight 0",
    )
    _require(
        (np.asarray(graph.node_w)[n:] == 0).all(),
        "padding: pad nodes must have weight 0",
    )
    deg = np.diff(row_ptr[: n + 1].astype(np.int64))
    _require(
        int(row_ptr[0]) == 0 and (deg >= 0).all() and int(row_ptr[n]) == m,
        "offsets: row_ptr must rise monotonically from 0 to m",
    )
    _require(
        np.array_equal(
            src[:m], np.repeat(np.arange(n, dtype=np.int64), deg)
        ),
        "src/row_ptr mismatch: COO sources disagree with CSR offsets",
    )
    return _validate_host_arrays(
        row_ptr[: n + 1].astype(np.int64), dst[:m], n, undirected,
        edge_w=np.asarray(graph.edge_w)[:m],
    )


def _validate_host_arrays(
    xadj: np.ndarray,
    adjncy: np.ndarray,
    n: int,
    undirected: bool,
    edge_w: Optional[np.ndarray] = None,
) -> None:
    m = int(xadj[-1]) if len(xadj) else 0
    _require(
        len(xadj) == n + 1, f"offsets: xadj has {len(xadj)} entries for n={n}"
    )
    _require(int(xadj[0]) == 0, "offsets: xadj must start at 0")
    _require(
        (np.diff(xadj) >= 0).all(), "offsets: xadj must be non-decreasing"
    )
    _require(
        m == len(adjncy),
        f"offsets: xadj ends at {m} but adjncy has {len(adjncy)} entries",
    )
    if m:
        _require(
            int(adjncy.min()) >= 0 and int(adjncy.max()) < n,
            "adjacency: neighbor id out of [0, n)",
        )
    if undirected and m:
        src = np.repeat(np.arange(n, dtype=np.int64), np.diff(xadj))
        adj64 = adjncy.astype(np.int64)
        fwd = np.lexsort((adj64, src))
        rev = np.lexsort((src, adj64))
        sym = np.array_equal(src[fwd], adj64[rev]) and np.array_equal(
            adj64[fwd], src[rev]
        )
        _require(sym, "symmetry: some edge's reverse twin is missing")
        if sym and edge_w is not None:
            _require(
                np.array_equal(
                    np.asarray(edge_w, dtype=np.int64)[fwd],
                    np.asarray(edge_w, dtype=np.int64)[rev],
                ),
                "symmetry: reverse twin present but weights differ",
            )


def pad_arrays_to(
    n_pad: int, m_pad: int, graph: DeviceGraph
) -> DeviceGraph:
    """Re-pad a device graph into larger buffers (no-op if sizes match).
    Only grows; used to keep hierarchy levels in shared shape buckets."""
    if n_pad == graph.n_pad and m_pad == graph.m_pad:
        return graph
    if n_pad < graph.n_pad or m_pad < graph.m_pad:
        raise ValueError("can only grow padding")
    pad_node = n_pad - 1

    def pad_edges(x, fill):
        return jnp.concatenate(
            [x, jnp.full(m_pad - graph.m_pad, fill, dtype=x.dtype)]
        )

    # re-point old pad slots at the new pad node
    src = jnp.where(jnp.arange(graph.m_pad) < graph.m, graph.src, pad_node)
    dst = jnp.where(jnp.arange(graph.m_pad) < graph.m, graph.dst, pad_node)
    row_ptr = jnp.concatenate(
        [
            graph.row_ptr,
            jnp.full(n_pad - graph.n_pad, graph.m, dtype=graph.row_ptr.dtype),
        ]
    )
    row_ptr = jnp.where(
        jnp.arange(n_pad + 1) <= graph.n, row_ptr, graph.m
    ).astype(jnp.int32)
    return DeviceGraph(
        row_ptr=row_ptr,
        src=pad_edges(src, pad_node),
        dst=pad_edges(dst, pad_node),
        edge_w=pad_edges(graph.edge_w, 0),
        node_w=jnp.concatenate(
            [graph.node_w, jnp.zeros(n_pad - graph.n_pad, dtype=graph.node_w.dtype)]
        ),
        n=graph.n,
        m=graph.m,
    )

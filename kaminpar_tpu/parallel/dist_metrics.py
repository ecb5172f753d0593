"""Distributed quality metrics.

The analog of kaminpar-dist/metrics.cc: each PE computes its local share of
the cut and the result is allreduced — here a `psum` over the mesh axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..ops.segments import ACC_DTYPE
from .dist_graph import DistGraph
from .mesh import NODE_AXIS


@partial(jax.jit, static_argnames=("mesh",))
def _dist_edge_cut_impl(mesh, graph: DistGraph, labels: jax.Array) -> jax.Array:
    """Edge cut of a (replicated) labeling over a sharded graph.

    Every undirected edge is stored once per endpoint, so the psum of local
    directed cut weight counts each cut edge twice (metrics.cc:37 divides
    the same way).
    """

    def local(src_l, dst_l, ew_l, labels):
        cut = jnp.sum(
            jnp.where(labels[src_l] != labels[dst_l], ew_l, 0).astype(ACC_DTYPE)
        )
        return lax.psum(cut, NODE_AXIS)

    total = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )(graph.src, graph.dst, graph.edge_w, labels)
    return total // 2


def dist_edge_cut(graph: DistGraph, labels: jax.Array) -> jax.Array:
    return _dist_edge_cut_impl(graph.src.sharding.mesh, graph, labels)

"""Sharded query execution steps over a device mesh.

The multi-chip execution path (SURVEY.md §5.8, §7 step 7): the graph's edge
table is sharded across the mesh axis; node-indexed frontier vectors are
combined with ``psum`` over ICI.  The same program runs on a 1-device or
v5e-8 mesh.

The flagship step is the 2-hop friend-of-friend MATCH (benchmark config 1)
in aggregate-pushdown form: counting paths (a)-[:KNOWS]->(b)-[:KNOWS]->(c)
with a seed predicate on ``a`` needs no row materialization — per-hop path
counts propagate as dense node vectors:

    cnt1[v] = Σ_{edges (u,v)} seed(u)          (segment-sum, psum)
    paths   = Σ_{edges (b,c)} cnt1[b]          (gather, psum)

which is two sparse-matrix/vector products against the adjacency — the
tensor-execution formulation of pattern joins (cf. PAPERS.md dimensional-
collapse / TrieJax lines of work).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from caps_tpu.parallel.collectives import (
    broadcast_concat, exchange_by_shard, global_sum, ring_shift, shard_of,
)


def two_hop_count_kernel(name_codes, edge_src, edge_dst, edge_ok, seed_code,
                         *, axis: str, n_nodes: int):
    """Per-device body (inside shard_map): edges are the local shard;
    ``name_codes`` is the replicated node property vector."""
    is_seed_edge = edge_ok & (name_codes[edge_src] == seed_code)
    local_cnt1 = jax.ops.segment_sum(
        is_seed_edge.astype(jnp.int32), edge_dst, num_segments=n_nodes)
    cnt1 = global_sum(local_cnt1, axis)          # frontier vector over ICI
    hop2 = jnp.where(edge_ok, cnt1[edge_src], 0)
    local_cnt2 = jax.ops.segment_sum(hop2, edge_dst, num_segments=n_nodes)
    cnt2 = global_sum(local_cnt2, axis)
    total = cnt2.sum()
    return total, cnt2


def make_sharded_two_hop(mesh: Mesh, n_nodes: int, axis: str = "shard"):
    """Build the jitted sharded 2-hop step for a mesh: edges sharded over
    ``axis``, node vector replicated, outputs replicated."""
    fn = functools.partial(two_hop_count_kernel, axis=axis, n_nodes=n_nodes)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(mapped)


def collectives_smoke_kernel(x, *, axis: str, n_shards: int):
    """Exercises every collective the engine uses — all_to_all radix
    exchange, ppermute ring shift, all_gather broadcast, psum — in one
    shard_map body (used by the multichip dryrun)."""
    dest = shard_of(x, n_shards)
    exchanged = exchange_by_shard(x, dest, n_shards, axis, x.shape[0])
    shifted = ring_shift(exchanged.sum(axis=0), axis, n_shards)
    gathered = broadcast_concat(x[:4], axis)
    total = global_sum(x.sum() + shifted.sum() + gathered.sum(), axis)
    return total


def make_collectives_smoke(mesh: Mesh, axis: str = "shard"):
    n = mesh.devices.size
    fn = functools.partial(collectives_smoke_kernel, axis=axis, n_shards=n)
    mapped = shard_map(fn, mesh=mesh, in_specs=(P(axis),), out_specs=P())
    return jax.jit(mapped)

"""Collective primitives for sharded query execution.

The engine's "shuffle service" (SURVEY.md §5.8): thin wrappers over
``jax.lax`` collectives used inside ``shard_map``ped query programs.

    exchange_by_shard   all_to_all radix repartition by key hash — the
                        analog of Spark's hash shuffle before joins/aggs
    ring_shift          ppermute rotation — the ring schedule for k-hop
                        frontier expansion against resident shards
    broadcast_concat    all_gather of a small build side — broadcast join
    global_sum          psum tree — global aggregates
    global_max          64-bit-safe max over the axis (sizing scalars)
    sum_scatter         64-bit-safe reduce-scatter of node-indexed partials

All take the mesh axis name; they only mean something inside shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from caps_tpu.obs import active_tracer, global_registry


def note_collective(op: str, *arrays, scale: int = 1, **attrs) -> None:
    """Observability hook for collective launches (obs/ — ISSUE 3).

    These wrappers execute at TRACE time (once per XLA compile of the
    enclosing shard_map program), not per device execution, so counts
    and byte totals are per-compile — recorded under
    ``collectives.<op>.*`` in the process-global registry and as
    ``when="trace"`` tracer events, never mislabeled as per-run wire
    traffic.  ``scale`` multiplies the byte estimate when the traced
    launch runs more than once per compile (a ring rotation inside a
    fori_loop body traces once but fires n_shards times).  The
    per-execution wire/payload accounting stays with the callers that
    know the run context (backends/tpu/table.py dist joins, which emit
    their own ``dist_join.*`` events)."""
    try:
        nbytes = scale * sum(int(a.size) * a.dtype.itemsize for a in arrays)
    except Exception:  # abstract avals without sizes: count the call only
        nbytes = 0
    reg = global_registry()
    reg.counter(f"collectives.{op}.calls").inc()
    reg.counter(f"collectives.{op}.traced_bytes").inc(nbytes)
    tr = active_tracer()
    if tr.enabled:
        tr.event(f"collective.{op}", kind="collective", bytes=nbytes,
                 when="trace", **attrs)


def shard_of(key: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Destination shard for a join/group key (dense ids: range partition
    by modulo — cheap and balanced for hashed/dense ids)."""
    return (key % n_shards).astype(jnp.int32)


def salted_dest(key: jnp.ndarray, n_shards: int, salt: int,
                salt_id: jnp.ndarray | None) -> jnp.ndarray:
    """Destination device of a key.  With salting, sub-bucket ``s`` of a
    key lands ``s * (n_shards // salt)`` devices away — the ``salt``
    sub-buckets of one key hit ``salt`` DISTINCT devices (stride
    ``n // salt``, ids ``s*stride < n`` pairwise distinct).  The skew
    guard of the radix-exchange join (SURVEY.md §5.8 'salting hot keys')."""
    base = (jnp.abs(key) % jnp.int64(n_shards)).astype(jnp.int32)
    if salt > 1 and salt_id is not None:
        stride = max(1, n_shards // salt)
        base = (base + salt_id.astype(jnp.int32) * stride) % n_shards
    return base


def bin_positions(dest: jnp.ndarray, ok: jnp.ndarray, n_shards: int,
                  bin_cap: int):
    """Within-bin position per row for a binned exchange; overflowed rows
    are counted and get an out-of-range destination so the scatter drops
    them (callers retry with a bigger ``bin_cap`` when ``dropped > 0``)."""
    dest = jnp.where(ok, dest, n_shards)
    one_hot = (dest[:, None] == jnp.arange(n_shards)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(one_hot, axis=0) - 1
    row_pos = jnp.where(ok, jnp.take_along_axis(
        pos, jnp.clip(dest, 0, n_shards - 1)[:, None], axis=1)[:, 0], 0)
    sent = ok & (row_pos < bin_cap)
    dropped = (ok & ~sent).sum()
    dest = jnp.where(sent, dest, n_shards)
    return dest, row_pos, dropped


def exchange_binned(arr: jnp.ndarray, dest: jnp.ndarray,
                    row_pos: jnp.ndarray, n_shards: int, bin_cap: int,
                    axis, fill) -> jnp.ndarray:
    """Scatter local rows into (n_shards, bin_cap, *trailing) bins
    (out-of-range destinations drop) and all_to_all: device i receives
    every other device's bin i → (n_shards, bin_cap, *trailing).
    Trailing dims carry matrix payloads (e.g. list columns); ``axis`` may
    be a tuple of mesh axes (2-D DCN×ICI meshes — the collective runs
    over the flattened product)."""
    binned = jnp.full((n_shards, bin_cap) + arr.shape[1:], fill, arr.dtype)
    binned = binned.at[dest, jnp.clip(row_pos, 0, bin_cap - 1)].set(
        arr, mode="drop")
    note_collective("all_to_all", binned)
    return lax.all_to_all(binned, axis, split_axis=0, concat_axis=0,
                          tiled=False)


def exchange_by_shard(data: jnp.ndarray, dest: jnp.ndarray, n_shards: int,
                      axis: str, capacity: int) -> jnp.ndarray:
    """All-to-all exchange: each device buckets its rows by ``dest`` into
    fixed-capacity bins, then all_to_all delivers bin i to device i.
    Returns the received (n_shards, capacity) buckets; slots beyond each
    bin's fill are garbage — callers carry a validity channel the same way.
    """
    ok = jnp.ones(data.shape[0], bool)
    dest, row_pos, _ = bin_positions(dest, ok, n_shards, capacity)
    return exchange_binned(data, dest, row_pos, n_shards, capacity, axis,
                           jnp.zeros((), data.dtype))


def ring_shift(x: jnp.ndarray, axis: str, n_shards: int,
               offset: int = 1) -> jnp.ndarray:
    """Rotate a block one step around the ICI ring (ppermute) — the
    communication pattern of ring attention, applied to frontier blocks in
    multi-hop expansion (SURVEY.md §5.7)."""
    perm = [(i, (i + offset) % n_shards) for i in range(n_shards)]
    note_collective("ppermute", x)
    return lax.ppermute(x, axis, perm)


def broadcast_concat(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """all_gather a small table side to every device (broadcast-hash join
    analog of Spark's TorrentBroadcast)."""
    note_collective("all_gather", x)
    return lax.all_gather(x, axis, tiled=True)


def global_sum(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    note_collective("psum", x)
    return lax.psum(x, axis)


# XLA's TPU backend lowers 64-bit collectives by rewriting them into
# 32-bit halves, and only some have that rewrite: psum, ppermute,
# all_gather and all_to_all compile for int64/float64; ``pmax``/``pmin``
# ("Supported lowering only of Sum all reduce") and ``psum_scatter``
# ("rewriting ... not implemented: reduce-scatter") are refused
# (compiled for v5e, jax 0.9.0 / libtpu 0.0.34).  The engine's row
# counts and path counts are int64, so the two below are built from the
# collectives that do lower; on CPU they compute the same values.


def global_max(x: jnp.ndarray, axis) -> jnp.ndarray:
    """``lax.pmax`` that also takes int64: the value splits into a signed
    high word and a biased low word (int32 order == the original's, as
    in ops/sort.py ``split_planes``) and the maximum is found word by
    word with two 32-bit ``pmax``."""
    if x.dtype != jnp.int64:
        return lax.pmax(x, axis)
    hi = (x >> 32).astype(jnp.int32)
    lo = ((x & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
    hi_max = lax.pmax(hi, axis)
    lo_max = lax.pmax(
        jnp.where(hi == hi_max, lo, jnp.iinfo(jnp.int32).min), axis)
    return (hi_max.astype(jnp.int64) << 32) \
        | (lo_max.astype(jnp.int64) + (1 << 31))


def sum_scatter(x: jnp.ndarray, axis: str, n_shards: int) -> jnp.ndarray:
    """``lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)`` for
    any dtype: an all_to_all delivers block i of every device's ``x`` to
    device i — the bytes a reduce-scatter moves — and the sum is local."""
    parts = x.reshape((n_shards, x.shape[0] // n_shards) + x.shape[1:])
    return lax.all_to_all(parts, axis, 0, 0).sum(axis=0)

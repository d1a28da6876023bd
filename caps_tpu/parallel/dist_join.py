"""Hand-scheduled distributed joins over 1-D and 2-D device meshes.

The engine's explicit "shuffle join" (SURVEY.md §5.8; round-4 VERDICT
item 4, round-5 items 7–8): instead of trusting GSPMD to lay out the
collectives for a sharded sort-merge join (which tends to all_gather both
sides over ICI), the two strategies the reference inherits from Spark are
scheduled by hand inside ``shard_map``:

* **Radix-partition exchange join** (Spark's shuffle-hash/sort-merge
  join): both sides bucket rows by ``key mod n_shards`` and one
  ``all_to_all`` delivers bucket *i* to device *i*; each device then
  sort-merge joins only its hash partition.  Each row crosses ICI once —
  versus *n* times for an all_gather — and local join work shrinks by
  ~1/n.

  **Surgical skew salting**: a device-resident HOT-KEY set (detected by
  the caller from a host-side key sample) marks the keys whose
  frequency would overload one device.  Probe rows of hot keys spread
  round-robin over ``salt`` devices; ONLY hot build rows replicate into
  the extra ``salt-1`` sub-buckets (exchanged at a smaller
  ``hot_bin_cap``) — non-hot keys pay nothing, fixing round-4's
  whole-build-side replication tax.

* **Broadcast join** (Spark's TorrentBroadcast / auto-broadcast): a small
  build side is ``all_gather``ed to every device once; the probe side
  never moves.  Chosen by the caller when the build side is under the
  configured row threshold.

Both run as two phases so output capacities stay static under ``jit``:
phase 1 exchanges rows and returns per-device match counts plus overflow
counters — the host doubles the bin capacity and retries on overflow;
phase 2 expands matches into output rows at a host-chosen bucket size.
Exchanged buckets stay device-resident between the phases (sharded
``shard_map`` outputs), so each row crosses ICI exactly once.

**2-D (DCN×ICI) meshes**: ``axis`` may be a tuple of mesh axis names —
the collectives then operate over the flattened device product
(DCN-major, matching ``DeviceBackend.place_rows``) and the same radix
schedule runs across slices.

ICI traffic is accounted two ways (round-5 VERDICT item 7): the caller's
static byte count of the PADDED exchange buffers (the wire truth for a
binned all_to_all) goes to ``DeviceBackend.ici_bytes``; phase 1
additionally returns device-measured counts of live rows that left their
home device, from which the caller computes ``ici_payload_bytes`` — the
cross-check that the estimate brackets reality.
"""
from __future__ import annotations

import functools
from typing import List, Tuple, Union

import jax

jax.config.update("jax_enable_x64", True)  # int64 join keys/sentinels

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from caps_tpu.parallel.collectives import (
    bin_positions as _bin_positions,
    broadcast_concat as _broadcast_concat,
    exchange_binned as _exchange,
    global_max as _global_max,
    salted_dest as _dest_for,
)

Axis = Union[str, Tuple[str, ...]]

# Join-key sentinels (match backends/tpu/kernels.py): nulls never match.
_L_NULL = jnp.int64(-(2**63) + 1)
_R_NULL = jnp.int64(-(2**63) + 2)


def _is_hot(key: jnp.ndarray, hot_keys: jnp.ndarray) -> jnp.ndarray:
    """Membership of each key in the sorted hot-key set (sentinel-padded;
    the sentinel never matches a real key)."""
    if hot_keys.shape[0] == 0:
        return jnp.zeros(key.shape, bool)
    pos = jnp.searchsorted(hot_keys, key)
    pos = jnp.clip(pos, 0, hot_keys.shape[0] - 1)
    return hot_keys[pos] == key


def _off_home(dest: jnp.ndarray, me, n_shards: int) -> jnp.ndarray:
    """Count of rows bound for a different device (live, in-range)."""
    return ((dest != me) & (dest < n_shards)).sum()


def _expand_matches(counts, lo, perm, lok, rok, out_cap_dev: int,
                    left_join: bool):
    """Segmented expansion of per-probe-row match counts into output row
    index pairs (the device-local analog of kernels.join_expand, shared by
    the radix phase-2 and broadcast programs)."""
    matched = counts > 0
    eff = jnp.where(lok & ~matched, 1, counts) if left_join else counts
    offsets = jnp.cumsum(eff)
    total = offsets[-1] if eff.shape[0] > 0 else jnp.int64(0)
    t = jnp.arange(out_cap_dev)
    l_idx = jnp.clip(jnp.searchsorted(offsets, t, side="right"),
                     0, counts.shape[0] - 1)
    seg_start = jnp.where(l_idx > 0, offsets[l_idx - 1], 0)
    within = t - seg_start
    r_pos = jnp.clip(lo[l_idx] + within, 0, perm.shape[0] - 1)
    r_idx = perm[r_pos]
    out_valid = t < total
    r_matched = out_valid & matched[l_idx]
    l_valid = out_valid & lok[l_idx]
    r_valid = r_matched & rok[r_idx]
    return l_idx, r_idx, l_valid, r_valid


@functools.lru_cache(maxsize=64)
def make_radix_join_phase1(mesh: Mesh, axis: Axis, n_shards: int,
                           n_l: int, n_r: int,
                           l_dtypes: Tuple[str, ...],
                           r_dtypes: Tuple[str, ...],
                           bin_cap: int, salt: int, hot_bin_cap: int):
    """Phase 1: exchange both sides, sort the received build partition,
    count matches per received probe row.  All row outputs stay sharded
    (device-resident) for phase 2.  ``hot_keys`` (sorted, sentinel-padded
    device array) drives surgical salting; with ``salt == 1`` it is
    ignored."""

    def body(hot_keys, l_key, l_ok, r_key, r_ok, *flat):
        l_arrs = flat[:n_l]
        r_arrs = flat[n_l:n_l + n_r]
        me = lax.axis_index(axis)

        # probe side: one exchange; ONLY hot keys round-robin over the
        # salt sub-buckets, everything else goes straight home
        if salt > 1:
            hot_l = _is_hot(l_key, hot_keys)
            sid = jnp.where(
                hot_l,
                (jnp.arange(l_key.shape[0]) % salt).astype(jnp.int32), 0)
        else:
            sid = jnp.zeros(l_key.shape, jnp.int32)
        dest = _dest_for(l_key, n_shards, salt, sid)
        dest, row_pos, l_drop = _bin_positions(dest, l_ok, n_shards, bin_cap)
        sent_l = _off_home(dest, me, n_shards)
        lk_recv = _exchange(jnp.where(l_ok, l_key, _L_NULL), dest, row_pos,
                            n_shards, bin_cap, axis, _L_NULL).reshape(-1)
        lok_recv = _exchange(l_ok, dest, row_pos, n_shards, bin_cap,
                             axis, False).reshape(-1)
        l_recv = tuple(
            _exchange(a, dest, row_pos, n_shards, bin_cap, axis,
                      jnp.zeros((), a.dtype)).reshape(
                          (-1,) + a.shape[1:]) for a in l_arrs)

        # build side: copy 0 carries every row; copies 1..salt-1 carry
        # ONLY hot rows (smaller bins — the surgical part)
        hot_r = _is_hot(r_key, hot_keys) if salt > 1 else None
        rk_parts: List[jnp.ndarray] = []
        rok_parts: List[jnp.ndarray] = []
        r_parts: List[List[jnp.ndarray]] = [[] for _ in r_arrs]
        r_drop = jnp.int64(0)
        sent_r = jnp.int64(0)
        for s in range(max(salt, 1)):
            cap_s = bin_cap if s == 0 else hot_bin_cap
            ok_s = r_ok if s == 0 else (r_ok & hot_r)
            sid_r = jnp.full(r_key.shape, s, jnp.int32)
            dest_r = _dest_for(r_key, n_shards, salt, sid_r)
            dest_r, pos_r, drop_s = _bin_positions(dest_r, ok_s, n_shards,
                                                   cap_s)
            r_drop = r_drop + drop_s
            sent_r = sent_r + _off_home(dest_r, me, n_shards)
            rk_parts.append(_exchange(
                jnp.where(ok_s, r_key, _R_NULL), dest_r, pos_r,
                n_shards, cap_s, axis, _R_NULL))
            rok_parts.append(_exchange(ok_s, dest_r, pos_r, n_shards,
                                       cap_s, axis, False))
            for i, a in enumerate(r_arrs):
                r_parts[i].append(_exchange(
                    a, dest_r, pos_r, n_shards, cap_s, axis,
                    jnp.zeros((), a.dtype)))
        rk_recv = jnp.concatenate(rk_parts, axis=1).reshape(-1)
        rok_recv = jnp.concatenate(rok_parts, axis=1).reshape(-1)
        r_recv = tuple(
            jnp.concatenate(p, axis=1).reshape((-1,) + p[0].shape[2:])
            for p in r_parts)

        # local sort-merge count on the received hash partitions
        rk = jnp.where(rok_recv, rk_recv, _R_NULL)
        rk_sorted, perm = lax.sort((rk, jnp.arange(rk.shape[0])), num_keys=1)
        lk = jnp.where(lok_recv, lk_recv, _L_NULL)
        lo = jnp.searchsorted(rk_sorted, lk, side="left")
        hi = jnp.searchsorted(rk_sorted, lk, side="right")
        counts = jnp.where(lok_recv, hi - lo, 0)
        my_total = counts.sum()
        max_total = _global_max(my_total, axis)
        max_left = _global_max(
            (counts + jnp.where(lok_recv & (counts == 0), 1, 0)).sum(), axis)
        dropped = lax.psum(l_drop + r_drop, axis)
        sent_l = lax.psum(sent_l, axis)
        sent_r = lax.psum(sent_r, axis)
        return (lok_recv, counts, lo, perm, rok_recv, max_total, max_left,
                dropped, sent_l, sent_r) + l_recv + r_recv

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(),) + (P(axis),) * (4 + n_l + n_r),
        out_specs=(P(axis),) * 5 + (P(),) * 5 + (P(axis),) * (n_l + n_r),
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def make_radix_join_phase2(mesh: Mesh, axis: Axis, n_l: int, n_r: int,
                           out_cap_dev: int, left_join: bool):
    """Phase 2: expand matches into output rows (static per-device cap)."""

    def body(lok, counts, lo, perm, rok, *flat):
        l_recv = flat[:n_l]
        r_recv = flat[n_l:n_l + n_r]
        l_idx, r_idx, l_valid, r_valid = _expand_matches(
            counts, lo, perm, lok, rok, out_cap_dev, left_join)
        outs = tuple(a[l_idx] for a in l_recv) + \
            tuple(a[r_idx] for a in r_recv)
        return (l_valid, r_valid) + outs

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis),) * (5 + n_l + n_r),
        out_specs=(P(axis),) * (2 + n_l + n_r),
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def make_broadcast_join(mesh: Mesh, axis: Axis, n_l: int, n_r: int,
                        out_cap_dev: int, left_join: bool,
                        count_only: bool):
    """Broadcast join: all_gather the (small) build side once, probe
    locally.  ``count_only`` is the phase-1 variant returning only the
    max per-device output size plus the live build-row count (the host
    then picks the bucket and accounts payload bytes)."""

    def body(l_key, l_ok, r_key, r_ok, *flat):
        l_arrs = flat[:n_l]
        r_arrs = flat[n_l:n_l + n_r]
        rk_all = _broadcast_concat(jnp.where(r_ok, r_key, _R_NULL), axis)
        rok_all = _broadcast_concat(r_ok, axis)
        rk = jnp.where(rok_all, rk_all, _R_NULL)
        rk_sorted, perm = lax.sort((rk, jnp.arange(rk.shape[0])), num_keys=1)
        lk = jnp.where(l_ok, l_key, _L_NULL)
        lo = jnp.searchsorted(rk_sorted, lk, side="left")
        hi = jnp.searchsorted(rk_sorted, lk, side="right")
        counts = jnp.where(l_ok, hi - lo, 0)
        eff = jnp.where(left_join & l_ok & (counts == 0), 1, counts) \
            if left_join else counts
        max_total = _global_max(eff.sum(), axis)
        if count_only:
            live_r = lax.psum(r_ok.sum(), axis)
            return (max_total, live_r)
        r_all = tuple(_broadcast_concat(a, axis) for a in r_arrs)
        l_idx, r_idx, l_valid, r_valid = _expand_matches(
            counts, lo, perm, l_ok, rok_all, out_cap_dev, left_join)
        outs = tuple(a[l_idx] for a in l_arrs) + \
            tuple(a[r_idx] for a in r_all)
        return (l_valid, r_valid) + outs

    n_out = 2 if count_only else (2 + n_l + n_r)
    out_specs = (P(), P()) if count_only else (P(axis),) * n_out
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis),) * (4 + n_l + n_r),
        out_specs=out_specs,
    )
    return jax.jit(mapped)

"""Device kernels for the columnar operators.

These are the jnp/lax reference implementations of the hot operators
(SURVEY.md §7 step 5); the Pallas kernels in ``caps_tpu.ops`` swap in
underneath for the perf-critical paths and are differential-tested against
these.  Everything here is shape-static (capacities are bucketed powers of
two) and jit-cached per shape, so eager op-by-op execution still runs as
compiled XLA programs.

Two-phase pattern: operators whose output size is data-dependent (filter,
join, explode, group) first run a jitted *count* kernel, sync one scalar to
the host to pick the output bucket, then run a jitted *materialize* kernel
with static output shape — the eager-mode analog of bucketed compilation.
The filter's materialize step (compact_indices) is scatter-free: a rank
search where the output bucket is small against the input, one int32
key sort otherwise; its two static shapes decide.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from caps_tpu.ops.compact import compact_form, compact_indices  # noqa: F401

jax.config.update("jax_enable_x64", True)

# Sentinels for join keys: nulls (and NaNs) on either side must never
# match anything.  They live in (-2^63, -2^63 + 2^52), the gap below any
# monotone-bitcast float64 key (table._join_key) — only an int64 key of
# exactly these pathological values could collide.
_L_NULL = jnp.int64(-(2**63) + 1)
_R_NULL = jnp.int64(-(2**63) + 2)
_L_NAN = jnp.int64(-(2**63) + 3)
_R_NAN = jnp.int64(-(2**63) + 4)
_PAD = jnp.int64(2**62)


def row_mask(capacity: int, n) -> jnp.ndarray:
    return jnp.arange(capacity) < n


# -- compaction (filter) ----------------------------------------------------

# compact_indices (rank search or one key sort, by shape) lives in
# ops/compact.py, beside the sort the expand kernel's prelude shares


@jax.jit
def mask_count(mask: jnp.ndarray) -> jnp.ndarray:
    return mask.sum()


# -- sort-merge join --------------------------------------------------------

@jax.jit
def sort_right(r_key, r_ok):
    """Reference build-side sort (lax.sort, un-gated).  The engine routes
    build-side sorts through DeviceTable._sort_perm so they can ride the
    bitonic kernel; this stays as the plain-XLA reference the kernel
    differential tests probe against."""
    cap_r = r_key.shape[0]
    rk = jnp.where(r_ok, r_key.astype(jnp.int64), _R_NULL)
    rk_sorted, perm = jax.lax.sort((rk, jnp.arange(cap_r)), num_keys=1)
    return rk_sorted, perm


@jax.jit
def probe_count(l_key, l_ok, rk_sorted):
    """Phase 1: per-left-row match counts against the sorted right keys.
    The path of a join whose build side has no resident index: an
    ingested table's id columns carry one (ops/expand.py ``csr_probe``,
    two gathers a row); a derived table, or ids ``build_csr`` refuses,
    are sorted once and searched here."""
    lk = jnp.where(l_ok, l_key.astype(jnp.int64), _L_NULL)
    lo = jnp.searchsorted(rk_sorted, lk, side="left")
    hi = jnp.searchsorted(rk_sorted, lk, side="right")
    counts = jnp.where(l_ok, hi - lo, 0)
    return counts, lo


@functools.partial(jax.jit, static_argnames=("out_cap", "left_join"))
def join_expand(counts, lo, perm, l_ok, out_cap: int, left_join: bool):
    """Phase 2: segmented expansion to (l_idx, r_idx, out_valid, r_matched)."""
    matched = counts > 0
    eff_counts = jnp.where(left_join & l_ok & ~matched, 1, counts)
    offsets = jnp.cumsum(eff_counts)
    total = offsets[-1] if eff_counts.shape[0] > 0 else jnp.int64(0)
    t = jnp.arange(out_cap)
    l_idx = jnp.searchsorted(offsets, t, side="right")
    l_idx = jnp.clip(l_idx, 0, counts.shape[0] - 1)
    seg_start = jnp.where(l_idx > 0, offsets[l_idx - 1], 0)
    within = t - seg_start
    r_pos = jnp.clip(lo[l_idx] + within, 0, perm.shape[0] - 1)
    r_idx = perm[r_pos]
    out_valid = t < total
    r_matched = out_valid & matched[l_idx]
    return l_idx, r_idx, out_valid, r_matched, total


@jax.jit
def join_total(counts, l_ok, left_join: bool = False):
    eff = jnp.where(left_join & l_ok & (counts == 0), 1, counts)
    return eff.sum()


@jax.jit
def cross_counts(l_ok, n_r):
    return jnp.where(l_ok, n_r, 0)


# -- multi-key lexicographic sort ------------------------------------------

def sort_perm(keys: Sequence[jnp.ndarray], capacity: int) -> jnp.ndarray:
    """Stable lexicographic sort by pre-transformed int64/float64 keys
    (nulls/padding already folded into the key values)."""
    operands = tuple(keys) + (jnp.arange(capacity),)
    out = jax.lax.sort(operands, num_keys=len(keys), is_stable=True)
    return out[-1]


@jax.jit
def neighbor_change(sorted_keys_stacked: jnp.ndarray) -> jnp.ndarray:
    """Given (k, capacity) stacked sorted keys, True where a row starts a
    new group (row 0 included)."""
    diff = jnp.any(sorted_keys_stacked[:, 1:] != sorted_keys_stacked[:, :-1],
                   axis=0)
    return jnp.concatenate([jnp.ones((1,), bool), diff])


@jax.jit
def neighbor_change_keys(sorted_keys) -> jnp.ndarray:
    """neighbor_change over a *list* of sorted key arrays compared each in
    its own dtype — int64 keys are never squeezed through float64 (which
    collides keys >= 2^53)."""
    cap = sorted_keys[0].shape[0]
    diff = jnp.zeros((max(cap - 1, 0),), bool)
    for k in sorted_keys:
        diff = diff | (k[1:] != k[:-1])
    return jnp.concatenate([jnp.ones((1,), bool), diff])


# -- segmented aggregation --------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_segments", "kind"))
def sorted_segment_agg(values, ok, seg_id, num_segments: int, kind: str):
    """Sum/count over *non-decreasing* ``seg_id`` via cumulative sum +
    boundary gather — a scan and two gathers instead of XLA scatter-add,
    which serializes on TPU.  Exact for integers (int64 cumsum); the
    group-by path sorts rows first, so its seg_ids always qualify."""
    if kind == "count":
        v = ok.astype(jnp.int64)
    elif kind == "sum":
        v = jnp.where(ok, values, 0)
    else:
        raise ValueError(f"sorted_segment_agg supports count/sum, not {kind}")
    c = jnp.cumsum(v)
    ends = jnp.searchsorted(seg_id, jnp.arange(num_segments),
                            side="right") - 1
    cum = jnp.where(ends >= 0, c[jnp.clip(ends, 0, None)], 0)
    prev = jnp.concatenate([jnp.zeros(1, cum.dtype), cum[:-1]])
    return cum - prev

@functools.partial(jax.jit, static_argnames=("num_segments", "kind"))
def segment_agg(values, ok, seg_id, num_segments: int, kind: str):
    """One aggregation over sorted segments.  ``ok`` masks nulls+padding."""
    if kind == "count":
        return jax.ops.segment_sum(ok.astype(jnp.int64), seg_id, num_segments)
    if kind == "sum":
        v = jnp.where(ok, values, 0)
        return jax.ops.segment_sum(v, seg_id, num_segments)
    if kind in ("min", "max"):
        # An all-null column (e.g. aggregation over an empty MATCH) can
        # arrive as bool; jnp.iinfo rejects 'b', and min/max over bools is
        # well-defined via int promotion, so widen before picking the
        # identity element.
        if values.dtype.kind == "b":
            values = values.astype(jnp.int64)
        if kind == "min":
            big = jnp.array(jnp.inf if values.dtype.kind == "f" else
                            jnp.iinfo(values.dtype).max, values.dtype)
            v = jnp.where(ok, values, big)
            return jax.ops.segment_min(v, seg_id, num_segments)
        small = jnp.array(-jnp.inf if values.dtype.kind == "f" else
                          jnp.iinfo(values.dtype).min, values.dtype)
        v = jnp.where(ok, values, small)
        return jax.ops.segment_max(v, seg_id, num_segments)
    if kind == "first":
        cap = values.shape[0]
        pos = jnp.where(ok, jnp.arange(cap), cap)
        first_pos = jax.ops.segment_min(pos, seg_id, num_segments)
        safe = jnp.clip(first_pos, 0, cap - 1)
        return values[safe], first_pos < cap
    raise ValueError(f"unknown segment aggregation {kind}")


# -- explode / pack --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("out_cap",))
def explode_expand(lens, ok, out_cap: int):
    counts = jnp.where(ok, lens, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[-1] if counts.shape[0] > 0 else jnp.int64(0)
    t = jnp.arange(out_cap)
    row = jnp.searchsorted(offsets, t, side="right")
    row = jnp.clip(row, 0, counts.shape[0] - 1)
    seg_start = jnp.where(row > 0, offsets[row - 1], 0)
    within = t - seg_start
    return row, within, t < total, total

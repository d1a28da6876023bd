"""Segmented-expand position kernel + device-resident CSR adjacency.

The hot path of every join/Expand hop is the *materialization* step: given
per-left-row match counts, produce for every output slot ``t`` the left row
it came from and the position of its match — i.e. invert the running sum
``offsets = cumsum(counts)``.  The jnp path (kernels.join_expand, ref
analog: Spark's shuffle-side expansion inside SparkTable joins —
reconstructed, mount empty; SURVEY.md §3.2) does this with a
``searchsorted(offsets, t)`` per output element: ~log2(n) dependent
HBM gathers per slot, the worst access pattern a TPU can run.

This kernel restructures the inversion to be VPU-shaped:

* left rows with ``count == 0`` are compacted away (XLA prelude: one
  key sort that carries each row's count and match start along,
  ops/compact.py), so a tile of T outputs can touch at most T+1
  consecutive live rows;
* per tile, the prelude computes which row *block* the tile starts in
  (one tiny searchsorted over tile starts, n_tiles elements);
* the kernel holds a 2T-row window of (offsets, lo, row-id) in VMEM and
  recovers, for each of the T output slots,

      l_local[t]  = Σ_w  (offsets[w] <= t)            # compare + reduce
      seg_start[t] = max(seg_base, max_w offsets[w]·[offsets[w]<=t])
      lo[t], row[t] = one-hot select at l_local[t]    # compare + reduce

  — three dense (2T × T) VPU passes, no gather, no scatter, streaming
  through VMEM.  The window always covers the tile (proof in comments).

``DeviceCSR`` makes the *probe* side of a join O(1) per row as well: an
ingested table's physical layout on HBM includes a CSR over each id column
a join probes — a relationship table's source and target, a node table's
id — built once per graph at ingest by the C++ host runtime
(native/csrc/host_runtime.cpp csr_build), or by one numpy sort without
it.  A probe is then two ``indptr`` gathers (lo/hi) per row, in one
program (``csr_probe``), instead of a sort + per-row binary search of
the build side.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from caps_tpu.ops.compact import kept_first


# ---------------------------------------------------------------------------
# expand positions: invert offsets = cumsum(counts) for every output slot
# ---------------------------------------------------------------------------

# Output slots per grid step.  Fixed at 1,024: the input windows are 1-D
# blocks whose block index is data-dependent (scalar prefetch), and for
# those Mosaic accepts only the full 1,024-element tile of a 1-D 32-bit
# operand — 256 and 512 are refused when compiling for v5e ("Input
# offsets outside of the first tile").  Same rule as ops/segment.py.
TILE = 1024


def _expand_kernel(blk_ref, seg_base_ref, total_ref,
                   offs_a, offs_b, lo_a, lo_b, orig_a, orig_b,
                   l_out, pos_out, valid_out, *, tile: int):
    # int32 literal, not a Python 0: the engine runs with x64 on, where a
    # bare 0 reaches jnp.where as a weak i64 scalar and its convert back
    # to i32 recurses without end in the Mosaic lowering (RecursionError
    # in _convert_helper when compiling for v5e, jax 0.9.0)
    zero = jnp.int32(0)
    i = pl.program_id(0)
    t = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)  # (1,T)
    offs = jnp.concatenate([offs_a[:], offs_b[:]]).reshape(2 * tile, 1)
    le = offs <= t                                  # (2T, T)
    cnt = jnp.sum(le.astype(jnp.int32), axis=0, dtype=jnp.int32)  # (T,)
    # seg_start = offsets[l_idx - 1]: the largest window offset <= t, or
    # the prelude-computed base when the window has no hit (cnt == 0 can
    # only happen when the tile starts exactly at a block boundary, in
    # which case seg_base IS offsets[l_idx-1]).
    seg = jnp.max(jnp.where(le, offs, zero), axis=0)
    seg = jnp.maximum(seg, seg_base_ref[i])
    # one-hot select of lo / original-row at window position cnt
    w = jax.lax.broadcasted_iota(jnp.int32, (2 * tile, tile), 0)
    onehot = w == cnt.reshape(1, tile)
    lo_win = jnp.concatenate([lo_a[:], lo_b[:]]).reshape(2 * tile, 1)
    orig_win = jnp.concatenate([orig_a[:], orig_b[:]]).reshape(2 * tile, 1)
    lo_t = jnp.sum(jnp.where(onehot, lo_win, zero), axis=0, dtype=jnp.int32)
    orig_t = jnp.sum(jnp.where(onehot, orig_win, zero), axis=0,
                     dtype=jnp.int32)
    tt = t.reshape(tile)
    l_out[:] = orig_t
    pos_out[:] = lo_t + (tt - seg)
    valid_out[:] = (tt < total_ref[0]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_cap", "interpret"))
def expand_positions(counts: jnp.ndarray, lo: jnp.ndarray, out_cap: int,
                     interpret: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """For each output slot t in [0, out_cap): the left row index it
    expands from, the match position ``lo[row] + within``, and validity.

    counts: (cap_l,) >=0 int; lo: (cap_l,) int — per-row match start.
    Returns (l_idx int32, r_pos int32, out_valid bool), each (out_cap,).
    """
    cap_l = counts.shape[0]
    tile = TILE
    if out_cap % tile:
        # non-tileable capacity (the 256 bucket, custom bucket_sizes): a
        # static choice by shape — the jnp twin is exact
        return expand_positions_ref(counts, lo, out_cap)
    n_tiles = out_cap // tile

    counts32 = counts.astype(jnp.int32)
    # -- prelude (XLA): compact away zero-count rows, by one key sort that
    # carries each row's count and match start along (no gather) --------
    key, nz_counts, lo_nz = kept_first(counts32 > 0, counts32,
                                       lo.astype(jnp.int32))
    slot_live = key < cap_l
    nz_counts = jnp.where(slot_live, nz_counts, 0)
    offsets = jnp.cumsum(nz_counts, dtype=jnp.int32)        # (cap_l,)
    total = offsets[-1] if cap_l else jnp.int32(0)
    lo_nz = jnp.where(slot_live, lo_nz, 0)
    orig_nz = jnp.where(slot_live, key, 0).astype(jnp.int32)

    # pad to a tile multiple so any window [blk*T, blk*T + 2T) is in
    # range; padded offsets repeat `total`, which only ever counts for
    # t >= total (masked out)
    pad = ((-cap_l) % tile) + 2 * tile
    offsets_p = jnp.concatenate(
        [offsets, jnp.full((pad,), total, jnp.int32)])
    lo_p = jnp.concatenate([lo_nz, jnp.zeros((pad,), jnp.int32)])
    orig_p = jnp.concatenate([orig_nz, jnp.zeros((pad,), jnp.int32)])

    # per-tile block + seg_base (tiny: n_tiles elements)
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    row_start = jnp.searchsorted(offsets, tile_starts,
                                 side="right").astype(jnp.int32)
    blk = row_start // tile
    seg_base = jnp.where(row_start > 0,
                         offsets[jnp.maximum(row_start - 1, 0)], 0)

    kernel = functools.partial(_expand_kernel, tile=tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i],),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i] + 1,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i],),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i] + 1,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i],),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (blk[i] + 1,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i, blk, sb, tot: (i,),
                         memory_space=pltpu.VMEM),
        ],
    )
    l_idx, r_pos, valid = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((out_cap,), jnp.int32),
            jax.ShapeDtypeStruct((out_cap,), jnp.int32),
            jax.ShapeDtypeStruct((out_cap,), jnp.int32),
        ],
        interpret=interpret,
    )(blk, seg_base, jnp.full((1,), total, jnp.int32),
      offsets_p, offsets_p, lo_p, lo_p, orig_p, orig_p)
    ok = valid != 0
    # invalid slots are don't-cares; normalize for deterministic equality
    # with the jnp twin
    return (jnp.where(ok, l_idx, 0), jnp.where(ok, r_pos, 0), ok)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def expand_positions_ref(counts, lo, out_cap: int):
    """jnp twin (searchsorted formulation) for differential tests."""
    counts = counts.astype(jnp.int64)
    offsets = jnp.cumsum(counts)
    total = offsets[-1] if counts.shape[0] else jnp.int64(0)
    t = jnp.arange(out_cap)
    l_idx = jnp.searchsorted(offsets, t, side="right")
    l_idx = jnp.clip(l_idx, 0, max(0, counts.shape[0] - 1))
    seg_start = jnp.where(l_idx > 0, offsets[jnp.maximum(l_idx - 1, 0)], 0)
    within = t - seg_start
    r_pos = lo.astype(jnp.int64)[l_idx] + within
    valid = t < total
    # align with the kernel on invalid slots (values are don't-cares, but
    # deterministic equality keeps the differential test exact)
    return (jnp.where(valid, l_idx, 0).astype(jnp.int32),
            jnp.where(valid, r_pos, 0).astype(jnp.int32),
            valid)


def join_expand_via_positions(counts, lo, perm, l_ok, out_cap: int,
                              left_join: bool, interpret: bool = False):
    """Full join materialization on top of :func:`expand_positions`:
    returns (l_idx, r_idx, out_valid, r_matched) with the same semantics
    as kernels.join_expand (left-join rows with no match emit one
    null-extended row)."""
    matched = counts > 0
    eff = jnp.where(left_join & l_ok & ~matched, 1, counts)
    l_idx, r_pos, out_valid = expand_positions(eff, lo, out_cap,
                                               interpret=interpret)
    r_pos = jnp.clip(r_pos, 0, perm.shape[0] - 1)
    r_idx = perm[r_pos]
    r_matched = out_valid & matched[l_idx]
    return l_idx, r_idx, out_valid, r_matched


# ---------------------------------------------------------------------------
# Device-resident CSR adjacency
# ---------------------------------------------------------------------------


@jax.jit
def csr_probe(indptr: jnp.ndarray, keys: jnp.ndarray, ok: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-probe-row (counts, lo) out of a CSR's ``indptr``: two gathers,
    no search, one program.  The domain is ``indptr.shape[0] - 1``; the
    comparison happens in the key's own dtype (int64 keys must not be
    truncated before the range check), so masked, negative and
    out-of-domain keys count 0."""
    n_keys = indptr.shape[0] - 1
    in_domain = ok & (keys >= 0) & (keys < n_keys)
    safe = jnp.where(in_domain, keys, 0).astype(jnp.int32)
    lo = indptr[safe]
    hi = indptr[safe + 1]
    counts = jnp.where(in_domain, hi - lo, 0)
    return counts, lo


@dataclasses.dataclass
class DeviceCSR:
    """HBM-resident CSR index over one int-key column of an ingested
    table (a relationship table's source or target, a node table's id):
    ``perm`` lists row indices grouped by key; rows for key k live at
    ``perm[indptr[k] : indptr[k+1]]``.  Domain is [0, n_keys)."""
    indptr: jnp.ndarray   # (n_keys + 1,) int32
    perm: jnp.ndarray     # (capacity,) int32
    n_keys: int

    def probe(self, keys: jnp.ndarray, ok: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-probe-row (counts, lo), see :func:`csr_probe`."""
        return csr_probe(self.indptr, keys, ok)


# CSR domains above this multiple of the column capacity fall back to the
# sort path (indptr would dwarf the data it indexes).
_MAX_DOMAIN_FACTOR = 8
_MIN_DOMAIN = 1 << 16


def build_csr(keys: jnp.ndarray, ok: jnp.ndarray, n: int,
              use_native: bool = True) -> Optional[DeviceCSR]:
    """CSR over ``keys[:n]`` (rows with ``ok`` False are excluded).

    Host-built by the C++ runtime when available (the ingest-time physical
    layout), else device-built from one sort.  Returns None when the key
    domain is unsuitable (negative / too sparse)."""
    cap = int(keys.shape[0])
    host_keys = np.asarray(keys[:n]).astype(np.int64)
    live = np.asarray(ok[:n]).astype(bool)
    if live.any() and int(host_keys[live].min()) < 0:
        # negative keys are legal on the sort path; CSR indexes [0, n_keys)
        return None
    if not live.any():
        n_keys = 1
    else:
        mx = int(host_keys[live].max())
        if mx >= max(_MIN_DOMAIN, _MAX_DOMAIN_FACTOR * max(cap, 1)):
            return None
        n_keys = mx + 1
    host_keys = np.where(live, host_keys, 0)
    from caps_tpu import native
    if use_native and native.lib is not None:
        # shunt masked rows to a sentinel bucket past the real domain
        shunted = np.where(live, host_keys, n_keys)
        off_b, perm_b = native.lib.csr_build(
            shunted.tobytes(), len(shunted), n_keys + 1)
        indptr = np.frombuffer(off_b, np.int64)[:n_keys + 1]
        perm = np.frombuffer(perm_b, np.int64)
    else:
        shunted = np.where(live, host_keys, n_keys)
        perm = np.argsort(shunted, kind="stable")
        sorted_keys = shunted[perm]
        indptr = np.searchsorted(sorted_keys, np.arange(n_keys + 1),
                                 side="left")
    perm_pad = np.zeros(cap, np.int32)
    perm_pad[:len(perm)] = perm.astype(np.int32)
    return DeviceCSR(jnp.asarray(indptr.astype(np.int32)),
                     jnp.asarray(perm_pad), n_keys)

"""Dense-domain segment aggregation: the group-by histogram kernel.

The reference's ``group`` delegates to Spark's hash aggregation
(ref: spark-cypher/.../impl/table/SparkTable.scala ``group`` via
``RelationalGroupedDataset`` — reconstructed, mount empty; SURVEY.md §2).
TPUs have no scatter-friendly hash tables, and ``lax.sort`` is O(n log²n)
on the VPU — but our string pool already dictionary-encodes group keys to
*dense* int32 codes, so a group-by over a string/bool key is a histogram
over a small dense domain.  This kernel aggregates straight into the
code-indexed output with no sort and no scatter:

    grid = (segment_tiles, row_tiles)   # row tiles innermost
    hit[r, s] = (codes[r] == s) & ok[r]          (VPU compare)
    count:  out[s] += Σ_r hit[r, s]              (VPU reduce)
    sum:    out[s] += v[None, :] @ hit           (MXU matmul)
    min/max: out[s] = min/max(out[s], Σ-free masked reduce)

The output block (one segment tile) stays resident in VMEM while the row
tiles stream through — the classic Pallas accumulation pattern.

Integer sums are NOT offered in f32 (exactness); the engine routes int
sums to the sorted path and uses this kernel for count/min/max and f32
sums where rounding semantics allow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Blocks are 1-D; the TPU compiler verifies Mosaic's derived layout
# against XLA's, and XLA tiles a 1-D 32-bit operand of padded size S at
# T(min(1024, S)) — so every 1-D block (inputs AND output) must be
# exactly min(1024, padded_array_size) or Mosaic is rejected with
# "XLA layout ({0:T(1024)}) does not match Mosaic layout ({0:T(512)})"
# (observed on v5e at s32[4096]/block 512 and s32[256]/block 128).
# Rows therefore pad to 1024 multiples with a fixed 1024 tile; the
# segment axis uses ONE whole-array block up to 1024 and 1024-tiles
# beyond.  ops/expand.py's TILE follows the same rule.
ROW_TILE = 1024
SEG_QUANTUM = 128

_KINDS = ("count", "sum_f32", "sum_i32", "min_i32", "max_i32",
          "min_f32", "max_f32")

_IDENT = {
    "min_i32": jnp.iinfo(jnp.int32).max,
    "max_i32": jnp.iinfo(jnp.int32).min,
    "min_f32": jnp.inf,
    "max_f32": -jnp.inf,
}


def default_interpret() -> bool:
    """Compiled on TPU; interpreter elsewhere (CPU unit suite)."""
    return jax.default_backend() != "tpu"


def _out_dtype(kind: str):
    return jnp.float32 if kind.endswith("f32") else jnp.int32


def _agg_kernel(codes_ref, ok_ref, val_ref, out_ref, *, kind: str,
                row_tile: int, seg_tile: int):
    i = pl.program_id(1)  # row tile (innermost: out block stays resident)
    j = pl.program_id(0)
    seg = j * seg_tile + jax.lax.broadcasted_iota(
        jnp.int32, (row_tile, seg_tile), 1)
    # reshape the int32 refs BEFORE comparing: Mosaic cannot insert a minor
    # dim on i1 vectors ("only supported for 32-bit types")
    codes2d = codes_ref[:].reshape(row_tile, 1)
    ok2d = ok_ref[:].reshape(row_tile, 1) != 0
    hit = (codes2d == seg) & ok2d
    # NB: dtype= on the reductions — x64 mode is enabled globally and the
    # default int32→int64 promotion does not lower on Mosaic TPU.
    if kind == "count":
        part = jnp.sum(hit.astype(jnp.int32), axis=0, dtype=jnp.int32)
    elif kind == "sum_f32":
        v = jnp.where(ok_ref[:] != 0, val_ref[:], jnp.float32(0))
        # HIGHEST: the MXU's default f32 precision truncates operands to
        # bf16, which is visible data loss in an aggregate (observed
        # ~2e-2 abs drift on v5e); bf16x6 passes restore f32 sums
        part = jnp.dot(v.reshape(1, row_tile), hit.astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST
                       ).reshape(seg_tile)
    elif kind == "sum_i32":
        v = val_ref[:].reshape(row_tile, 1)
        part = jnp.sum(jnp.where(hit, v, jnp.int32(0)), axis=0,
                       dtype=jnp.int32)
    elif kind in ("min_i32", "min_f32"):
        v = val_ref[:].reshape(row_tile, 1)
        ident = jnp.asarray(_IDENT[kind], val_ref.dtype)
        part = jnp.min(jnp.where(hit, v, ident), axis=0)
    elif kind in ("max_i32", "max_f32"):
        v = val_ref[:].reshape(row_tile, 1)
        ident = jnp.asarray(_IDENT[kind], val_ref.dtype)
        part = jnp.max(jnp.where(hit, v, ident), axis=0)
    else:  # pragma: no cover
        raise ValueError(f"unknown kind {kind}")

    @pl.when(i == 0)
    def _init():
        out_ref[:] = part

    @pl.when(i != 0)
    def _accumulate():
        if kind.startswith("min"):
            out_ref[:] = jnp.minimum(out_ref[:], part)
        elif kind.startswith("max"):
            out_ref[:] = jnp.maximum(out_ref[:], part)
        else:
            out_ref[:] = out_ref[:] + part


def _pad1(x, multiple: int, fill):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem:
        x = jnp.concatenate([x, jnp.full((rem,), fill, x.dtype)])
    return x


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "kind", "interpret"))
def dense_segment_agg(codes: jnp.ndarray, ok: jnp.ndarray,
                      values, num_segments: int, kind: str,
                      interpret: bool = False) -> jnp.ndarray:
    """Aggregate ``values`` (or row counts) into ``num_segments`` dense
    slots indexed by ``codes``; rows with ``ok == False`` are ignored.

    codes: (n,) int32 in [0, num_segments); ok: (n,) bool;
    values: (n,) f32/i32 (ignored for kind="count" — pass codes).
    """
    assert kind in _KINDS, kind
    n = codes.shape[0]
    if n == 0:
        ident = _IDENT.get(kind, 0)
        return jnp.full((num_segments,), ident, _out_dtype(kind))
    row_tile = ROW_TILE  # fixed: sub-1024 1-D blocks fail layout checks
    codes_p = _pad1(codes.astype(jnp.int32), row_tile, -1)
    ok_p = _pad1(ok.astype(jnp.int32), row_tile, 0)
    if kind == "count":
        vals_p = codes_p  # unused; same shape keeps the specs uniform
    else:
        want = jnp.float32 if kind.endswith("f32") else jnp.int32
        vals_p = _pad1(values.astype(want), row_tile, 0)
    seg_pad = ((num_segments + SEG_QUANTUM - 1) // SEG_QUANTUM) * SEG_QUANTUM
    if seg_pad > 1024:
        seg_tile = 1024
        seg_pad = ((seg_pad + 1023) // 1024) * 1024
    else:
        seg_tile = seg_pad  # single whole-array output block
    n_pad = codes_p.shape[0]
    grid = (seg_pad // seg_tile, n_pad // row_tile)
    kernel = functools.partial(_agg_kernel, kind=kind, row_tile=row_tile,
                               seg_tile=seg_tile)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((seg_tile,), lambda j, i: (j,),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((seg_pad,), _out_dtype(kind)),
        interpret=interpret,
    )(codes_p, ok_p, vals_p)
    return out[:num_segments]


@functools.lru_cache(maxsize=256)
def _sharded_agg_fn(mesh, num_segments: int, kind: str, interpret: bool):
    from caps_tpu.obs.compile import charged as _compile_charged
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # rows split over EVERY mesh axis (matches DeviceBackend.place_rows):
    # on a 2-D DCN x ICI mesh each device keeps its own row block and only
    # the final (num_segments,) partials cross DCN in the combine
    axes = tuple(mesh.axis_names)

    def body(c, o, v):
        local = dense_segment_agg(c, o, v, num_segments, kind,
                                  interpret=interpret)
        if kind.startswith("min"):
            return jax.lax.pmin(local, axes)
        if kind.startswith("max"):
            return jax.lax.pmax(local, axes)
        return jax.lax.psum(local, axes)

    # check_vma=False: pallas_call outputs don't carry varying-mesh-axis
    # metadata, so shard_map's vma checker can't see through them.
    # An lru_cache miss here is a compile boundary (obs/compile.py).
    with _compile_charged("dist_join",
                          shape=f"segagg:{num_segments}:{kind}"):
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P(axes), P(axes), P(axes)),
                                 out_specs=P(), check_vma=False))


def dense_segment_agg_sharded(mesh, axis: str, codes, ok, values,
                              num_segments: int, kind: str,
                              interpret: bool = False) -> jnp.ndarray:
    """Distributed histogram: each device aggregates its row block with
    the Pallas kernel, partials combine over the mesh (psum / pmin /
    pmax; ICI within a slice, DCN only for the final partials) — the
    engine's partial-aggregation shuffle (SURVEY.md §5.8).  The jitted
    shard_map program is cached per (mesh, segments, kind)."""
    del axis  # rows always split over every mesh axis (place_rows layout)
    fn = _sharded_agg_fn(mesh, num_segments, kind, interpret)
    return fn(codes.astype(jnp.int32), ok,
              values if kind != "count" else codes.astype(jnp.int32))


def dense_segment_agg_ref(codes, ok, values, num_segments: int,
                          kind: str) -> jnp.ndarray:
    """jnp reference twin (tests only — SURVEY.md §2 native components)."""
    codes = codes.astype(jnp.int32)
    safe = jnp.where(ok, codes, num_segments)  # shunt masked rows off-range
    if kind == "count":
        return jax.ops.segment_sum(ok.astype(jnp.int32), safe,
                                   num_segments=num_segments + 1
                                   )[:num_segments]
    want = jnp.float32 if kind.endswith("f32") else jnp.int32
    v = values.astype(want)
    if kind.startswith("sum"):
        out = jax.ops.segment_sum(jnp.where(ok, v, 0), safe,
                                  num_segments=num_segments + 1)
        return out[:num_segments]
    ident = jnp.asarray(_IDENT[kind], want)
    v = jnp.where(ok, v, ident)
    fn = jax.ops.segment_min if kind.startswith("min") else jax.ops.segment_max
    out = fn(v, safe, num_segments=num_segments + 1)[:num_segments]
    # segment_min/max fill empty segments with dtype extremes; align to ident
    return jnp.where(jnp.isin(jnp.arange(num_segments), safe), out, ident)

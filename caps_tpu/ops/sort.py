"""Pallas multi-column bitonic sort — the order_by / distinct / group-by
sort permutation kernel.

The reference delegates sorting to Spark's shuffle/Tungsten sort (ref:
spark-cypher/.../impl/table/SparkTable.scala ``orderBy``/``distinct`` —
reconstructed, mount empty; SURVEY.md §2 native components).  Here the
whole multi-key comparator network runs in one Pallas kernel, VMEM
resident (SURVEY.md §7 step 6, the last jnp stand-in the survey named).

Layout.  The flat array of ``cap = R·128`` elements maps COLUMN-major
onto a (R, 128) tile: flat index ``i = r + R·c``.  A bitonic
compare-exchange at distance ``d`` pairs ``i ↔ i^d``:

  * ``d < R``  (77 of 105 stages at cap=16k): a SUBLANE permutation —
    implemented as reshape (R/2d, 2, d, 128) + swap of the middle pair +
    reshape back, i.e. static slices/concats Mosaic handles natively;
  * ``d ≥ R``: a LANE permutation with XOR stride ``d/R`` — the tile is
    transposed (≤128×128), the same sublane swap applied, transposed
    back.  Only the top log2(128/R)+… stages pay the two transposes.

Multi-column keys arrive as int32 PLANES (``split_planes``): int64 keys
split into (hi, biased-lo) pairs — exact for the full 64-bit range, in
particular ints ≥ 2^53 that a float64 squeeze would collide — and
float64 keys bitcast through the standard monotone mapping that matches
XLA's total order (-NaN < -Inf < … < -0 < +0 < … < +Inf < +NaN).  The
comparator chains plane-wise (gt, eq) lexicographically with the running
row index as the final tiebreaker, which makes the network a strict
total order and therefore STABLE — bit-identical permutations to the
``lax.sort(…, is_stable=True)`` twin (kernels.sort_perm), which remains
the differential-test oracle and the fallback for shapes the tile form
does not cover.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

jax.config.update("jax_enable_x64", True)

LANES = 128
ROWS_MAX = 128          # one tile: cap <= 128*128 = 16384 elements
_I64_MIN = jnp.int64(-(2 ** 63))


def sort_cap_supported(cap: int) -> bool:
    """True when the one-tile kernel covers this capacity."""
    r = cap // LANES
    return (cap % LANES == 0 and 2 <= r <= ROWS_MAX
            and (r & (r - 1)) == 0)


def split_planes(keys: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """Lexicographic key columns -> int32 comparison planes (see module
    docstring).  Ascending int32 order on the planes == ascending
    int64/float64 total order on the originals."""
    out: List[jnp.ndarray] = []
    for k in keys:
        if k.dtype == jnp.float64:
            b = jax.lax.bitcast_convert_type(k, jnp.int64)
            k = jnp.where(b >= 0, b, (~b) ^ _I64_MIN)
        if k.dtype == jnp.int64:
            out.append((k >> 32).astype(jnp.int32))
            out.append(((k & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32))
        else:  # bool / int32 already compare correctly in int32
            out.append(k.astype(jnp.int32))
    return out


def _swap_rows(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """y[r, c] = x[r ^ d, c] for power-of-two d < R (static slices)."""
    r, c = x.shape
    g = x.reshape(r // (2 * d), 2, d, c)
    g = jnp.concatenate([g[:, 1], g[:, 0]], axis=1)
    return g.reshape(r, c)


def _partner(x: jnp.ndarray, d: int, rows: int) -> jnp.ndarray:
    if d < rows:
        return _swap_rows(x, d)
    return _swap_rows(x.T, d // rows).T


def _exchange_step(planes: List[jnp.ndarray], i_mat: jnp.ndarray,
                   dir_bit: jnp.ndarray, d: int,
                   rows: int) -> List[jnp.ndarray]:
    """One compare-exchange stage at static distance ``d`` — THE shared
    comparator body: both the XLA twin (differential tests) and the
    Pallas kernel call exactly this, so the tests validate the kernel's
    logic, not a copy."""
    partners = [_partner(p, d, rows) for p in planes]
    gt = jnp.zeros((rows, LANES), jnp.bool_)
    eq = jnp.ones((rows, LANES), jnp.bool_)
    for a, b in zip(planes, partners):
        gt = gt | (eq & (a > b))
        eq = eq & (a == b)
    take_min = ((i_mat & d) == 0) ^ (dir_bit == 1)
    # NOT jnp.where(take_min, gt, ~gt): a select over BOOL operands
    # lowers through an i8->i1 vector trunci Mosaic rejects on TPU
    # (observed on v5e); the XOR form is identical.
    sel_p = ~(gt ^ take_min)
    return [jnp.where(sel_p, pb, pa) for pa, pb in zip(planes, partners)]


def _network(planes: List[jnp.ndarray], rows: int,
             total_levels: int) -> jnp.ndarray:
    """The full bitonic network on (rows, 128) tiles; returns the
    original-position payload tile.  Pure jnp — the CPU twin and the
    differential tests run it directly under XLA; the Pallas kernel
    steps the same _exchange_step per grid step."""
    # running original-position payload; also the final comparator
    # tiebreaker, which makes the order strict (=> stable network)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    i_mat = r_iota + rows * c_iota
    planes = list(planes) + [i_mat]

    for m in range(1, total_levels + 1):
        dir_bit = (i_mat >> m) & 1  # 1 = descending block this level
        d = 1 << (m - 1)
        while d >= 1:
            planes = _exchange_step(planes, i_mat, dir_bit, d, rows)
            d //= 2
    return planes[-1]


def _stage_kernel(*refs, rows: int, total_levels: int):
    """One grid step = one compare-exchange stage of the network.

    Fully unrolling the 105-stage network into one Mosaic program hangs
    the TPU compiler (observed >7 min at cap=256), so the grid iterates
    stages instead: program_id = (level-1, within-level j), distance
    d = 2^(level-1-j), and the body predicates over the log2(cap)
    possible static distances with pl.when — each branch carries the
    static-shape swap that distance needs.  Plane refs are input/output
    aliased whole-array blocks, so they stay VMEM-resident across the
    whole grid; steps with j >= level are no-ops (the rectangular grid
    over a triangular stage table)."""
    n = len(refs) // 2
    in_refs, out_refs = refs[:n], refs[n:]
    m = pl.program_id(0) + 1          # level: merge size 2^m
    j = pl.program_id(1)              # stage within level
    first = (m == 1) & (j == 0)
    k_idx = (m - 1) - j               # d = 2^k_idx

    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    i_mat = r_iota + rows * c_iota

    @pl.when(first)
    def _load():
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[:, :] = i_ref[:, :]

    @pl.when(j < m)
    def _stage():
        planes = [o[:, :] for o in out_refs]
        dir_bit = (i_mat >> m) & 1
        for k in range(total_levels):
            @pl.when(k_idx == k)
            def _exchange(k=k):
                new = _exchange_step(planes, i_mat, dir_bit, 1 << k, rows)
                for o_ref, p in zip(out_refs, new):
                    o_ref[:, :] = p


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_perm(planes: Tuple[jnp.ndarray, ...],
                      interpret: bool = False) -> jnp.ndarray:
    """Stable ascending-lexicographic sort permutation of int32 planes
    (cap,), cap = R*128 with R a power of two <= 128."""
    cap = planes[0].shape[0]
    rows = cap // LANES
    assert sort_cap_supported(cap), cap
    total_levels = cap.bit_length() - 1
    tiles = [p.reshape(LANES, rows).T for p in planes]  # [r,c]=flat[r+R*c]
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    tiles = tiles + [r_iota + rows * c_iota]  # position payload/tiebreak
    kernel = functools.partial(_stage_kernel, rows=rows,
                               total_levels=total_levels)
    # index maps must yield int32: under this module's x64 mode plain
    # Python 0s trace as i64 and Mosaic rejects the (i64, i64) block
    # index (observed on v5e) — the same family of fault as the weak
    # i64 literal in ops/expand.py's kernel body
    whole = pl.BlockSpec((rows, LANES),
                         lambda m, j: (jnp.int32(0), jnp.int32(0)))
    outs = pl.pallas_call(
        kernel,
        grid=(total_levels, total_levels),
        in_specs=[whole] * len(tiles),
        out_specs=[whole] * len(tiles),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)
                   for _ in tiles],
        interpret=interpret,
    )(*tiles)
    return outs[-1].T.reshape(cap)


def bitonic_sort_perm_twin(planes: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """The identical network under plain XLA, EAGER on purpose — the
    differential twin for the CPU suite.  (Jitting the unrolled network
    through XLA:CPU takes ~30 s at cap=256; op-by-op dispatch runs it in
    seconds and tests only need values, not speed.)"""
    cap = planes[0].shape[0]
    rows = cap // LANES
    assert sort_cap_supported(cap), cap
    tiles = [p.reshape(LANES, rows).T for p in planes]
    out = _network(tiles, rows, cap.bit_length() - 1)
    return out.T.reshape(cap)


def sort_perm_pallas(keys: Sequence[jnp.ndarray], cap: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Drop-in for kernels.sort_perm on supported capacities: same key
    contract (pre-transformed columns, nulls folded), same stable
    ascending permutation, int32 positions."""
    planes = split_planes(keys)
    return bitonic_sort_perm(tuple(planes), interpret=interpret)


def validate(compiled: bool = False, seed: int = 0) -> dict:
    """Differential validation of the bitonic sort-permutation against
    the ``lax.sort`` reference across capacities and key mixes.

    ``compiled=False`` exercises the kernel's ROUTING logic (plane
    splitting, tiling, network schedule) through the eager XLA twin —
    provable without a chip.  ``compiled=True`` runs the real
    pallas_call on the active backend.
    Returns {"cases": n, "failures": [...]}.
    """
    import numpy as np
    from caps_tpu.backends.tpu import kernels as K

    rng = np.random.RandomState(seed)
    failures = []
    cases = 0
    # routing (eager-twin) validation: small caps — the op-by-op network
    # at cap 1024 takes minutes on CPU; the compiled sweep covers them
    caps = [c for c in ((128, 256) if not compiled
                        else (128, 256, 512, 1024))
            if sort_cap_supported(c)]
    for cap in caps:
        for nkeys in (1, 2, 3):
            for rep in range(2):
                keys = []
                for _ in range(nkeys):
                    if rep == 0:  # heavy duplicates: stability stress
                        keys.append(jnp.asarray(
                            rng.randint(0, 4, cap).astype(np.int64)))
                    else:
                        keys.append(jnp.asarray(
                            rng.randint(-(2**40), 2**40, cap)
                            .astype(np.int64)))
                want = np.asarray(K.sort_perm(keys, cap))
                if compiled:
                    got = np.asarray(sort_perm_pallas(keys, cap))
                else:
                    got = np.asarray(bitonic_sort_perm_twin(
                        tuple(split_planes(keys))))
                cases += 1
                if not np.array_equal(want, got):
                    failures.append((cap, nkeys, rep))
    return {"cases": cases, "failures": failures}

"""The shared iterative-fixpoint executor: fixed-shape device programs.

Every procedure's device path but ``algo.bfs``'s (a frontier search
over CSRs, ``ops/bfs.py``) is ONE jitted program built per
``(procedure, node capacity, edge capacity)``: the node and edge arrays
are padded to shape-lattice buckets (``relational/shapes.py``) and the
iteration runs as a ``lax.while_loop`` whose carried state has a fixed
shape — so a compiled program is replayable across snapshots, deltas,
and parameter bindings whose sizes land in the same buckets, and the
data-dependent convergence (the *number* of iterations) never changes
the compiled shape.  Scalars (damping, tolerance, iteration caps, the
live node count) ride as 0-d operands, not trace-time constants, so a
parameter sweep reuses one program.

Off-TPU the same jnp program runs under ``jax.jit`` on the CPU backend
— the jnp twin — which is also what the differential tests exercise.
Dead lanes are masked: padded nodes carry zero rank / identity labels /
unreached distances, padded edges a zero mask, and every step keeps the
masked lanes at their fixpoint so they can never leak into live lanes.

``build_program`` returns a compiled callable
``fn(node_mask, src, tgt, edge_mask, weights, scalars) ->
(out, iterations, converged)`` with NO internal caching — the operator
(`algo/op.py`) owns the per-backend program cache and charges the
``algo`` compile-ledger kind exactly once per first-seen shape.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

jax.config.update("jax_enable_x64", True)


def _loop(cond_extra, body, state0, cap):
    """``lax.while_loop`` with the shared (iteration < cap) guard; the
    carry is ``(i, state, done)``, ``body`` maps state -> (state, done),
    and the call site receives ``(state, iterations, done)``."""
    def cond(c):
        i, state, done = c
        return (i < cap) & jnp.logical_not(done) & cond_extra(state)

    def step(c):
        i, state, _ = c
        nstate, done = body(state)
        return i + 1, nstate, done

    i, state, done = lax.while_loop(
        cond, step,
        (jnp.asarray(0, jnp.int64), state0, jnp.asarray(False)))
    return state, i, done


def _degree(node_mask, src, tgt, edge_mask, weights, scalars):
    one = edge_mask.astype(jnp.int64)
    n_pad = node_mask.shape[0]
    mode = scalars["direction_code"]  # 0=out 1=in 2=both
    deg = jnp.zeros(n_pad, jnp.int64)
    out_part = jnp.zeros(n_pad, jnp.int64).at[src].add(one)
    in_part = jnp.zeros(n_pad, jnp.int64).at[tgt].add(one)
    deg = jnp.where(mode != 1, deg + out_part, deg)
    deg = jnp.where(mode != 0, deg + in_part, deg)
    return deg, jnp.asarray(1, jnp.int64), jnp.asarray(True)


def _pagerank(node_mask, src, tgt, edge_mask, weights, scalars):
    n_pad = node_mask.shape[0]
    live = node_mask.astype(jnp.float64)
    n_live = jnp.maximum(scalars["n_live"].astype(jnp.float64), 1.0)
    d = scalars["damping"]
    tol = scalars["tolerance"]
    e_live = edge_mask.astype(jnp.float64)
    out_deg = jnp.zeros(n_pad, jnp.float64).at[src].add(e_live)
    r0 = live / n_live
    base = (1.0 - d) / n_live

    def body(state):
        r, _delta = state
        contrib = jnp.where(out_deg > 0, r / jnp.maximum(out_deg, 1.0),
                            0.0)
        nxt = jnp.zeros(n_pad, jnp.float64).at[tgt].add(
            contrib[src] * e_live)
        dangling = jnp.sum(r * live * (out_deg == 0))
        nxt = live * (base + d * (nxt + dangling / n_live))
        delta = jnp.abs(nxt - r).sum()
        return (nxt, delta), delta <= tol

    (r, _), it, done = _loop(lambda s: jnp.asarray(True), body,
                             (r0, jnp.asarray(jnp.inf)),
                             scalars["max_iterations"])
    # NOT quantized here: XLA may rewrite the /10^d into a reciprocal
    # multiply and drift an ulp from numpy — the operator quantizes on
    # the host (np.round, same function as the oracle) after transfer
    return r, it, done


def _wcc(node_mask, src, tgt, edge_mask, weights, scalars):
    n_pad = node_mask.shape[0]
    idx = jnp.arange(n_pad, dtype=jnp.int64)
    # dead edges self-loop on lane 0 of the label array; min with a
    # live lane's own label is a no-op only if they carry the lane's
    # value — route them to a scatter that cannot lower anything by
    # pointing both endpoints at the label they already carry
    big = jnp.asarray(jnp.iinfo(jnp.int64).max, jnp.int64)

    def body(state):
        label = state
        ls = jnp.where(edge_mask, label[src], big)
        lt = jnp.where(edge_mask, label[tgt], big)
        nxt = label.at[tgt].min(ls)
        nxt = nxt.at[src].min(lt)
        nxt = nxt[nxt]  # pointer jumping (matches the host twin)
        return nxt, jnp.all(nxt == label)

    label, it, done = _loop(lambda s: jnp.asarray(True), body, idx,
                            scalars["max_iterations"])
    return label, it, done


def _sssp(node_mask, src, tgt, edge_mask, weights, scalars):
    n_pad = node_mask.shape[0]
    source = scalars["source_index"]
    in_range = (source >= 0) & (source < scalars["n_live"])
    w = jnp.where(edge_mask, jnp.maximum(weights, 0.0), jnp.inf)
    dist0 = jnp.full(n_pad, jnp.inf, jnp.float64)
    dist0 = jnp.where((jnp.arange(n_pad) == source) & in_range,
                      0.0, dist0)
    cap = scalars["max_iterations"]
    cap = jnp.where(cap >= 0, cap, jnp.asarray(n_pad, jnp.int64))

    def body(state):
        dist = state
        cand = dist[src] + w
        nxt = dist.at[tgt].min(cand)
        return nxt, jnp.all(nxt == dist)

    dist, it, done = _loop(lambda s: jnp.asarray(True), body, dist0, cap)
    return dist, it, done  # quantized host-side, like _pagerank


_DEVICE_KERNELS = {
    "algo.degree": _degree,
    "algo.pagerank": _pagerank,
    "algo.wcc": _wcc,
    "algo.sssp": _sssp,
}

#: scalar operand names per procedure, in a fixed order (the jitted
#: program's positional tail — names keyed out of the bound-args dict)
SCALAR_OPERANDS: Dict[str, Tuple[str, ...]] = {
    "algo.degree": ("direction_code",),
    "algo.pagerank": ("n_live", "damping", "max_iterations", "tolerance"),
    "algo.wcc": ("max_iterations",),
    "algo.sssp": ("n_live", "source_index", "max_iterations"),
}

_FLOAT_SCALARS = frozenset({"damping", "tolerance"})


def scalar_values(name: str, bound: Dict[str, Any], n_live: int) -> tuple:
    """The jnp scalar operands for one bound call, in operand order."""
    pool = dict(bound)
    pool["n_live"] = n_live
    if name == "algo.degree":
        pool["direction_code"] = {"out": 0, "in": 1,
                                  "both": 2}[pool["direction"]]
    out = []
    for key in SCALAR_OPERANDS[name]:
        v = pool[key]
        dtype = jnp.float64 if key in _FLOAT_SCALARS else jnp.int64
        out.append(jnp.asarray(v, dtype))
    return tuple(out)


def build_program(name: str, n_pad: int, e_pad: int):
    """Build (and first-compile via ``jax.jit``) the fixed-shape program
    for one procedure at one (node, edge) capacity pair.  The caller
    caches the returned callable and owns the compile-ledger charge."""
    kernel = _DEVICE_KERNELS[name]
    operand_names = SCALAR_OPERANDS[name]

    @jax.jit
    def program(node_mask, src, tgt, edge_mask, weights, *scalars):
        sdict = dict(zip(operand_names, scalars))
        return kernel(node_mask, src, tgt, edge_mask, weights, sdict)

    return program


# -- dense family: SpMV as matrix product over the full capacity tile ------
#
# When the graph is dense enough that the edge list approaches the full
# n x n tile, the edge-list scatter inside the loop is the wrong layout:
# the matrix-unit-native formulation materializes the (bucketed) dense
# adjacency ONCE per call and iterates with contiguous matrix products /
# masked reductions — no scatter, no data-dependent memory traffic in
# the loop.  The operator densifies on the host (``op.py``) and picks
# this family when ``e >= n_pad^2 / DENSE_EDGE_DIVISOR`` and the node
# capacity fits ``DENSE_MAX_NODES`` (the tile memory guard).

#: largest node capacity the dense family will tile (n_pad^2 doubles)
DENSE_MAX_NODES = 2048
#: density gate: dense when e >= n_pad*n_pad / this divisor
DENSE_EDGE_DIVISOR = 8

_BIG = jnp.iinfo(jnp.int64).max


def dense_eligible(n_pad: int, n_edges: int) -> bool:
    return (n_pad <= DENSE_MAX_NODES
            and n_edges * DENSE_EDGE_DIVISOR >= n_pad * n_pad)


def _degree_dense(node_mask, A, W, scalars):
    mode = scalars["direction_code"]  # 0=out 1=in 2=both
    out_part = A.sum(axis=1).astype(jnp.int64)
    in_part = A.sum(axis=0).astype(jnp.int64)
    deg = jnp.where(mode != 1, out_part, 0) \
        + jnp.where(mode != 0, in_part, 0)
    return deg, jnp.asarray(1, jnp.int64), jnp.asarray(True)


def _pagerank_dense(node_mask, A, W, scalars):
    n_pad = node_mask.shape[0]
    live = node_mask.astype(jnp.float64)
    n_live = jnp.maximum(scalars["n_live"].astype(jnp.float64), 1.0)
    d = scalars["damping"]
    tol = scalars["tolerance"]
    out_deg = A.sum(axis=1)
    r0 = live / n_live
    base = (1.0 - d) / n_live

    def body(state):
        r, _delta = state
        contrib = jnp.where(out_deg > 0, r / jnp.maximum(out_deg, 1.0),
                            0.0)
        nxt = contrib @ A  # the SpMV, as one dense product
        dangling = jnp.sum(r * live * (out_deg == 0))
        nxt = live * (base + d * (nxt + dangling / n_live))
        delta = jnp.abs(nxt - r).sum()
        return (nxt, delta), delta <= tol

    (r, _), it, done = _loop(lambda s: jnp.asarray(True), body,
                             (r0, jnp.asarray(jnp.inf)),
                             scalars["max_iterations"])
    return r, it, done  # quantized host-side, like the sparse twin


def _wcc_dense(node_mask, A, W, scalars):
    n_pad = node_mask.shape[0]
    B = (A > 0) | (A.T > 0)  # symmetrized reachability mask
    idx = jnp.arange(n_pad, dtype=jnp.int64)

    def body(state):
        label = state
        cand = jnp.where(B, label[:, None], _BIG)  # [s, t] -> label[s]
        nxt = jnp.minimum(label, cand.min(axis=0))
        nxt = nxt[nxt]  # pointer jumping (matches both twins)
        return nxt, jnp.all(nxt == label)

    label, it, done = _loop(lambda s: jnp.asarray(True), body, idx,
                            scalars["max_iterations"])
    return label, it, done


def _sssp_dense(node_mask, A, W, scalars):
    n_pad = node_mask.shape[0]
    source = scalars["source_index"]
    in_range = (source >= 0) & (source < scalars["n_live"])
    dist0 = jnp.full(n_pad, jnp.inf, jnp.float64)
    dist0 = jnp.where((jnp.arange(n_pad) == source) & in_range,
                      0.0, dist0)
    cap = scalars["max_iterations"]
    cap = jnp.where(cap >= 0, cap, jnp.asarray(n_pad, jnp.int64))

    def body(state):
        dist = state
        # W holds min weight per (s, t), +inf off-edge: the min over
        # parallel edges relaxes to the same fixpoint as the edge list
        nxt = jnp.minimum(dist, (dist[:, None] + W).min(axis=0))
        return nxt, jnp.all(nxt == dist)

    dist, it, done = _loop(lambda s: jnp.asarray(True), body, dist0, cap)
    return dist, it, done  # quantized host-side


_DENSE_KERNELS = {
    "algo.degree": _degree_dense,
    "algo.pagerank": _pagerank_dense,
    "algo.wcc": _wcc_dense,
    "algo.sssp": _sssp_dense,
}


def build_dense_program(name: str, n_pad: int):
    """Dense-family twin of :func:`build_program`: the program takes the
    densified adjacency ``A`` ([n_pad, n_pad] float64 edge multiplicity)
    and min-weight matrix ``W`` ([n_pad, n_pad] float64, +inf off-edge)
    instead of edge lists.  Same scalar operand tail; the caller caches
    and owns the ledger charge."""
    kernel = _DENSE_KERNELS[name]
    operand_names = SCALAR_OPERANDS[name]

    @jax.jit
    def program(node_mask, A, W, *scalars):
        sdict = dict(zip(operand_names, scalars))
        return kernel(node_mask, A, W, sdict)

    return program

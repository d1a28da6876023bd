"""Ring-scheduled k-hop frontier expansion over the ICI.

SURVEY.md §5.7: ``BoundedVarLengthExpand`` is the engine's "long sequence"
— a data-dependent frontier growing hop by hop.  For sharded graphs the
frontier (a dense per-node count vector, the aggregate-pushdown form of
expansion — see query_step.py) is **node-block partitioned**, adjacency
shards stay resident, and blocks rotate around the ring with ``ppermute``
— ring attention's communication schedule with (gather ⋈ segment-sum) in
place of (QKᵀ · softmax):

    step t: shard s holds frontier block (s - t) mod S
            local edges whose src falls in that block pick up cnt[src]
    after S steps every local edge has its source count; one segment-sum
    by dst + psum_scatter returns the next frontier, again block-sharded.

Per hop each shard sends N/S counts S-1 times — the same bytes as an
all_gather, but pipelined against the local gather so compute hides the
ICI latency, and no shard ever materializes the full frontier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from caps_tpu.obs.compile import charged as _compile_charged
from caps_tpu.parallel.collectives import note_collective, sum_scatter
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P


def _ring_hop(cnt_block, edge_src, edge_dst, edge_ok, *, axis: str,
              n_nodes: int, n_shards: int):
    """One hop: node-block-sharded counts -> next counts, block-sharded."""
    nb = n_nodes // n_shards
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    # trace-time accounting (obs/): the fori body traces ONCE but the
    # rotation runs n_shards times per hop — scale the byte estimate
    note_collective("ring.ppermute", cnt_block, scale=n_shards,
                    rotations=n_shards)

    def body(t, carry):
        blk, acc = carry
        block_id = (my - t) % n_shards
        lo = block_id * nb
        m = edge_ok & (edge_src >= lo) & (edge_src < lo + nb)
        local = jnp.clip(edge_src - lo, 0, nb - 1)
        acc = acc + jnp.where(m, blk[local], 0)
        blk = jax.lax.ppermute(blk, axis, perm)
        return blk, acc

    # the accumulator becomes device-varying on the first iteration, so the
    # loop carry must start with matching vma type
    acc0 = pcast(jnp.zeros(edge_src.shape, cnt_block.dtype), axis,
                 to="varying")
    _, per_edge = jax.lax.fori_loop(0, n_shards, body, (cnt_block, acc0))
    local_out = jax.ops.segment_sum(per_edge, edge_dst,
                                    num_segments=n_nodes)
    # psum + scatter back to node blocks in one collective
    note_collective("ring.psum_scatter", local_out)
    return sum_scatter(local_out, axis, n_shards)


def make_ring_khop(mesh: Mesh, n_nodes: int, n_hops: int,
                   axis: str = "shard", masked: bool = False):
    """Build the jitted k-hop ring expansion: seed counts and edges come
    in sharded (node blocks / edge shards), result is the total path count
    and the final block-sharded frontier.  With ``masked``, a node-block-
    sharded mask vector is multiplied into the frontier after every hop
    (the planner's per-hop node-existence/label mask)."""
    n_shards = int(mesh.devices.size)
    if n_nodes % n_shards:
        raise ValueError(f"n_nodes {n_nodes} must divide over {n_shards}")
    hop = functools.partial(_ring_hop, axis=axis, n_nodes=n_nodes,
                            n_shards=n_shards)

    def check_edges(edge_src, edge_dst, edge_ok):
        for name, arr in (("edge_src", edge_src), ("edge_dst", edge_dst),
                          ("edge_ok", edge_ok)):
            if arr.shape[0] % n_shards:
                raise ValueError(
                    f"{name} length {arr.shape[0]} must divide over "
                    f"{n_shards} shards; pad edges (edge_ok=False) to a "
                    f"multiple of the shard count")

    if masked:
        def body(seed_block, edge_src, edge_dst, edge_ok, mask_block):
            blk = seed_block
            for _ in range(n_hops):
                blk = hop(blk, edge_src, edge_dst, edge_ok) * mask_block
            total = jax.lax.psum(blk.sum(), axis)
            return total, blk
        in_specs = (P(axis),) * 5
    else:
        def body(seed_block, edge_src, edge_dst, edge_ok):
            blk = seed_block
            for _ in range(n_hops):
                blk = hop(blk, edge_src, edge_dst, edge_ok)
            total = jax.lax.psum(blk.sum(), axis)
            return total, blk
        in_specs = (P(axis),) * 4

    mapped = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(), P(axis)))
    jitted = jax.jit(mapped)

    def call(seed_block, edge_src, edge_dst, edge_ok, mask_block=None):
        check_edges(edge_src, edge_dst, edge_ok)
        if seed_block.shape[0] != n_nodes:
            raise ValueError(f"seed length {seed_block.shape[0]} != n_nodes "
                             f"{n_nodes}")
        if masked != (mask_block is not None):
            raise ValueError("mask_block must be passed iff masked=True")
        args = (seed_block, edge_src, edge_dst, edge_ok)
        return jitted(*args, mask_block) if masked else jitted(*args)

    return call


def _ring_hop_matrix(f_block, edge_src, edge_dst, edge_ok, *, axis: str,
                     n_nodes: int, n_shards: int, edge_w=None):
    """One hop of the MATRIX frontier: ``f_block`` is the (seeds,
    node-block) slice of a per-seed path-count matrix F[s, v].  Blocks
    rotate around the ring exactly as in ``_ring_hop``; the seed axis
    stays local, so this is the general VarExpand frontier exchange — the
    aggregate form above is the seeds==1 special case.  ``edge_w``
    weights each edge's contribution (the 3-hop isomorphism correction
    applies weighted sparse hops)."""
    nb = n_nodes // n_shards
    n_seeds = f_block.shape[0]
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    note_collective("ring.ppermute", f_block, scale=n_shards,
                    rotations=n_shards)

    def body(t, carry):
        blk, acc = carry  # blk: (S, nb); acc: (S, E_local)
        block_id = (my - t) % n_shards
        lo = block_id * nb
        m = edge_ok & (edge_src >= lo) & (edge_src < lo + nb)
        local = jnp.clip(edge_src - lo, 0, nb - 1)
        contrib = blk[:, local]
        if edge_w is not None:
            contrib = contrib * edge_w[None, :]
        acc = acc + jnp.where(m[None, :], contrib, 0)
        blk = jax.lax.ppermute(blk, axis, perm)
        return blk, acc

    acc0 = pcast(
        jnp.zeros((n_seeds, edge_src.shape[0]), f_block.dtype), axis,
        to="varying")
    _, per_edge = jax.lax.fori_loop(0, n_shards, body, (f_block, acc0))
    local_out = jax.ops.segment_sum(per_edge.T, edge_dst,
                                    num_segments=n_nodes)  # (N, S)
    note_collective("ring.psum_scatter", local_out)
    out = sum_scatter(local_out, axis, n_shards)  # (nb, S)
    return out.T


def make_ring_varexpand(mesh: Mesh, n_nodes: int, lengths: tuple,
                        axis: str = "shard", correction: str = "loops"):
    """Jitted ring-scheduled var-length expand: per-seed PATH-count matrix
    over the union of ``lengths`` (each in 0..2), with the relationship-
    isomorphism correction applied at length 2.  ``correction`` names the
    invalid-walk structure of the edge list:

      * ``"loops"`` (uniform OUT/IN direction): the only length-2 walk
        reusing its relationship is a self-loop taken twice — subtract
        the per-node self-loop count on the diagonal;
      * ``"degree"`` (undirected — the edge list arrives symmetrized,
        self-loops once): every incident edge yields exactly one
        there-and-back walk s -e- m -e- s — subtract the per-node count
        of symmetrized edges leaving the node (which counts non-loop
        incident edges once per endpoint and self-loops once).

    Inputs arrive sharded: the seed-indicator matrix F0 (seeds, n_nodes)
    node-block sharded on its node axis, edges edge-sharded, the
    target-node mask node-block sharded.  Output is the (seeds, n_nodes)
    multiplicity matrix M[s, v] = #paths seed_s ->..-> v with len in
    ``lengths`` and v in the mask."""
    n_shards = int(mesh.devices.size)
    if n_nodes % n_shards:
        raise ValueError(f"n_nodes {n_nodes} must divide over {n_shards}")
    if correction not in ("loops", "degree"):
        raise ValueError(correction)
    max_len = max(lengths) if lengths else 0
    if max_len > 2:
        raise ValueError("ring var-expand supports lengths <= 2")
    hop = functools.partial(_ring_hop_matrix, axis=axis, n_nodes=n_nodes,
                            n_shards=n_shards)

    def body(f0_block, edge_src, edge_dst, edge_ok, tmask_block):
        out = jnp.zeros_like(f0_block)
        if 0 in lengths:
            out = out + f0_block * tmask_block[None, :]
        f = f0_block
        for length in range(1, max_len + 1):
            f = hop(f, edge_src, edge_dst, edge_ok)
            if length == 2:
                # relationship-isomorphism correction on the diagonal
                # (see docstring)
                loc = _r2_vector(edge_src, edge_dst, edge_ok, n_nodes,
                                 f.dtype, correction)
                corr = sum_scatter(loc, axis, n_shards)  # (nb,)
                f = f - f0_block * corr[None, :]
            if length in lengths:
                out = out + f * tmask_block[None, :]
        return out

    mapped = shard_map(body, mesh=mesh,
                       in_specs=(P(None, axis), P(axis), P(axis), P(axis),
                                 P(axis)),
                       out_specs=P(None, axis))
    return jax.jit(mapped)


def _r2_vector(edge_src, edge_dst, edge_ok, n_nodes, dtype,
               correction: str):
    """Per-node reuse-pair count: self-loops (uniform direction) or the
    symmetrized degree (undirected) — the length-2 isomorphism
    correction vector, also the A12/A23 factor of the 3-hop one."""
    if correction == "loops":
        bad = edge_ok & (edge_src == edge_dst)
    else:
        bad = edge_ok
    return jax.ops.segment_sum(bad.astype(dtype), edge_src,
                               num_segments=n_nodes)


def make_ring_varexpand3(mesh: Mesh, n_nodes: int, lengths: tuple,
                         axis: str = "shard", correction: str = "loops"):
    """Ring-scheduled var-expand for lengths up to 3.  Walk counts are
    SpMV hops; relationship isomorphism is restored per length:

        P2 = W2 − F0·r2                                (reuse at start)
        P3 = W3 − A12 − A23 − A13 + 2T   (inclusion–exclusion over the
                                          pairs (1,2), (2,3), (1,3);
                                          every pairwise intersection is
                                          the all-equal triple T)
        A12 = H(F0 ⊙ r2)        — same-rel pair first, any third hop
        A23 = H(F0) ⊙ r2        — any first hop, same-rel pair after
        A13 = H_sp13(F0)        — first rel reused as third; the free
                                  middle hop's count is folded into a
                                  host-built weighted sparse hop
        T   = H_spT(F0)         — all three the same rel

    Extra inputs beyond make_ring_varexpand's: the two weighted sparse
    edge lists (sp13/spT as (src, dst, w) triples, edge-sharded)."""
    n_shards = int(mesh.devices.size)
    if n_nodes % n_shards:
        raise ValueError(f"n_nodes {n_nodes} must divide over {n_shards}")
    if correction not in ("loops", "degree"):
        raise ValueError(correction)
    max_len = max(lengths) if lengths else 0
    if max_len != 3:
        raise ValueError("use make_ring_varexpand for lengths <= 2")
    hop = functools.partial(_ring_hop_matrix, axis=axis, n_nodes=n_nodes,
                            n_shards=n_shards)

    def body(f0, e_src, e_dst, e_ok, tmask, s13_src, s13_dst, s13_w,
             st_src, st_dst, st_w):
        loc = _r2_vector(e_src, e_dst, e_ok, n_nodes, f0.dtype, correction)
        r2 = sum_scatter(loc, axis, n_shards)  # (nb,) node-block sharded
        out = jnp.zeros_like(f0)
        if 0 in lengths:
            out = out + f0 * tmask[None, :]
        f1 = hop(f0, e_src, e_dst, e_ok)
        if 1 in lengths:
            out = out + f1 * tmask[None, :]
        f2 = hop(f1, e_src, e_dst, e_ok)
        if 2 in lengths:
            out = out + (f2 - f0 * r2[None, :]) * tmask[None, :]
        f3 = hop(f2, e_src, e_dst, e_ok)
        a12 = hop(f0 * r2[None, :], e_src, e_dst, e_ok)
        a23 = f1 * r2[None, :]
        a13 = hop(f0, s13_src, s13_dst, s13_w > 0, edge_w=s13_w)
        t3 = hop(f0, st_src, st_dst, st_w > 0, edge_w=st_w)
        p3 = f3 - a12 - a23 - a13 + 2 * t3
        return out + p3 * tmask[None, :]

    mapped = shard_map(body, mesh=mesh,
                       in_specs=(P(None, axis),) + (P(axis),) * 10,
                       out_specs=P(None, axis))
    return jax.jit(mapped)


def ring_varexpand3_reference(f0, edge_src, edge_dst, edge_ok, tmask,
                              lengths: tuple, s13, st,
                              correction: str = "loops"):
    """Single-device jnp twin of make_ring_varexpand3 (``s13``/``st`` are
    (src, dst, w) array triples)."""
    if (max(lengths) if lengths else 0) != 3:
        raise ValueError("use ring_varexpand_reference for lengths <= 2")
    n_nodes = f0.shape[1]

    def hop(f, src, dst, ok, w=None):
        per_edge = jnp.where(ok[None, :], f[:, src], 0)
        if w is not None:
            per_edge = per_edge * w[None, :]
        return jax.ops.segment_sum(per_edge.T, dst,
                                   num_segments=n_nodes).T

    r2 = _r2_vector(edge_src, edge_dst, edge_ok, n_nodes, f0.dtype,
                    correction)
    out = jnp.zeros_like(f0)
    if 0 in lengths:
        out = out + f0 * tmask[None, :]
    f1 = hop(f0, edge_src, edge_dst, edge_ok)
    if 1 in lengths:
        out = out + f1 * tmask[None, :]
    f2 = hop(f1, edge_src, edge_dst, edge_ok)
    if 2 in lengths:
        out = out + (f2 - f0 * r2[None, :]) * tmask[None, :]
    f3 = hop(f2, edge_src, edge_dst, edge_ok)
    a12 = hop(f0 * r2[None, :], edge_src, edge_dst, edge_ok)
    a23 = f1 * r2[None, :]
    a13 = hop(f0, s13[0], s13[1], s13[2] > 0, w=s13[2])
    t3 = hop(f0, st[0], st[1], st[2] > 0, w=st[2])
    return out + (f3 - a12 - a23 - a13 + 2 * t3) * tmask[None, :]


@functools.lru_cache(maxsize=128)
def ring_varexpand3_cached(mesh: Mesh, n_nodes: int, lengths: tuple,
                           axis: str = "shard",
                           correction: str = "loops"):
    return make_ring_varexpand3(mesh, n_nodes, lengths, axis, correction)


@functools.lru_cache(maxsize=32)
def ring_varexpand3_single(lengths: tuple, correction: str = "loops"):
    @jax.jit
    def fn(f0, edge_src, edge_dst, edge_ok, tmask, s13_src, s13_dst,
           s13_w, st_src, st_dst, st_w):
        return ring_varexpand3_reference(
            f0, edge_src, edge_dst, edge_ok, tmask, lengths,
            (s13_src, s13_dst, s13_w), (st_src, st_dst, st_w), correction)

    return fn


def build_iso3_sparse(frm, to, rid, n_nodes: int):
    """Host-side weighted sparse edge lists for the 3-hop correction.

    ``frm``/``to``/``rid`` describe the ENTRY list the hops traverse
    (symmetrized for undirected patterns; each entry carries its
    underlying relationship id).  Returns (sp13, spT) as (src, dst, w)
    numpy triples:

      * sp13: for each ordered orientation pair (o1, o3) of one
        relationship, an edge from(o1) -> to(o3) weighted by the number
        of entries that can serve as the free middle hop
        to(o1) -> from(o3);
      * spT: for each orientation chain o1 -> o2 -> o3 of one
        relationship, an edge from(o1) -> to(o3) with weight 1.
    """
    import numpy as np
    frm = np.asarray(frm, dtype=np.int64)
    to = np.asarray(to, dtype=np.int64)
    rid = np.asarray(rid, dtype=np.int64)

    # entry-count lookup between ordered node pairs
    keys = np.sort(frm * n_nodes + to)

    def cnt(x, y):
        q = x * n_nodes + y
        return (np.searchsorted(keys, q, side="right")
                - np.searchsorted(keys, q, side="left"))

    # group entries by relationship id: 1 orientation (directed or a
    # loop) or 2 (undirected non-loop)
    order = np.argsort(rid, kind="stable")
    r_sorted = rid[order]
    first = np.ones(len(rid), dtype=bool)
    first[1:] = r_sorted[1:] != r_sorted[:-1]
    starts = np.nonzero(first)[0]
    counts = np.diff(np.append(starts, len(rid)))

    s13_s, s13_d, s13_w = [], [], []
    st_s, st_d, st_w = [], [], []
    if counts.size and int(counts.max()) > 2:
        # a rel id appearing 3+ times means a malformed entry list
        # (e.g. double symmetrization); an omitted correction would be a
        # silent wrong answer, so fail loudly
        raise ValueError("entry list has a relationship id with more "
                         "than two orientations")
    one = starts[counts == 1]
    u1, v1 = frm[order[one]], to[order[one]]
    # single-orientation rels: (o1, o3) = (e, e); chain o1->o2->o3 needs
    # o2 = e too, which chains only for loops
    s13_s.append(u1)
    s13_d.append(v1)
    s13_w.append(cnt(v1, u1))
    lo = u1 == v1
    st_s.append(u1[lo])
    st_d.append(v1[lo])
    st_w.append(np.ones(int(lo.sum()), dtype=np.int64))
    two = starts[counts == 2]
    if len(two):
        ua, va = frm[order[two]], to[order[two]]        # orientation uv
        # orientation pairs (see make_ring_varexpand3 docstring)
        s13_s.append(np.concatenate([ua, ua, va, va]))
        s13_d.append(np.concatenate([va, ua, va, ua]))
        s13_w.append(np.concatenate([cnt(va, ua), cnt(va, va),
                                     cnt(ua, ua), cnt(ua, va)]))
        # chains: u -e- v -e- u -e- v and the reverse
        st_s.append(np.concatenate([ua, va]))
        st_d.append(np.concatenate([va, ua]))
        st_w.append(np.ones(2 * len(two), dtype=np.int64))

    def pack(ss, dd, ww):
        s = np.concatenate(ss) if ss else np.zeros(0, np.int64)
        d = np.concatenate(dd) if dd else np.zeros(0, np.int64)
        w = np.concatenate(ww) if ww else np.zeros(0, np.int64)
        keep = w > 0
        return (s[keep].astype(np.int32), d[keep].astype(np.int32),
                w[keep])

    return pack(s13_s, s13_d, s13_w), pack(st_s, st_d, st_w)


def ring_varexpand_reference(f0, edge_src, edge_dst, edge_ok, tmask,
                             lengths: tuple, correction: str = "loops"):
    """Single-device jnp twin for differential tests."""
    n_nodes = f0.shape[1]
    out = jnp.zeros_like(f0)
    if 0 in lengths:
        out = out + f0 * tmask[None, :]
    f = f0
    for length in range(1, (max(lengths) if lengths else 0) + 1):
        per_edge = jnp.where(edge_ok[None, :], f[:, edge_src], 0)
        f = jax.ops.segment_sum(per_edge.T, edge_dst,
                                num_segments=n_nodes).T
        if length == 2:
            corr = _r2_vector(edge_src, edge_dst, edge_ok, n_nodes,
                              f.dtype, correction)
            f = f - f0 * corr[None, :]
        if length in lengths:
            out = out + f * tmask[None, :]
    return out


@functools.lru_cache(maxsize=128)
def ring_varexpand_cached(mesh: Mesh, n_nodes: int, lengths: tuple,
                          axis: str = "shard", correction: str = "loops"):
    """Memoized make_ring_varexpand (compiled program reuse per shape).
    A miss is a compile boundary: it charges the compile ledger
    (obs/compile.py) under the executing query's family."""
    with _compile_charged("dist_join",
                          shape=f"varexpand:{n_nodes}:{lengths}:"
                                f"{correction}"):
        return make_ring_varexpand(mesh, n_nodes, lengths, axis, correction)


@functools.lru_cache(maxsize=32)
def ring_varexpand_single(lengths: tuple, correction: str = "loops"):
    """Single-device matrix var-expand: the same SpMV-hop computation as
    the ring body, without collectives, as one jitted program (the
    VarExpand matrix strategy off-mesh).  One wrapper per (lengths,
    correction) — jax's own trace cache handles the shapes.  A miss
    charges the compile ledger (the jit wrapper build; the per-shape
    trace+compile lands on the first dispatch)."""
    with _compile_charged("dist_join",
                          shape=f"varexpand1:{lengths}:{correction}"):
        @jax.jit
        def fn(f0, edge_src, edge_dst, edge_ok, tmask):
            return ring_varexpand_reference(f0, edge_src, edge_dst, edge_ok,
                                            tmask, lengths, correction)

        return fn


@functools.lru_cache(maxsize=128)
def ring_khop_cached(mesh: Mesh, n_nodes: int, n_hops: int,
                     axis: str = "shard", masked: bool = False):
    """Memoized make_ring_khop: repeat queries reuse the traced + compiled
    shard_map program instead of re-jitting per call.  A miss charges
    the compile ledger (obs/compile.py)."""
    with _compile_charged("dist_join",
                          shape=f"khop:{n_nodes}:{n_hops}:{masked}"):
        return make_ring_khop(mesh, n_nodes, n_hops, axis, masked)


def ring_khop_reference(seed_counts, edge_src, edge_dst, edge_ok,
                        n_hops: int, n_nodes: int):
    """Single-device jnp twin for differential tests."""
    cnt = seed_counts
    for _ in range(n_hops):
        per_edge = jnp.where(edge_ok, cnt[edge_src], 0)
        cnt = jax.ops.segment_sum(per_edge, edge_dst,
                                  num_segments=n_nodes)
    return cnt.sum(), cnt

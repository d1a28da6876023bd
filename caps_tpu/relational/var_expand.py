"""Bounded variable-length expand.

Mirrors the reference's ``planBoundedVarLengthExpand`` — iterative
join-and-union up to the upper bound with relationship-uniqueness (edge
isomorphism) filters (ref: okapi-relational planner — reconstructed,
mount empty; SURVEY.md §3.2).

The unroll is static: hop ``k`` joins the frontier against a per-hop copy
of the relationship scan; every new hop id is filtered against all previous
hop ids; lengths ``lower..upper`` are unioned, with traversed relationship
ids packed into one list-valued column.  Static unrolling is deliberate —
on the TPU backend every hop is a fixed-shape join the compiler can fuse,
the device-side analog of ragged frontier schedules (SURVEY.md §5.7).

When the relationship variable is dead downstream (the planner proves it
— no projection, filter, or return touches it), the op instead computes a
per-seed path-count MATRIX and explodes (source, target, multiplicity)
back into rows — the general-frontier form of SURVEY.md §5.7's "frontier
= long sequence" story.  On a device mesh the matrix rides the ppermute
RING schedule against resident adjacency shards (parallel/ring.py,
``make_ring_varexpand``, strategy "ring-matrix"); single-chip the same
SpMV hops run as one jitted program (strategy "matrix").  Per-path
relationship lists cannot ride this form; those queries stay on joins.
"""
from __future__ import annotations

from typing import List, Optional as Opt, Tuple

import numpy as np

from caps_tpu.ir import exprs as E
from caps_tpu.ir.pattern import Direction
from caps_tpu.okapi.types import (
    CTInteger, CTList, CTNode, CTRelationship, CypherType,
)
from caps_tpu.relational.header import RecordHeader
from caps_tpu.relational.ops import RelationalOperator
from caps_tpu.relational.table import Table

# Safety cap for unbounded `[*]` patterns (the reference requires Spark to
# materialize each iteration too; unbounded expansion needs *some* limit).
DEFAULT_UNBOUNDED_UPPER = 10


def synth_header(table: Table) -> RecordHeader:
    """A header mapping every physical column to ``Var(col)`` — used for
    internal columnar filtering where no user-level header applies."""
    return RecordHeader([(E.Var(c), c, table.column_type(c))
                         for c in table.columns])


class VarExpandOp(RelationalOperator):
    def __init__(self, context, parent: RelationalOperator, graph,
                 source: str, rel: str, rel_types: Tuple[str, ...],
                 target: str, target_labels, direction: Direction,
                 lower: int, upper: Opt[int], into: bool,
                 rel_needed: bool = True, emit_len: Opt[str] = None):
        super().__init__(context, [parent])
        self.graph = graph
        self.source = source
        self.rel = rel
        self.rel_types = rel_types
        self.target = target
        self.target_labels = frozenset(target_labels)
        self.direction = direction
        self.lower = lower
        self.upper = upper if upper is not None else max(
            lower, DEFAULT_UNBOUNDED_UPPER)
        self.into = into
        # False = the planner proved no downstream operator reads the rel
        # variable, so per-path relationship lists need not materialize.
        self.rel_needed = rel_needed
        # Set when the planner rewrote every size(rel)/length(rel) read
        # to this path-length column (planner._collect_used_names).
        self.emit_len = emit_len
        self.strategy = "join"

    # ------------------------------------------------------------------

    def _rel_hop_table(self, k: int) -> Tuple[Table, str, str, str]:
        """The relationship table for hop ``k`` with per-hop column names
        (id, near, far) following the traversal direction."""
        tmp_var = f"__vle{k}"
        header, t = self.graph.scan_rel(tmp_var, self.rel_types)
        idc = header.column(E.Var(tmp_var))
        src = header.column(E.StartNode(E.Var(tmp_var)))
        tgt = header.column(E.EndNode(E.Var(tmp_var)))
        t = t.select([idc, src, tgt])
        hid, hnear, hfar = f"__hop{k}_id", f"__hop{k}_near", f"__hop{k}_far"
        if self.direction == Direction.OUTGOING:
            t = t.rename({idc: hid, src: hnear, tgt: hfar})
        elif self.direction == Direction.INCOMING:
            t = t.rename({idc: hid, tgt: hnear, src: hfar})
        else:  # BOTH: traverse each edge in either orientation
            fwd = t.rename({idc: hid, src: hnear, tgt: hfar})
            bwd = t.rename({idc: hid, tgt: hnear, src: hfar})
            sh = synth_header(bwd)
            bwd = bwd.filter(
                E.Not(E.Equals(E.Var(hnear), E.Var(hfar))), sh, {})
            fwd = fwd.select([hid, hnear, hfar])
            bwd = bwd.select([hid, hnear, hfar])
            t = fwd.union_all(bwd)
        return t.select([hid, hnear, hfar]), hid, hnear, hfar

    def _compute(self):
        out = self._try_ring()
        if out is None:
            self.strategy = "join"
            out = self._join_compute()
        self._metric_extra = {"strategy": self.strategy}
        return out

    # -- matrix path (ring on mesh, SpMV single-chip; see module docstring)

    # Refuse seed-matrix shapes beyond this many entries (int64 frontier
    # blocks must fit comfortably in HBM across the mesh); larger inputs
    # stay on the join path.  Seed-axis blocking is the scale-out path.
    _RING_MAX_MATRIX = 1 << 24

    @staticmethod
    def _host_arrays(table, col: str):
        """(values, ok) host copies of an integer column of a pure-device
        table (DeviceTable.host_column), or None when there is no device
        path."""
        from caps_tpu.backends.tpu.table import DeviceTable
        if not isinstance(table, DeviceTable):
            return None
        return table.host_column(col)

    def _try_ring(self):
        """Matrix-form var-expand (multiplicity form): returns the
        (header, table) result, or None when the shape is ineligible.
        All three directions qualify — undirected patterns symmetrize
        the edge list and use the degree-form isomorphism correction.
        On a mesh the per-seed count matrix rides the ppermute ring
        (parallel/ring.py make_ring_varexpand); single-chip it runs the
        same SpMV hops as one jitted program (the twin) — either way the
        join cascade and its per-hop materializations disappear."""
        # ``into`` (both endpoints bound) stays on joins: measured at
        # LDBC scale 11, the single-pair shape pays more in per-length
        # explode/union dispatch than the tiny bound-pair joins cost
        # (6.2 s vs 2.0 s p50 for IC13 on the CPU fallback).
        if self.rel_needed or self.into or self.upper > 3:
            return None
        backend = getattr(self.context.factory, "backend", None)
        if backend is None:
            return None
        import jax.numpy as jnp
        from caps_tpu.backends.tpu import kernels as K
        from caps_tpu.backends.tpu.column import Column
        from caps_tpu.backends.tpu.table import DeviceTable
        from caps_tpu.okapi.types import CTInteger
        from caps_tpu.parallel.ring import (
            build_iso3_sparse, ring_varexpand3_cached,
            ring_varexpand3_single, ring_varexpand_cached,
            ring_varexpand_single,
        )

        parent_header, parent_table = self.children[0].result
        src_id_col = parent_header.column(E.Var(self.source))
        parent = self._host_arrays(parent_table, src_id_col)
        if parent is None:
            return None
        rel_header, rel_t = self.graph.scan_rel("__ring_r", self.rel_types)
        rv = E.Var("__ring_r")
        rsrc = self._host_arrays(rel_t, rel_header.column(E.StartNode(rv)))
        rtgt = self._host_arrays(rel_t, rel_header.column(E.EndNode(rv)))
        tgt_header, tgt_table = self.graph.scan_node(
            self.target, self.target_labels)
        tgt_id_col = tgt_header.column(E.Var(self.target))
        tids = self._host_arrays(tgt_table, tgt_id_col)
        if rsrc is None or rtgt is None or tids is None:
            return None

        hsrc, hok = parent
        esrc, eok1 = rsrc
        etgt, eok2 = rtgt
        eok = eok1 & eok2
        nids, nok = tids
        mx = -1
        for vals, ok in ((hsrc, hok), (esrc, eok), (etgt, eok),
                         (nids, nok)):
            if vals.shape[0] and ok.any():
                m = int(vals[ok].max())
                if int(vals[ok].min()) < 0:
                    return None
                mx = max(mx, m)
        n_shards = backend.n_shards
        n_pad = max(((mx + 1 + n_shards - 1) // n_shards) * n_shards,
                    n_shards)
        seeds = np.unique(hsrc[hok])
        n_seeds = int(seeds.shape[0])
        if n_pad > self._RING_MAX_MATRIX:
            return None  # a single frontier row exceeds the budget
        # (large SEED sets are fine — the execution below chunks them)
        lengths = tuple(range(self.lower, self.upper + 1))
        self.strategy = ("ring-matrix"
                         if backend.mesh is not None
                         and backend.mesh.devices.ndim == 1
                         else "matrix")
        rel_list_type = CTList(CTRelationship(self.rel_types))

        if n_seeds == 0:
            cols0 = {
                "__ring_src": Column("int", jnp.zeros(1, jnp.int64),
                                     jnp.zeros(1, bool), CTInteger),
                "__ring_tgt": Column("int", jnp.zeros(1, jnp.int64),
                                     jnp.zeros(1, bool), CTInteger),
            }
            if self.emit_len:
                cols0[self.emit_len] = Column(
                    "int", jnp.zeros(1, jnp.int64), jnp.zeros(1, bool),
                    CTInteger)
            pairs = DeviceTable(backend, cols0, n=0)
            return self._ring_assemble(parent_header, parent_table,
                                       src_id_col, tgt_header, tgt_table,
                                       tgt_id_col, pairs, rel_list_type)

        # target mask + padded edges (seed-indicator frontiers are built
        # per seed CHUNK below, so host memory stays bounded too)
        tmask = np.zeros(n_pad, dtype=np.int64)
        tmask[nids[nok]] = 1
        if self.direction == Direction.BOTH:
            # symmetrize: each non-loop edge in both orientations,
            # self-loops once (VarExpandOp's BOTH hop table does the
            # same); isomorphism correction switches to degree form
            nonloop = eok & (esrc != etgt)
            a = np.concatenate([esrc, etgt[nonloop]])
            b = np.concatenate([etgt, esrc[nonloop]])
            ok_cat = np.concatenate([eok, np.ones(nonloop.sum(), bool)])
            correction = "degree"
        else:
            a, b = (esrc, etgt) if self.direction == Direction.OUTGOING \
                else (etgt, esrc)
            ok_cat = eok
            correction = "loops"
        def shard_pad(length: int) -> int:
            return max(((length + n_shards - 1) // n_shards) * n_shards,
                       n_shards)

        # compact to live entries: host mirrors are capacity-padded (the
        # bucket, not the live row count), and dead rows would inflate
        # every hop's gather width
        live = np.asarray(ok_cat)
        a, b = np.asarray(a)[live], np.asarray(b)[live]
        ok_cat = np.ones(a.shape[0], dtype=bool)
        e_pad = shard_pad(a.shape[0])
        # peak working set is the per-hop (seeds, edges) gather — bound
        # it like the (seeds, nodes) frontier.  Only the 1-D ring path
        # splits edges across devices; single-chip and 2-D meshes run
        # the whole gather on one device's program.  The 3-hop sparse
        # correction hops gather up to 4 entries per rel (vs <= 2 in the
        # base list), so bound the widest list the program will touch.
        on_ring = (backend.mesh is not None
                   and backend.mesh.devices.ndim == 1)
        widest = e_pad * 2 if self.upper == 3 else e_pad
        edges_per_device = widest // n_shards if on_ring else widest
        # SEED BLOCKING: the per-hop working set is seeds x max(nodes,
        # edges-per-device); larger seed sets run in fixed-size chunks
        # (one compile, zero-padded last block) whose pair tables union.
        per_seed = max(n_pad, edges_per_device)
        if per_seed > self._RING_MAX_MATRIX:
            return None  # even one seed's per-hop gather exceeds budget
        # pow2-pad the chunk dimension: tying it to the exact seed count
        # would recompile the hop programs (and rebuild different
        # shapes) for every distinct parameter value — padded chunks
        # keep shapes stable across a parameter sweep, and the last
        # block is zero-padded anyway.  Plain pow2, NOT backend.bucket:
        # its 256-row minimum would inflate a single-seed frontier (the
        # common point-lookup expand) by 256x in host upload and hop
        # gather work.
        seeds_p2 = 1 << max(0, n_seeds - 1).bit_length()
        chunk = max(1, min(seeds_p2, self._RING_MAX_MATRIX // per_seed))
        n_chunks = (n_seeds + chunk - 1) // chunk
        if n_chunks > 64:  # degenerate shapes stay on the join path
            return None
        frm = np.zeros(e_pad, dtype=np.int32)
        to = np.zeros(e_pad, dtype=np.int32)
        okp = np.zeros(e_pad, dtype=bool)
        frm[:a.shape[0]] = np.where(ok_cat, a, 0)
        to[:b.shape[0]] = np.where(ok_cat, b, 0)
        okp[:ok_cat.shape[0]] = ok_cat

        if self.upper == 3:
            # 3-hop isomorphism correction needs the entries' underlying
            # relationship ids (host-side sparse-hop build)
            rids = self._host_arrays(rel_t, rel_header.column(rv))
            if rids is None or not bool(np.all(rids[1] >= eok)):
                # the id column must be valid wherever the endpoints are
                # (a garbage id would corrupt the orientation grouping)
                return None
            rid_all = rids[0]
            if self.direction == Direction.BOTH:
                rid_cat = np.concatenate([rid_all, rid_all[nonloop]])
            else:
                rid_cat = rid_all
            # a/b are already live-compacted; align rids with the same mask
            sp13, spt = build_iso3_sparse(a, b, rid_cat[live], n_pad)

            def pad_sparse(tr):
                s, d, w = tr
                p = shard_pad(s.shape[0])
                ps = np.zeros(p, dtype=np.int32)
                pd = np.zeros(p, dtype=np.int32)
                pw = np.zeros(p, dtype=np.int64)
                ps[:s.shape[0]] = s
                pd[:d.shape[0]] = d
                pw[:w.shape[0]] = w
                return ps, pd, pw

            s13s, s13d, s13w = pad_sparse(sp13)
            sts, std_, stw = pad_sparse(spt)
            extra3 = tuple(jnp.asarray(x)
                           for x in (s13s, s13d, s13w, sts, std_, stw))
        else:
            extra3 = ()

        # constants uploaded ONCE; only the frontier block varies per call
        frm_d, to_d, okp_d, tmask_d = (jnp.asarray(frm), jnp.asarray(to),
                                       jnp.asarray(okp), jnp.asarray(tmask))

        def run_chunk(f0_np, lens):
            """One compiled program per distinct ``lens`` tuple."""
            base = (jnp.asarray(f0_np), frm_d, to_d, okp_d, tmask_d)
            if max(lens) == 3:
                fn = (ring_varexpand3_cached(backend.mesh, n_pad, lens,
                                             backend.axis, correction)
                      if on_ring
                      else ring_varexpand3_single(lens, correction))
                return fn(*base, *extra3)
            fn = (ring_varexpand_cached(backend.mesh, n_pad, lens,
                                        backend.axis, correction)
                  if on_ring
                  # single chip, or a 2-D (DCN x ICI) mesh where the
                  # GSPMD partitioner schedules the collectives
                  else ring_varexpand_single(lens, correction))
            return fn(*base)

        # emit_len: one multiplicity matrix PER length with its length
        # tagged on the rows; otherwise one matrix for the union
        length_runs = ([(L, (L,)) for L in lengths] if self.emit_len
                       else [(None, lengths)])
        parts: List[Table] = []
        for ci in range(n_chunks):
            block = seeds[ci * chunk:(ci + 1) * chunk]
            f0 = np.zeros((chunk, n_pad), dtype=np.int64)
            f0[np.arange(block.shape[0]), block] = 1
            for tag, lens in length_runs:
                m = run_chunk(f0, lens)
                counts = m.reshape(-1)
                total, live = backend.consume_rows(counts.sum())
                out_cap = backend.bucket(total)
                row, _within, valid, _tot = K.explode_expand(
                    counts, jnp.ones_like(counts, dtype=bool), out_cap)
                s_idx = row // n_pad
                v = row % n_pad
                block_pad = np.zeros(chunk, dtype=np.int64)
                block_pad[:block.shape[0]] = block
                src_ids = jnp.asarray(block_pad)[s_idx]
                cols = {
                    "__ring_src": Column(
                        "int", backend.place_rows(src_ids),
                        backend.place_rows(valid), CTInteger),
                    "__ring_tgt": Column(
                        "int", backend.place_rows(v.astype(jnp.int64)),
                        backend.place_rows(valid), CTInteger),
                }
                if tag is not None:
                    cols[self.emit_len] = Column(
                        "int",
                        backend.place_rows(jnp.full(out_cap, tag,
                                                    jnp.int64)),
                        backend.place_rows(valid), CTInteger)
                parts.append(DeviceTable(backend, cols, n=total, live=live))
        # balanced pairwise concat: incremental union over many chunk x
        # length parts would re-copy the accumulated rows quadratically
        while len(parts) > 1:
            parts = [parts[i].union_all(parts[i + 1])
                     if i + 1 < len(parts) else parts[i]
                     for i in range(0, len(parts), 2)]
        pairs = parts[0]
        return self._ring_assemble(parent_header, parent_table, src_id_col,
                                   tgt_header, tgt_table, tgt_id_col, pairs,
                                   rel_list_type)

    def _ring_assemble(self, parent_header, parent_table, src_id_col,
                       tgt_header, tgt_table, tgt_id_col, pairs,
                       rel_list_type):
        """(source, target) multiplicity rows -> the join path's exact
        output schema: parent columns + null rel-list (+ path-length)
        + target columns."""
        joined = parent_table.join(pairs, "inner",
                                   [(src_id_col, "__ring_src")])
        tt = tgt_table.rename({c: f"__t_{c}" for c in tgt_table.columns})
        joined = joined.join(tt, "inner",
                             [("__ring_tgt", f"__t_{tgt_id_col}")])
        joined = joined.rename({f"__t_{c}": c for c in tgt_table.columns})
        joined = joined.with_literal_column(self.rel, None, rel_list_type)
        out_header = parent_header.with_expr(E.Var(self.rel), rel_list_type,
                                             column=self.rel)
        if self.emit_len:
            out_header = out_header.with_expr(E.Var(self.emit_len),
                                              CTInteger,
                                              column=self.emit_len)
        out_header = out_header.concat(tgt_header)
        return out_header, joined.select(list(out_header.columns))

    # -- join path (the general form) --------------------------------------

    def _join_compute(self):
        parent_header, parent_table = self.children[0].result
        params = self.context.parameters
        rel_list_type: CypherType = CTList(CTRelationship(self.rel_types))

        src_id_col = parent_header.column(E.Var(self.source))
        if self.into:
            tgt_header = None
            tgt_id_col = parent_header.column(E.Var(self.target))
            final_cols = list(parent_table.columns) + [self.rel]
        else:
            tgt_header, tgt_table = self.graph.scan_node(
                self.target, self.target_labels)
            tgt_id_col = tgt_header.column(E.Var(self.target))
            final_cols = list(parent_table.columns) + [self.rel] \
                + list(tgt_header.columns)

        if self.emit_len:
            final_cols = final_cols + [self.emit_len]

        cur = "__vle_cur"
        frontier = parent_table.copy_column(src_id_col, cur)
        hop_id_cols: List[str] = []
        branches: List[Table] = []

        def finish_branch(t: Table, hops: List[str]) -> Table:
            """Pack hop ids into the rel list column, join/filter target,
            project to the uniform final column set."""
            t = t.pack_list(hops, self.rel, rel_list_type)
            if self.emit_len:
                t = t.with_literal_column(self.emit_len, len(hops),
                                          CTInteger)
            if self.into:
                sh = synth_header(t)
                t = t.filter(E.Equals(E.Var(cur), E.Var(tgt_id_col)), sh, params)
                return t.select(final_cols)
            tt = tgt_table.rename({c: f"__t_{c}" for c in tgt_table.columns})
            joined = t.join(tt, "inner", [(cur, f"__t_{tgt_id_col}")])
            joined = joined.rename(
                {f"__t_{c}": c for c in tgt_table.columns})
            return joined.select(final_cols)

        if self.lower == 0:
            branches.append(finish_branch(frontier, []))

        for k in range(1, self.upper + 1):
            hop_t, hid, hnear, hfar = self._rel_hop_table(k)
            joined = frontier.join(hop_t, "inner", [(cur, hnear)])
            # edge-isomorphism: this hop's rel must differ from all previous
            sh = synth_header(joined)
            for prev in hop_id_cols:
                joined = joined.filter(
                    E.Not(E.Equals(E.Var(hid), E.Var(prev))), sh, params)
            # advance the frontier cursor to the far end of this hop
            joined = joined.select(
                [c for c in joined.columns if c not in (cur, hnear)])
            joined = joined.copy_column(hfar, cur)
            joined = joined.select(
                [c for c in joined.columns if c != hfar])
            frontier = joined
            hop_id_cols = hop_id_cols + [hid]
            if k >= self.lower:
                branches.append(finish_branch(frontier, hop_id_cols))

        if not branches:
            raise ValueError("variable-length expand produced no branches")
        out = branches[0]
        for b in branches[1:]:
            out = out.union_all(b)

        out_header = parent_header.with_expr(E.Var(self.rel), rel_list_type,
                                             column=self.rel)
        if self.emit_len:
            out_header = out_header.with_expr(E.Var(self.emit_len),
                                              CTInteger,
                                              column=self.emit_len)
        if not self.into and tgt_header is not None:
            out_header = out_header.concat(tgt_header)
        return out_header, out.select(list(out_header.columns))

    def _pretty_args(self):
        return (f"({self.source})-[{self.rel}:{'|'.join(self.rel_types)}"
                f"*{self.lower}..{self.upper}]-({self.target})")

"""``AlgoProcedureOp``: the relational operator behind ``CALL algo.*``.

One operator per planned procedure call.  ``_compute`` reads the graph
through the snapshot-consistent ``scan_node``/``scan_rel`` seam (live
writes and delta overlays are visible exactly as every other operator
sees them), compacts ids to index space, and dispatches:

* **device-fixpoint** — the fixed-shape jitted ``lax.while_loop``
  program (``algo/fixpoint.py``) at shape-lattice bucketed capacities,
  cached per ``(procedure, node capacity, edge capacity)`` on the
  device backend (``backend.algo_fns``); a miss builds and
  first-dispatches the program inside a ``charged("algo", ...)``
  compile-ledger boundary, so a warmed shape charges zero;
* **host** — the NumPy kernel (``algo/kernels.py``), chosen up front
  when the cost model priced the pushdown out (``prefer_host``), the
  session has no device backend, or the graph is empty;
* **fallback-host** — the same NumPy kernel serving a device FAULT
  (injected via ``testing/faults.failing_algo`` or real), counted in
  ``algo.fallbacks`` — digest-equal by construction, the degraded-mode
  contract.

``algo.bfs`` has no fixpoint program: its device path is a frontier
search over CSRs (``ops/bfs.py``; strategy device-fixpoint).  On a
device graph as ingested (a ``ScanGraph``: no delta overlay) it reads
no snapshot: it searches the ingest-time CSRs where they live (layout
``resident-csr``), brings a few numbers a level to the host, and leaves
its rows on the device.  Otherwise it searches CSRs built from the
snapshot (layout ``snapshot-csr``).

Convergence metrics (``iterations``, ``converged``, ``strategy``,
``procedure``) ride the operator's op_stats entry into PROFILE and the
observed-statistics store.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from caps_tpu.algo import kernels
from caps_tpu.algo.registry import ProcedureSignature
from caps_tpu.ir import exprs as E
from caps_tpu.obs.compile import charged as _compile_charged
from caps_tpu.okapi.types import CTFloat
from caps_tpu.relational.header import HeaderError, RecordHeader
from caps_tpu.relational.ops import RelationalOperator, host_eval
from caps_tpu.serve.errors import CancellationError as _CancellationError


class _HostOnly(Exception):
    """Internal: the device path is not applicable (no device backend,
    cost model chose host, empty graph) — NOT a fault."""


class _GraphArrays:
    """The compacted snapshot view one execution operates on: sorted
    unique node ids, edge endpoint *indices*, per-edge weights."""

    __slots__ = ("ids", "src", "tgt", "weights", "n")

    def __init__(self, ids: np.ndarray, src: np.ndarray, tgt: np.ndarray,
                 weights: np.ndarray):
        self.ids = ids
        self.src = src
        self.tgt = tgt
        self.weights = weights
        self.n = int(ids.shape[0])


class AlgoProcedureOp(RelationalOperator):
    """Execute one registered graph-algorithm procedure and emit its
    YIELD columns as plain value columns."""

    def __init__(self, context, parent: RelationalOperator, graph,
                 signature: ProcedureSignature,
                 args: Tuple[E.Expr, ...],
                 yields: Tuple[Tuple[str, str], ...],
                 prefer_host: bool = False):
        super().__init__(context, [parent])
        self.graph = graph
        self.signature = signature
        self.args = args
        self.yields = yields
        self.prefer_host = prefer_host
        self.strategy = "unplanned"
        self._layout = "host"

    # -- dispatch ----------------------------------------------------------

    def _compute(self):
        registry = self._registry()
        values = [host_eval(a, self.context.parameters) for a in self.args]
        bound = self.signature.bind(values)
        adjacency = None if self.prefer_host else self._resident_adjacency()
        if adjacency is not None:
            try:
                return self._compute_resident(adjacency, bound, registry)
            except _CancellationError:
                raise
            except Exception:
                # degraded mode, as below: the snapshot's host twin
                if registry is not None:
                    registry.counter("algo.fallbacks").inc()
                data = self._graph_arrays(bound)
                self._resolve_source(bound, data)
                out, iters, converged = self._compute_host(data, bound)
                self.strategy = "fallback-host"
                self._layout = "host"
                self._account(registry, iters, converged)
                return self._emit(data, out)
        data = self._graph_arrays(bound)
        self._resolve_source(bound, data)
        try:
            if self.prefer_host or data.n == 0:
                raise _HostOnly()
            out, iters, converged = self._compute_device(data, bound)
            self.strategy = "device-fixpoint"
        except _HostOnly:
            out, iters, converged = self._compute_host(data, bound)
            self.strategy = "host"
            self._layout = "host"
        except _CancellationError:
            raise  # budget expiry is the request's outcome, not a fault
        except Exception:
            # degraded mode: a faulting device fixpoint (injected or
            # real) is served by the NumPy twin — same answer, counted
            if registry is not None:
                registry.counter("algo.fallbacks").inc()
            out, iters, converged = self._compute_host(data, bound)
            self.strategy = "fallback-host"
            self._layout = "host"
        self._account(registry, iters, converged)
        return self._emit(data, out)

    def _account(self, registry, iters: int, converged: bool) -> None:
        if registry is not None:
            registry.counter("algo.executions").inc()
            registry.counter("algo.iterations").inc(int(iters))
        self._metric_extra = {
            "strategy": self.strategy,
            "procedure": self.signature.name,
            "layout": self._layout,
            "iterations": int(iters),
            "converged": bool(converged),
        }

    def _registry(self):
        session = getattr(self.context, "session", None)
        return getattr(session, "metrics_registry", None)

    # -- resident path: BFS over the ingest-time CSRs ----------------------

    def _resident_adjacency(self) -> Optional["Adjacency"]:
        """The graph's resident adjacency for ``algo.bfs``, built on first
        use and kept on the graph: on a single-chip device backend whose
        scanned tables are the ingested ones (a ``ScanGraph``: no delta
        overlay), every relationship table with a CSR over its source
        and its target and every node table with its id index.  None
        sends the call down the snapshot path."""
        from caps_tpu.backends.tpu.table import DeviceTable
        from caps_tpu.relational.graphs import ScanGraph
        backend = getattr(self.context.factory, "backend", None)
        graph = self.graph
        if (self.signature.name != "algo.bfs" or backend is None
                or backend.mesh is not None or type(graph) is not ScanGraph):
            return None
        cached = graph.__dict__.get("_bfs_adjacency")
        if cached is not None:
            return cached

        def csr(table, col):
            if not isinstance(table, DeviceTable) or table.is_local:
                return None
            column = table._cols.get(col)
            return None if column is None else table._csr_for(table, column)

        segments = []
        for rt in graph.rel_tables:
            m, t = rt.mapping, rt.table
            for key, other in ((m.source_col, m.target_col),
                               (m.target_col, m.source_col)):
                index = csr(t, key)
                if index is None:
                    return None
                segments.append((index, t._cols[other].data))
        node_csrs = [csr(nt.table, nt.mapping.id_col)
                     for nt in graph.node_tables]
        if not node_csrs or any(c is None for c in node_csrs):
            return None
        from caps_tpu.ops.bfs import build_adjacency
        adjacency = build_adjacency(segments, node_csrs, backend.host_read)
        graph.__dict__["_bfs_adjacency"] = adjacency
        return adjacency

    def _compute_resident(self, adjacency, bound: Dict[str, Any], registry):
        """``algo.bfs`` on the device from the ingest-time CSRs: nothing
        comes to the host but a few numbers a level, and the reached
        vertices stay on the device as the operator's table."""
        from caps_tpu.backends.tpu.column import Column
        from caps_tpu.backends.tpu.table import DeviceTable
        from caps_tpu.obs import profiler_span
        from caps_tpu.ops import bfs as BFS
        from caps_tpu.ops.segment import default_interpret
        backend = self.context.factory.backend
        # segments alternate (source CSR, target CSR) table by table
        n_segments = len(adjacency.indptrs)
        segments = {"out": range(0, n_segments, 2),
                    "in": range(1, n_segments, 2),
                    "both": range(n_segments)}[bound["direction"]]
        with profiler_span("caps_tpu.algo.bfs"):
            found = BFS.search(
                adjacency, tuple(segments), bound["source"],
                bound["max_depth"], bucket=backend.bucket,
                read=backend.host_read, use_kernel=backend.use_expand_kernel,
                interpret=default_interpret(),
                level_span=lambda: profiler_span("caps_tpu.algo.bfs.level"))
        if registry is not None:
            registry.counter("algo.bfs_searches").inc()
            registry.counter("algo.bfs_levels").inc(found.levels)
            registry.counter("algo.bfs_edges_examined").inc(found.examined)
            registry.counter("algo.bfs_passes").inc(found.passes)
            registry.counter("algo.bfs_chunks").inc(found.chunks)
        self.strategy = "device-fixpoint"
        self._layout = "resident-csr"
        self._account(registry, found.levels, found.converged)
        values = {"node": found.ids, "dist": found.dist}
        columns: Dict[str, Column] = {}
        header = RecordHeader.empty()
        for yield_name, out_name in self.yields:
            ctype = self.signature.yield_type(yield_name)
            columns[out_name] = Column("int", values[yield_name], found.ok,
                                       ctype)
            header = header.concat(RecordHeader.for_value(out_name, ctype))
        return header, DeviceTable(backend, columns, found.reached)

    # -- snapshot seam -----------------------------------------------------

    def _host_ints(self, table, col: str
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(values, ok) of one column, device table or host table."""
        host_column = getattr(table, "host_column", None)
        if host_column is not None:
            pair = host_column(col)  # None for non-integer columns
            if pair is not None:
                vals, ok = pair
                return np.asarray(vals), np.asarray(ok, dtype=bool)
        raw = table.column_values(col)
        ok = np.array([v is not None for v in raw], dtype=bool)
        vals = np.array([0 if v is None else v for v in raw])
        if vals.shape[0] == 0:
            vals = vals.astype(np.int64)
        return vals, ok

    def _graph_arrays(self, bound: Dict[str, Any]) -> _GraphArrays:
        nvar, rvar = "__algo_n", "__algo_r"
        n_header, n_table = self.graph.scan_node(nvar, ())
        ids, ok = self._host_ints(n_table, n_header.column(E.Var(nvar)))
        ids = np.unique(np.asarray(ids)[ok]).astype(np.int64)
        n = int(ids.shape[0])

        r_header, r_table = self.graph.scan_rel(rvar, ())
        rv = E.Var(rvar)
        src, sok = self._host_ints(r_table,
                                   r_header.column(E.StartNode(rv)))
        tgt, tok = self._host_ints(r_table,
                                   r_header.column(E.EndNode(rv)))
        # compact to valid rows up front: a device table's host mirror
        # is capacity-padded (validity folds in dead lanes) while the
        # local path's column_values is exact — after this both agree
        eok = sok & tok
        src = np.asarray(src).astype(np.int64)[eok]
        tgt = np.asarray(tgt).astype(np.int64)[eok]

        weights = np.ones(src.shape[0], dtype=np.float64)
        key = bound.get("weight")
        if key:
            try:
                wcol = r_header.column(E.Property(rv, key))
            except HeaderError:
                wcol = None  # unknown property: unit weights
            if wcol is not None:
                w, wok = self._host_ints(r_table, wcol)
                w = np.where(np.asarray(wok, bool),
                             np.asarray(w, dtype=np.float64), 1.0)
                if w.shape[0] == eok.shape[0]:
                    w = w[eok]  # capacity-aligned: same compaction
                if w.shape[0] == src.shape[0]:
                    weights = w

        if n == 0:
            empty = np.zeros(0, dtype=np.int64)
            return _GraphArrays(ids, empty, empty,
                                np.zeros(0, dtype=np.float64))
        lo, hi = int(ids[0]), int(ids[-1])
        span = hi - lo + 1
        if span <= max(1024, 4 * n):
            # dense id space (the common allocator layout): one O(1)
            # table lookup per endpoint instead of a binary search —
            # the wrong-slot mappings are filtered by the live check
            lut = np.full(span, n - 1, dtype=np.int64)
            lut[ids - lo] = np.arange(n, dtype=np.int64)
            si = lut[np.clip(src - lo, 0, span - 1)]
            ti = lut[np.clip(tgt - lo, 0, span - 1)]
        else:
            si = np.minimum(np.searchsorted(ids, src), n - 1)
            ti = np.minimum(np.searchsorted(ids, tgt), n - 1)
        live = (ids[si] == src) & (ids[ti] == tgt)
        return _GraphArrays(ids, si[live], ti[live], weights[live])

    def _resolve_source(self, bound: Dict[str, Any],
                        data: _GraphArrays) -> None:
        """Map a ``source`` node-id argument to its compacted index
        (-1 when the id is absent from the snapshot)."""
        if "source" not in bound:
            return
        sid = bound["source"]
        idx = int(np.searchsorted(data.ids, sid)) if data.n else 0
        if data.n and idx < data.n and int(data.ids[idx]) == sid:
            bound["source_index"] = idx
        else:
            bound["source_index"] = -1

    # -- device path (the failing_algo patch point) ------------------------

    def _compute_device(self, data: _GraphArrays, bound: Dict[str, Any]
                        ) -> Tuple[np.ndarray, int, bool]:
        backend = getattr(self.context.factory, "backend", None)
        if backend is None:
            raise _HostOnly()
        import jax.numpy as jnp

        from caps_tpu.algo.fixpoint import (build_dense_program,
                                            build_program, dense_eligible,
                                            scalar_values)

        name = self.signature.name
        if name == "algo.bfs":
            return self._bfs_snapshot(data, bound, backend)
        n, e = data.n, int(data.src.shape[0])
        n_pad = backend.bucket(max(n, 1))
        e_pad = backend.bucket(max(e, 1))

        node_mask = np.zeros(n_pad, dtype=bool)
        node_mask[:n] = True

        if dense_eligible(n_pad, e):
            # dense tile: the edge list approaches the full n x n
            # capacity square, so the matrix-unit-native layout wins —
            # densify ONCE on the host, iterate with matrix products
            self._layout = "dense-tile"
            flat = data.src * n_pad + data.tgt
            A = np.bincount(flat, minlength=n_pad * n_pad) \
                .reshape(n_pad, n_pad).astype(np.float64)
            if name == "algo.sssp":
                W = np.full(n_pad * n_pad, np.inf, dtype=np.float64)
                np.minimum.at(W, flat, np.maximum(data.weights, 0.0))
                W = W.reshape(n_pad, n_pad)
            else:
                W = A  # ignored by every non-sssp dense kernel
            Aj = jnp.asarray(A)
            Wj = Aj if W is A else jnp.asarray(W)
            operands = (jnp.asarray(node_mask), Aj,
                        Wj) + scalar_values(name, bound, n)
            key = (name, n_pad, "dense")
            shape = f"{name}:n{n_pad}:dense"
            build = lambda: build_dense_program(name, n_pad)
        else:
            self._layout = "edge-list"
            src = np.zeros(e_pad, dtype=np.int64)
            tgt = np.zeros(e_pad, dtype=np.int64)
            edge_mask = np.zeros(e_pad, dtype=bool)
            w = np.zeros(e_pad, dtype=np.float64)
            src[:e] = data.src
            tgt[:e] = data.tgt
            edge_mask[:e] = True
            w[:e] = data.weights
            operands = (jnp.asarray(node_mask), jnp.asarray(src),
                        jnp.asarray(tgt), jnp.asarray(edge_mask),
                        jnp.asarray(w)) + scalar_values(name, bound, n)
            key = (name, n_pad, e_pad)
            shape = f"{name}:n{n_pad}:e{e_pad}"
            build = lambda: build_program(name, n_pad, e_pad)

        fn = backend.algo_fns.get(key)
        if fn is None:
            # build + first-dispatch inside ONE ledger boundary, like
            # the count-pushdown closures: re-running a warmed shape
            # charges zero (the once-then-zero assertion)
            with _compile_charged("algo", shape=shape):
                fn = build()
                out, iters, converged = fn(*operands)
                out = np.asarray(out)
            backend.algo_fns[key] = fn
        else:
            out, iters, converged = fn(*operands)
            out = np.asarray(out)
        if out.dtype.kind == "f":
            # quantize with the SAME host function the oracle uses —
            # quantizing inside the jitted program drifts an ulp (XLA
            # turns the constant division into a reciprocal multiply)
            out = np.round(out, kernels.SCORE_DECIMALS)
        return out[:data.n], int(iters), bool(converged)

    def _bfs_snapshot(self, data: _GraphArrays, bound: Dict[str, Any],
                      backend) -> Tuple[np.ndarray, int, bool]:
        """``algo.bfs`` on a snapshot: the resident path's search
        (``ops/bfs.py``) over CSRs built here from the snapshot's edges,
        in index space; the node index is the identity over ``[0, n)``."""
        import jax.numpy as jnp

        from caps_tpu.obs import profiler_span
        from caps_tpu.ops import bfs as BFS
        from caps_tpu.ops.expand import DeviceCSR
        from caps_tpu.ops.segment import default_interpret
        n, e = data.n, int(data.src.shape[0])
        self._layout = "snapshot-csr"
        if n == 0:
            return np.zeros(0, dtype=np.int64), 0, True
        cap = backend.bucket(max(e, 1))

        def csr(keys: np.ndarray) -> DeviceCSR:
            perm = np.zeros(cap, dtype=np.int32)
            perm[:e] = np.argsort(keys, kind="stable")
            indptr = np.searchsorted(keys[perm[:e]], np.arange(n + 1))
            return DeviceCSR(jnp.asarray(indptr.astype(np.int32)),
                             jnp.asarray(perm), n)

        def padded(col: np.ndarray):
            out = np.zeros(cap, dtype=np.int32)
            out[:e] = col
            return jnp.asarray(out)

        ids = np.arange(n + 1, dtype=np.int32)
        nodes = DeviceCSR(jnp.asarray(ids), jnp.asarray(ids[:n]), n)
        adjacency = BFS.build_adjacency(
            [(csr(data.src), padded(data.tgt)),
             (csr(data.tgt), padded(data.src))], [nodes], backend.host_read)
        found = BFS.search(
            adjacency, {"out": (0,), "in": (1,),
                        "both": (0, 1)}[bound["direction"]],
            bound["source_index"], bound["max_depth"],
            bucket=backend.bucket, read=backend.host_read,
            use_kernel=backend.use_expand_kernel,
            interpret=default_interpret(),
            level_span=lambda: profiler_span("caps_tpu.algo.bfs.level"))
        out = np.full(n, kernels.UNREACHED, dtype=np.int64)
        reached = found.reached
        out[np.asarray(found.ids)[:reached]] = \
            np.asarray(found.dist)[:reached]
        return out, found.levels, found.converged

    # -- host path (oracle twin; also the degraded fallback) ---------------

    def _compute_host(self, data: _GraphArrays, bound: Dict[str, Any]
                      ) -> Tuple[np.ndarray, int, bool]:
        return kernels.run_host(self.signature.name, data.n, data.src,
                                data.tgt, data.weights, bound)

    # -- output assembly ---------------------------------------------------

    def _emit(self, data: _GraphArrays, out: np.ndarray):
        name = self.signature.name
        ids = data.ids
        if name == "algo.wcc":
            # labels are component-min *indices*: map back to node ids
            # so components are named by their smallest member id
            out = ids[out] if data.n else out
        keep = np.ones(data.n, dtype=bool)
        if name == "algo.bfs":
            keep = out != kernels.UNREACHED
        elif name == "algo.sssp":
            keep = np.isfinite(out)
        ids = ids[keep]
        out = out[keep]

        columns: Dict[str, list] = {}
        types: Dict[str, Any] = {}
        header = RecordHeader.empty()
        for yield_name, out_name in self.yields:
            ctype = self.signature.yield_type(yield_name)
            if yield_name == "node":
                vals = [int(v) for v in ids]
            elif ctype == CTFloat:
                vals = [float(v) for v in out]
            else:
                vals = [int(v) for v in out]
            columns[out_name] = vals
            types[out_name] = ctype
            header = header.concat(RecordHeader.for_value(out_name, ctype))
        table = self.context.factory.from_columns(columns, types)
        return header, table

    def _pretty_args(self) -> str:
        a = ", ".join(x.cypher_repr() for x in self.args)
        y = ", ".join(out if yn == out else f"{yn} AS {out}"
                      for yn, out in self.yields)
        return f"{self.signature.name}({a}) YIELD {y}"

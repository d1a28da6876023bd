"""TPUCypherSession — the user-facing session for the TPU backend.

Mirrors the reference's ``CAPSSession``/``CAPSSessionImpl`` (ref:
spark-cypher/.../api/CAPSSession.scala — reconstructed, mount empty;
SURVEY.md §2): the planning stack is untouched; only the Table factory is
device-backed.  Exposes the backend's fallback counter so benchmarks can
assert the hot path stayed on-device.
"""
from __future__ import annotations

from caps_tpu import obs
from caps_tpu.backends.tpu.table import DeviceBackend, DeviceTableFactory
from caps_tpu.ops import default_interpret
from caps_tpu.obs import clock, xla_events
from caps_tpu.okapi.config import DEFAULT_CONFIG
from caps_tpu.relational.session import (RelationalCypherSession,
                                         degraded_state)


class TPUCypherSession(RelationalCypherSession):
    # planner gate for the SpMV count pushdown (relational/count_pattern.py);
    # the local oracle stays on the join path so parity tests remain
    # independent
    supports_count_pushdown = True
    # planner gate for the worst-case-optimal multiway join
    # (relational/wcoj.py) — same oracle-independence rationale
    supports_wcoj = True

    def __init__(self, config=None):
        super().__init__(config)
        self.backend = DeviceBackend(self.config)
        # one lattice: session-level shape buckets (relational/shapes.py)
        # ARE the device padding ladder, so seeding from op_stats or the
        # plan store adapts padding, compile-shape labels, and the
        # ragged batch keys together
        self.backend.shapes = self.shape_lattice
        self._factory = DeviceTableFactory(self.backend)
        from caps_tpu.backends.tpu.fused import FusedExecutor
        self.fused = FusedExecutor(self.backend,
                                   max_entries=self.config.compile_cache_size)
        # every jax trace / backend compile of the process, counted
        # (xla.* in metrics_snapshot): once per process, not per session
        xla_events.install()

    @property
    def table_factory(self) -> DeviceTableFactory:
        return self._factory

    def _cypher_on_graph(self, graph, query, parameters=None):
        """Route every query through the fused executor: first run records
        the data-dependent sizes, repeats replay them with zero host syncs
        (backends/tpu/fused.py — the whole-stage-codegen analog).  Attaches
        the backend's communication accounting (ICI bytes shuffled by the
        hand-scheduled joins, strategy counts — SURVEY.md §5.5) to the
        result's metrics as per-query deltas."""
        be = self.backend
        # degraded unfused mode (relational/session.py, serve/ failure
        # containment): per-operator eager execution, no memo touched.
        # Update statements NEVER fuse: their effect is a commit, not a
        # replayable size stream — recording one under the handle's key
        # would replay stale sizes over changed data.
        from caps_tpu.relational.updates import is_update_query
        use_fused = (self.config.use_fused and not degraded_state()[1]
                     and not is_update_query(query))
        before = (be.ici_bytes, be.dist_joins, be.broadcast_joins,
                  be.fallbacks, be.syncs, be.ici_payload_bytes,
                  be.salted_joins, self.fused.generic_replays
                  if use_fused else 0)
        if not use_fused:
            result = super()._cypher_on_graph(graph, query, parameters)
        else:
            key = self.fused.key(graph, query, dict(parameters or {}))
            from caps_tpu.obs.compile import current_charges
            charges = current_charges()
            n0 = len(charges) if charges is not None else 0
            result = self.fused.run(
                key, lambda: super(TPUCypherSession, self)._cypher_on_graph(
                    graph, query, parameters))
            if (key is not None and self.fused.last_mode == "record"
                    and result.metrics is not None):
                # Compile ledger (obs/compile.py): a record-mode run is
                # THE fused compile boundary — its execute phase traces
                # and XLA-compiles every operator program.  Replays
                # charge nothing; a post-quarantine re-record of the
                # same (graph, params) shape counts as a re-compile.
                # Inner EXECUTE-phase boundaries (count-fused builds,
                # dist-join program misses) already charged themselves
                # above — subtract them so a query's compile seconds
                # sum the wall clock once, not twice ("plan" charges
                # never overlap: execute_s excludes the plan phase).
                exec_s = float(result.metrics.get("execute_s") or 0.0)
                if charges is not None:
                    exec_s -= sum(c["seconds"] for c in charges[n0:]
                                  if c["kind"] != "plan")
                # Shape label = the BUCKETED parameter shape signature
                # (relational/shapes.py), not a value hash: two record
                # runs whose bindings differ only within a bucket are
                # the SAME compiled shape, so the second counts as a
                # re-compile — compile.recompiles now measures genuinely
                # redundant record work (what generic replay + bucket
                # headroom exist to eliminate), not value churn.
                from caps_tpu.relational.shapes import (
                    param_shape_signature, signature_text)
                sig = signature_text(param_shape_signature(
                    dict(parameters or {}), lattice=self.shape_lattice))
                obs.compile_charge("fused_record", max(0.0, exec_s),
                                   shape=f"g{key[0]}:{sig}")
        if result.metrics is not None:
            result.metrics["ici_bytes"] = be.ici_bytes - before[0]
            result.metrics["dist_joins"] = be.dist_joins - before[1]
            result.metrics["broadcast_joins"] = be.broadcast_joins - before[2]
            result.metrics["device_fallbacks"] = be.fallbacks - before[3]
            result.metrics["size_syncs"] = be.syncs - before[4]
            result.metrics["ici_payload_bytes"] = \
                be.ici_payload_bytes - before[5]
            result.metrics["salted_joins"] = be.salted_joins - before[6]
            if use_fused:
                result.metrics["fused_generic_replays"] = \
                    self.fused.generic_replays - before[7]
        if self._profiling:
            self._annotate_profile(result)
        return result

    def cypher_batch(self, graph, items, scopes=None):
        """Serving micro-batch (relational/session.py): on this backend
        the members' fused replays dispatch back-to-back under one
        ``fused.batch`` bracket — zero size syncs per member, and the
        server defers materialization past the last member, so the
        device stream stays dense across the whole batch."""
        if self.config.use_fused and len(items) > 1:
            with self.fused.batch(len(items)):
                return super().cypher_batch(graph, items, scopes)
        return super().cypher_batch(graph, items, scopes)

    def _annotate_profile(self, result) -> None:
        """Fused-replay-aware PROFILE epilogue (never silently wrong
        numbers): when the query REPLAYED and per-op device sync was off,
        per-operator spans measured only host dispatch of an async
        stream — tag them so, and report device time as ONE per-replay
        aggregate span (a block_until_ready delta over the result
        table).  Eager/record runs (and per-op-sync profiles) already
        carry honest per-op times."""
        mode = self.fused.last_mode if self.config.use_fused else None
        if result.metrics is not None:
            result.metrics["fused_mode"] = mode or "eager"
        replayed = mode in ("replay", "replay_gen")
        per_op_device = self.tracer.sync_device
        if result.profile is not None:
            obs.tag_timing(result.profile,
                           "device" if per_op_device else
                           ("dispatch" if replayed else "host"))
        if replayed and not per_op_device and result.records is not None:
            t0 = clock.now()
            result.records.table.device_sync()
            device_s = clock.now() - t0
            self.tracer.event("fused_replay.aggregate", kind="phase",
                              device_s=device_s, fused_mode=mode)
            if result.metrics is not None:
                result.metrics["replay_device_s"] = device_s
            if result.profile is not None:
                result.profile["replay_device_s"] = device_s
                # per-op rows under generic replay are served UPPER
                # bounds; fix the root to the exact result cardinality
                # (one sync) and say what the inner numbers are
                if mode == "replay_gen":
                    try:
                        result.profile["rows"] = \
                            result.records.table.exact_size()
                    except Exception:
                        pass
                    result.profile["rows_inner"] = "upper-bound"

    def metrics_snapshot(self) -> dict:
        """Session snapshot extended with the device backend's counters
        (communication accounting, fallbacks, size syncs with the host
        seconds and bytes of every device->host read, Pallas kernel
        launches per family and whether they ran compiled), the fused
        executor's record/replay stats — the scattered stats the obs
        registry absorbs (ISSUE 3 tentpole) — and the process's jax
        trace/compile counts (obs/xla_events.py)."""
        snap = super().metrics_snapshot()
        be = self.backend
        snap.update({
            "backend.ici_bytes": be.ici_bytes,
            "backend.ici_payload_bytes": be.ici_payload_bytes,
            "backend.dist_joins": be.dist_joins,
            "backend.broadcast_joins": be.broadcast_joins,
            "backend.salted_joins": be.salted_joins,
            "backend.fallbacks": be.fallbacks,
            "backend.syncs": be.syncs,
            "backend.sync_wait_s": be.sync_wait_s,
            "backend.d2h_bytes": be.d2h_bytes,
            "backend.gathered_columns": be.gathered_columns,
            "backend.pruned_columns": be.pruned_columns,
            "backend.index_probes": be.index_probes,
            "backend.search_probes": be.search_probes,
            "backend.sort_compactions": be.sort_compactions,
            "backend.search_compactions": be.search_compactions,
            "backend.kernel.expand": be.kernel_launches["expand"],
            "backend.kernel.segment": be.kernel_launches["segment"],
            "backend.kernel.sort": be.kernel_launches["sort"],
            # compiled on TPU, Pallas interpret mode elsewhere
            "backend.kernels_compiled": int(not default_interpret()),
            "fused.recordings": self.fused.recordings,
            "fused.replays": self.fused.replays,
            "fused.generic_replays": self.fused.generic_replays,
            "fused.mismatches": self.fused.mismatches,
            "fused.batches": self.fused.batches,
            "fused.batch_members": self.fused.batch_members,
        })
        snap.update(xla_events.snapshot())
        return snap

    @property
    def fallback_count(self) -> int:
        return self.backend.fallbacks

    def health_check(self) -> dict:
        """Device health probe (SURVEY.md §5.3): run a tiny canary program
        on every device of the session's mesh (or the default device) and
        verify the arithmetic.  Returns {device_str: bool}.  A failed or
        crashing device reports False rather than raising, so callers can
        shrink the mesh and re-shard."""
        import jax
        import jax.numpy as jnp
        devices = (list(self.backend.mesh.devices.flat)
                   if self.backend.mesh is not None else [jax.devices()[0]])
        status = {}
        for d in devices:
            try:
                x = jax.device_put(jnp.arange(8, dtype=jnp.int32), d)
                ok = int((x * 2 + 1).sum()) == 64
            except Exception:
                ok = False
            status[str(d)] = ok
        return status

    def shrink_and_reshard(self, healthy=None, graphs=None) -> int:
        """Failure recovery (SURVEY.md §5.3): rebuild the mesh over the
        surviving devices (largest power-of-two prefix — bucketed
        capacities stay divisible) and re-place every device-resident
        graph onto it.  Columns with an ingest host mirror re-place from
        the mirror (a dead device's buffers are unreadable; the mirror
        is the replica — durable snapshots live in the fs PGDS); columns
        without one re-place device-to-device.  Compiled-program and
        physical-layout caches keyed to the old placement (fused-count
        closures, join sorts, CSR) are dropped/rebuilt.  Returns the new
        shard count.

        ``healthy``: surviving devices (default: health_check() == True).
        ``graphs``: extra graphs to re-place beyond the session catalog
        (e.g. ones created but never stored)."""
        import numpy as np
        from jax.sharding import Mesh
        from caps_tpu.backends.tpu.column import Column
        from caps_tpu.backends.tpu.table import DeviceTable
        from caps_tpu.okapi.catalog import SessionGraphDataSource
        import jax.numpy as jnp

        backend = self.backend
        old_mesh = backend.mesh
        if healthy is None:
            status = self.health_check()
            pool = (list(old_mesh.devices.flat)
                    if old_mesh is not None else [])
            healthy = [d for d in pool if status.get(str(d), False)]
        if not healthy:
            raise RuntimeError("no healthy devices to reshard onto")

        if old_mesh is not None and old_mesh.devices.ndim == 2:
            # multi-slice: regroup survivors by their original DCN row so
            # the rebuilt mesh keeps slice-contiguous placement (bulk
            # collectives stay on ICI); rows shrink to the smallest
            # surviving power-of-two width
            by_row = {}
            for r, row in enumerate(old_mesh.devices):
                keep = [d for d in row if d in healthy]
                if keep:
                    by_row[r] = keep
            width = 1 << (min(len(v) for v in by_row.values())
                          .bit_length() - 1)
            rows = [v[:width] for v in by_row.values()]
            if len(rows) > 1:
                backend.mesh = Mesh(np.array(rows),
                                    ("dcn", backend.axis))
            elif width > 1:
                backend.mesh = Mesh(np.array(rows[0]), (backend.axis,))
            else:
                backend.mesh = None
            survivors_flat = [d for r in rows for d in r]
        else:
            n = 1 << (len(healthy).bit_length() - 1)
            backend.mesh = (Mesh(np.array(healthy[:n]), (backend.axis,))
                            if n > 1 else None)
            survivors_flat = healthy[:n]
        target0 = survivors_flat[0]
        backend.fused_count_static.clear()
        backend.fused_count_fns.clear()

        targets = list(graphs or [])
        for ns in self.catalog.namespaces:
            src = self.catalog.source(ns)
            if isinstance(src, SessionGraphDataSource):
                targets.extend(src.graph(g) for g in src.graph_names())

        import jax

        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(arr):
            # explicit placement: jnp.asarray would stage on the DEFAULT
            # device, which may be the dead one; with no mesh the single
            # survivor is the target
            arr = jnp.asarray(arr) if not hasattr(arr, "ndim") else arr
            if (backend.mesh is not None and arr.ndim >= 1
                    and arr.shape[0] % backend.n_shards == 0):
                spec = ((tuple(backend.mesh.axis_names),)
                        + (None,) * (arr.ndim - 1))
                return jax.device_put(
                    arr, NamedSharding(backend.mesh, P(*spec)))
            return jax.device_put(arr, target0)

        def replace(col: Column) -> Column:
            if col.host is not None:
                data, valid = col.host
                return Column(col.kind, put(data), put(valid), col.ctype,
                              col.lens if col.lens is None
                              else put(col.lens), host=col.host)
            # no mirror: device-to-device reshard (readable survivors
            # only — truly lost buffers need the fs PGDS snapshot)
            return Column(col.kind, put(col.data), put(col.valid),
                          col.ctype,
                          col.lens if col.lens is None
                          else put(col.lens))

        seen = set()
        for g in targets:
            for et in (tuple(getattr(g, "node_tables", ()))
                       + tuple(getattr(g, "rel_tables", ()))):
                t = et.table
                if id(t) in seen or not isinstance(t, DeviceTable) \
                        or t.is_local:
                    continue
                seen.add(id(t))
                t._cols = {c: replace(col) for c, col in t._cols.items()}
            # rebuild the CSR physical layout on the new placement
            for nt in getattr(g, "node_tables", ()):
                self._factory.prepare_node_table(nt)
            for rt in getattr(g, "rel_tables", ()):
                self._factory.prepare_rel_table(rt)
        return int(backend.mesh.devices.size) if backend.mesh is not None \
            else 1

    @staticmethod
    def local(**kwargs) -> "TPUCypherSession":
        return TPUCypherSession(**kwargs)

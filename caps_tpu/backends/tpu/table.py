"""DeviceTable: the Table SPI over bucketed device columns.

The TPU counterpart of the reference's ``SparkTable.DataFrameTable`` (ref:
spark-cypher/.../impl/table/SparkTable.scala — reconstructed, mount empty;
SURVEY.md §2): filter = mask + compact, join = sort-merge + segmented
expansion, aggregate = sort + segment reductions, orderBy = multi-key
lexicographic lax.sort — all shape-static and jit-cached per bucket.

Collect and DISTINCT aggregation run on-device (sorted segment gather; an
extra stable sort per distinct column marks first occurrences — see
``_group_device``); the full LDBC read suite executes with zero host
fallbacks (``tests/test_ldbc.py::test_no_device_fallbacks``).  The
remaining operators without a device path (percentile DISTINCT, some
collection-valued expressions, …) raise :class:`UnsupportedOnDevice`; the
table then converts to the local oracle backend and continues there.
Fallbacks are counted on the backend object so benchmarks can assert the
hot path stayed on-device.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from caps_tpu import ops as OPS
from caps_tpu.backends.local.table import LocalTable, LocalTableFactory
from caps_tpu.backends.tpu import kernels as K
from caps_tpu.backends.tpu.column import (
    Column, column_to_host, kind_for, literal_column, make_column,
)
from caps_tpu.backends.tpu.expr import DeviceExprCompiler, UnsupportedOnDevice
from caps_tpu.backends.tpu.pool import make_pool
from caps_tpu.ir.exprs import Expr
from caps_tpu.obs import active_tracer, profiler_span, timed_span
from caps_tpu.obs.lockgraph import make_lock
from caps_tpu.okapi.config import EngineConfig
from caps_tpu.okapi.types import CTBoolean, CTInteger, CypherType
from caps_tpu.relational.header import RecordHeader
from caps_tpu.relational.table import AggSpec, Table, TableFactory

#: Where XLA's persistent compilation cache goes when the environment
#: does not place it: one fixed directory beside the package (the
#: checkout's root; git-ignored).  The path is part of every cache key,
#: so it never carries a pid, a time or a temp name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


#: span of the host blocked on the device while it dispatches a query
#: (``DeviceBackend.host_read``), and of a result's columns read back
#: (``DeviceBackend.result_read``: the serving tier does that after it
#: has released the execution lock, and a ``caps_tpu.`` name on a thread
#: that is not dispatching would take the dispatching thread's idle gaps)
SYNC_SPAN = "caps_tpu.sync"
RESULT_READ_SPAN = "table.to_host"


def _spanned(name: str):
    """Run a ``DeviceTable`` method under the profiler span ``name``:
    the level below the operator spans (one span a table method or join
    step, never one a ``jnp`` call)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with profiler_span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def _host_nbytes(out) -> int:
    """Bytes of the numpy array(s) a device->host read returned."""
    if isinstance(out, np.ndarray):
        return out.nbytes
    if isinstance(out, (tuple, list)):
        return sum(_host_nbytes(x) for x in out)
    return 0


def _column_read_nbytes(col: Column, n: int) -> int:
    """Bytes ``column_to_host`` brings back for ``n`` rows of ``col``
    (a ``valid`` that is already numpy is not read)."""
    parts = [col.data, col.valid]
    if col.lens is not None:
        parts.append(col.lens)
    return sum(n * a.dtype.itemsize * math.prod(a.shape[1:])
               for a in parts if not isinstance(a, np.ndarray))


def _place_compile_cache() -> None:
    """Persistent XLA compilation cache for TPU sessions: repeat
    processes reuse compiled executables.  ``JAX_COMPILATION_CACHE_DIR``
    places it from outside — JAX reads that variable itself, so with it
    set the engine configures no directory at all; unset, the fixed
    in-checkout default above is used.  The min-compile-time threshold
    drops to 0 either way: a query executes as many sub-second programs,
    exactly the entries the default 1 s threshold refuses to persist.
    TPU only: persisted XLA:CPU executables are host-machine AOT code,
    and reloading them on a host with different CPU features risks
    SIGILL (observed with virtual-device test meshes)."""
    if jax.default_backend() != "tpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class DeviceBackend:
    """Shared per-session state: string pool, config, mesh, fallback counter.

    Distribution model (SURVEY.md §7 step 7): with a mesh configured,
    columns are row-sharded over the mesh axis via ``NamedSharding`` and
    every jitted operator runs SPMD — XLA's partitioner inserts the
    collectives (all_gather for sort/probe, all_to_all for repartition),
    the scaling-book recipe.  Hand-written shard_map paths (the pushdown
    query step, the sharded Pallas aggregation) override it where we can
    schedule ICI traffic better than the partitioner.
    """

    def __init__(self, config: EngineConfig):
        self.pool = make_pool()
        self.config = config
        # Row-capacity bucket lattice (relational/shapes.py): defaults
        # to config.bucket_sizes — identical rounding to the old
        # ``config.bucket_for`` — and can be seeded from observed sizes.
        # The TPU session swaps in its session-level lattice so padding,
        # compile-shape labels, and the ragged batch keys all share ONE
        # set of boundaries.
        from caps_tpu.relational.shapes import ShapeBucketLattice
        self.shapes = ShapeBucketLattice(config.bucket_sizes)
        _place_compile_cache()
        self.fallbacks = 0
        self.fallback_reasons: List[str] = []
        self.syncs = 0  # device->host scalar materializations (perf metric)
        # host seconds blocked in device->host reads and the bytes they
        # brought back (counted syncs, PROFILE's barrier and result
        # materialization alike): all through account_wait()
        self.sync_wait_s = 0.0
        self.d2h_bytes = 0
        # projection push-down (relational/live_columns.py): columns
        # passed to _gather_cols, and columns a join or filter had and
        # did not gather because its ``keep`` left them out
        self.gathered_columns = 0
        self.pruned_columns = 0
        # equi-joins (_sort_merge_join) that probed the build side's
        # resident index (ops/expand.py csr_probe), and those that found
        # none and searched its sorted keys (kernels.probe_count)
        self.index_probes = 0
        self.search_probes = 0
        # row compactions a table method dispatched (compact_indices), by
        # the form their two shapes select: one key sort or a rank search
        self.sort_compactions = 0
        self.search_compactions = 0
        self._wait_lock = make_lock("table.DeviceBackend._wait_lock")
        # Pallas kernel launches per family (ops/kernel_table.py FAMILIES):
        # counted where the engine dispatches the kernel itself, not a
        # jnp twin chosen by shape
        self.kernel_launches: Dict[str, int] = dict.fromkeys(OPS.FAMILIES, 0)
        # device bool scalar accumulated by generic-replay relation
        # checks (consume_count/_rows); the fused executor syncs it once
        # per query and re-records on violation
        self._replay_viol = None
        # debug_obj_guard bookkeeping: __obj__ entries served under
        # generic replay with no non-stat relation check after them yet
        # (see consume_obj's invariant)
        self._obj_unguarded = 0
        # Distributed-join accounting (SURVEY.md §5.5/§5.8): bytes moved
        # over ICI by hand-scheduled collectives (static shape estimates:
        # each exchanged/gathered buffer counted once per hop it crosses),
        # and how often each strategy fired.
        self.ici_bytes = 0
        # device-MEASURED live-row payload bytes (psum of off-home rows
        # inside the exchange programs) — the cross-check on the padded
        # wire estimate above (round-5 VERDICT item 7)
        self.ici_payload_bytes = 0
        self.dist_joins = 0       # radix exchange joins executed
        self.broadcast_joins = 0  # all_gather broadcast joins executed
        self.salted_joins = 0     # radix joins that salted hot keys
        # last cost-model distribution decision (relational/cost.py
        # choose_dist_strategy) — the okapi sharded path's EXPLAIN /
        # debugging surface for radix-vs-salted-vs-broadcast
        self.last_dist_decision: Optional[Dict] = None
        # Size-sync routing for the fused executor (backends/tpu/fused.py):
        # None = eager (device->host sync per data-dependent size);
        # ("record", sizes)       = eager + record every size in order;
        # ("replay", sizes, [i])  = serve sizes from the memo, NO syncs —
        # the whole query stays async / traceable.
        self.count_mode: Optional[tuple] = None
        # Single-program count-pushdown caches (relational/count_pattern.py):
        # per-graph static structures (sorted edges/ids, segment boundary
        # gathers, id domain) and per-(graph, plan, params) jitted closures.
        self.fused_count_static: Dict[int, dict] = {}
        self.fused_count_fns: Dict[tuple, tuple] = {}
        # Worst-case-optimal multiway join (relational/wcoj.py): step
        # shapes whose first launch already charged the compile ledger's
        # ``wcoj`` kind — warmed shapes (and fused replays) charge zero.
        self.wcoj_compiled_shapes: set = set()
        # Graph-algorithm fixpoint programs (caps_tpu/algo/): jitted
        # per-(procedure, node capacity, edge capacity) closures; a miss
        # builds + first-dispatches one program and charges the compile
        # ledger's ``algo`` kind.
        self.algo_fns: Dict[tuple, object] = {}
        self.mesh = None
        self.axis = config.mesh_axis
        # degenerate leading axes collapse to a 1-D mesh so (1, 8) keeps
        # the hand-scheduled ring fast paths that (8,) gets
        if math.prod(config.mesh_shape[:-1] or (1,)) > 1:
            # multi-slice: ("dcn", axis) with DCN outer (SURVEY.md §5.8)
            from caps_tpu.parallel.mesh import make_mesh_2d
            self.mesh = make_mesh_2d(
                (math.prod(config.mesh_shape[:-1]), config.mesh_shape[-1]),
                axis=self.axis)
        elif config.mesh_shape:
            from caps_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(math.prod(config.mesh_shape),
                                  axis=self.axis)

    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    def place_rows(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Row-shard an array over the mesh (no-op single-chip or when the
        row count doesn't divide)."""
        if (self.mesh is None or arr.ndim == 0
                or arr.shape[0] % self.n_shards):
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P
        # rows flatten over every mesh axis (1-D: (axis,); 2-D: DCN-major
        # so each slice owns a contiguous row range)
        spec = (tuple(self.mesh.axis_names),) + (None,) * (arr.ndim - 1)
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def place_column(self, col: Column) -> Column:
        if self.mesh is None:
            return col
        # resharding moves device buffers only — the ingest host mirror
        # still describes the same values
        return Column(col.kind, self.place_rows(col.data),
                      self.place_rows(col.valid), col.ctype,
                      self.place_rows(col.lens) if col.lens is not None
                      else None, host=col.host)

    def bucket(self, n: int) -> int:
        return max(1, self.shapes.bucket(n))

    def use_kernel(self, family: str) -> bool:
        """Does the engine route ``family`` to its Pallas kernel here?
        ``use_pallas`` is the user's switch; the rest is the static
        table (ops/kernel_table.py): per device, and per whether this
        session's columns are sharded over a mesh."""
        return self.config.use_pallas and OPS.pallas_usable(
            family, sharded=self.mesh is not None)

    def use_expand_kernel(self, out_cap: int) -> bool:
        """:meth:`use_kernel` for an expansion into ``out_cap`` slots,
        counting the launch: capacities that are not a multiple of the
        kernel's tile (the 256 bucket) take its jnp twin by shape inside
        ``expand_positions`` and are not launches."""
        on = self.use_kernel("expand")
        if on and out_cap % OPS.EXPAND_TILE == 0:
            self.kernel_launches["expand"] += 1
        return on

    def compact_indices(self, mask: jnp.ndarray, out_cap: int) -> jnp.ndarray:
        """``K.compact_indices``, counted by the form its shapes select."""
        if K.compact_form(mask.shape[0], out_cap) == "sort":
            self.sort_compactions += 1
        else:
            self.search_compactions += 1
        return K.compact_indices(mask, out_cap)

    def account_wait(self, seconds: float, nbytes: int = 0,
                     syncs: int = 0) -> None:
        """Add one device->host wait to the three counters.  Under a
        lock: a worker materializing a result (``result_read``) runs
        beside the worker that holds the execution lock."""
        with self._wait_lock:
            self.syncs += syncs
            self.sync_wait_s += seconds
            self.d2h_bytes += nbytes

    def host_read(self, read):
        """THE counted device->host sync: every place the dispatching
        host blocks on a device value goes through here, so the count
        (``syncs``), the clock (``sync_wait_s``), the bytes
        (``d2h_bytes``) and the span are in one place.  ``read`` is a
        device array (brought back as numpy) or ``consume_obj``'s thunk,
        which does its reads and returns host arrays."""
        with timed_span(SYNC_SPAN) as t:
            out = read() if callable(read) else np.asarray(read)
        self.account_wait(t.seconds, _host_nbytes(out), syncs=1)
        return out

    def result_read(self, read, nbytes: int):
        """A result's values read back by the thunk ``read``: timed and
        sized like a sync, never counted in ``syncs`` (which counts what
        stands between the host and its next dispatch), and under the
        off-stream span name."""
        with timed_span(RESULT_READ_SPAN) as t:
            out = read()
        self.account_wait(t.seconds, nbytes)
        return out

    def consume_count(self, dev_scalar, relation: str = "exact") -> int:
        """Materialize a data-dependent size (see ``count_mode``).

        ``relation`` declares how the caller uses the value, so a
        param-GENERIC replay (fused.py) can serve sizes recorded for
        *different* parameter values and still stay exact:

        * ``"cap"``   — an upper bound (capacity/bucket/width choice);
          serving any value ≥ the actual one is correct.
        * ``"lo"``    — a lower bound (e.g. a domain minimum); serving
          any value ≤ the actual one is correct.
        * ``"exact"`` — semantics depend on the exact value (error
          counts, retry predicates); a generic replay must re-execute
          when the actual value differs.
        * ``"stat"``  — metrics only; any served value is acceptable.

        Under generic replay the relation is CHECKED on device (no sync):
        a violation raises the end-of-query re-record, so a wrong served
        value can never reach results."""
        mode = self.count_mode
        if mode is None:
            return int(self.host_read(dev_scalar))
        if mode[0] == "record":
            v = int(self.host_read(dev_scalar))
            mode[1].append(("size", v, relation))
            return v
        v = self._next_entry(mode, "size")
        if mode[0] == "replay_gen":
            if v[2] != relation:
                raise FusedReplayMismatch(
                    f"generic replay relation mismatch: recorded {v[2]}, "
                    f"consumed as {relation}")
            self._accumulate_violation(dev_scalar, v[1], relation)
        return v[1]

    @staticmethod
    def _next_entry(mode, tag: str):
        """Pop the next record/replay stream entry, validating its tag —
        any misalignment means the op sequence diverged from the
        recording."""
        entries, cursor = mode[1], mode[2]
        if cursor[0] >= len(entries):
            raise FusedReplayMismatch(
                f"replay consumed {cursor[0]} entries but the recording "
                f"only has {len(entries)}")
        v = entries[cursor[0]]
        cursor[0] += 1
        if not (isinstance(v, tuple) and v and v[0] == tag):
            raise FusedReplayMismatch(
                f"replay op sequence diverged: {tag} consumed where "
                f"{v[0] if isinstance(v, tuple) else type(v)} was recorded")
        return v

    def consume_rows(self, dev_scalar):
        """Like :meth:`consume_count` for a table's LIVE ROW COUNT:
        returns ``(n, live)`` where ``n`` is the host row count and
        ``live`` is ``None`` in eager/record/exact-replay mode.  Under
        generic replay ``n`` is a served upper bound and ``live`` is the
        exact device scalar — the caller must attach it to the produced
        table (``DeviceTable(..., live=live)``) so ``row_ok`` stays
        exact without a sync."""
        mode = self.count_mode
        if mode is None:
            return int(self.host_read(dev_scalar)), None
        if mode[0] == "record":
            v = int(self.host_read(dev_scalar))
            mode[1].append(("rows", v))
            return v, None
        v = self._next_entry(mode, "rows")
        if mode[0] == "replay_gen":
            # strict: actual must fit the SERVED count, not just its
            # bucket — consumers like union's concat offset slice by the
            # served n, so bucket slack is not uniformly safe.  Headroom
            # comes from the merge widening violated row caps to the
            # next bucket boundary instead (fused._merge_streams).
            self._accumulate_violation(dev_scalar, v[1], "cap")
            return v[1], jnp.asarray(dev_scalar).astype(jnp.int32)
        return v[1], None

    def consume_pred(self, host_value: bool, dev_thunk) -> bool:
        """A host BRANCH PREDICATE routed through the record/replay
        stream.  Never syncs: the host value is exactly known in
        eager/record mode, replay serves the recorded branch, and
        generic replay additionally checks ``dev_thunk()`` (a device
        bool of the actual predicate) against it — a divergent branch
        trips the end-of-query violation and re-records.  Without this,
        a host `if table.size == 0:` would silently follow the recorded
        branch when the actual emptiness differs (served sizes are only
        upper bounds)."""
        mode = self.count_mode
        if mode is None:
            return host_value
        if mode[0] == "record":
            mode[1].append(("size", int(host_value), "exact"))
            return host_value
        v = self._next_entry(mode, "size")
        if v[2] != "exact":
            raise FusedReplayMismatch(
                f"replay op sequence diverged: branch predicate consumed "
                f"where a {v[2]} size was recorded")
        if mode[0] == "replay_gen":
            self._accumulate_violation(
                jnp.asarray(dev_thunk()).astype(jnp.int64), v[1], "exact")
        return bool(v[1])

    def _accumulate_violation(self, dev_scalar, served: int,
                              relation: str) -> None:
        """Device-side relation check for generic replay: ORs into
        ``_replay_viol``, synced ONCE at the end of the query."""
        if relation == "stat":
            return
        actual = jnp.asarray(dev_scalar).astype(jnp.int64)
        served64 = jnp.int64(served)
        if relation == "cap":
            bad = actual > served64
        elif relation == "lo":
            bad = actual < served64
        else:  # exact
            bad = actual != served64
        self._replay_viol = (bad if self._replay_viol is None
                             else self._replay_viol | bad)
        # any non-stat relation check downstream of a served __obj__
        # counts as its guard (see consume_obj's invariant)
        self._obj_unguarded = 0

    def consume_obj(self, make):
        """Materialize a small data-dependent HOST value (e.g. the hot-key
        sample of the radix dist join) through the same record/replay
        stream as sizes: eager/record mode runs ``make()`` (counting its
        sync), replay serves the recorded value with NO device round trip
        — fused replays stay sync-free and ``be.syncs`` stays honest.

        INVARIANT (ADVICE r5): an ``__obj__`` entry has no device-side
        relation check of its own — under GENERIC replay the served host
        object may be stale for the current parameter values, and nothing
        here would notice.  Every consumer of ``consume_obj`` MUST
        therefore be guarded by a downstream relation-checked consume
        (``consume_count``/``consume_rows``/``consume_pred`` with a
        relation other than ``"stat"``) that would trip the end-of-query
        violation flag whenever the stale object could shape results —
        e.g. the radix join consumes its hot-key sample and then checks
        ``dropped == 0`` with relation ``"exact"``.  A consumer without
        such a guard silently serves wrong results.  Debug builds
        (``config.debug_obj_guard``) assert the guard exists: an obj
        served under generic replay with no later non-stat check raises
        at the end of the query (fused.py epilogue)."""
        mode = self.count_mode
        if mode is None:
            return self.host_read(make)
        if mode[0] == "record":
            v = self.host_read(make)
            mode[1].append(("__obj__", v))
            return v
        v = self._next_entry(mode, "__obj__")[1]
        if mode[0] == "replay_gen" and self.config.debug_obj_guard:
            self._obj_unguarded += 1
        return v


class FusedReplayMismatch(RuntimeError):
    """The op sequence during fused replay diverged from the recording."""


class DeviceTable(Table):
    def __init__(self, backend: DeviceBackend,
                 columns: Optional[Dict[str, Column]] = None, n: int = 0,
                 local: Optional[LocalTable] = None,
                 live: Optional[jnp.ndarray] = None):
        self.backend = backend
        self._cols: Dict[str, Column] = dict(columns or {})
        self._n = n
        self._local = local  # non-None → host-fallback mode
        # Generic-replay mode (fused.py): ``n`` is a SERVED upper bound
        # and ``live`` is the exact live-row count as a device scalar —
        # live rows always form a prefix (every producer compacts or
        # expands live-first), so row_ok stays exact with zero syncs.
        # None in eager/record mode, where ``n`` is exact.
        self._live = live
        self._exact_cache: Optional[int] = None  # memoized int(_live)

    # -- mode handling -------------------------------------------------

    @property
    def is_local(self) -> bool:
        return self._local is not None

    def to_local(self) -> LocalTable:
        if self._local is not None:
            return self._local
        n = self._exact_n()
        data = {c: self._column_to_host(col, n)
                for c, col in self._cols.items()}
        types = {c: col.ctype for c, col in self._cols.items()}
        return LocalTable(tuple(self._cols.keys()), data, types,
                          size=n)

    def _column_to_host(self, col: Column, n: int) -> List[Any]:
        be = self.backend
        return be.result_read(lambda: column_to_host(col, n, be.pool),
                              _column_read_nbytes(col, n))

    def _fallback(self, reason: str) -> "DeviceTable":
        self.backend.fallbacks += 1
        self.backend.fallback_reasons.append(reason)
        return DeviceTable(self.backend, local=self.to_local())

    def _wrap_local(self, local: LocalTable) -> "DeviceTable":
        return DeviceTable(self.backend, local=local)

    def _coerce_local(self, other: Table) -> LocalTable:
        if isinstance(other, DeviceTable):
            return other.to_local()
        assert isinstance(other, LocalTable)
        return other

    @property
    def capacity(self) -> int:
        if self._cols:
            return next(iter(self._cols.values())).capacity
        return self.backend.bucket(self._n)

    @property
    def row_ok(self) -> jnp.ndarray:
        m = K.row_mask(self.capacity, self._n)
        if self._live is not None:
            m = m & (jnp.arange(self.capacity) < self._live)
        return m

    def _with_cols(self, columns: Dict[str, Column]) -> "DeviceTable":
        """Row-preserving rebuild: same n and live count."""
        return DeviceTable(self.backend, columns, self._n, live=self._live)

    def _exact_n(self) -> int:
        """The exact live row count as a host int.  Free in eager mode;
        under generic replay this is a sync (counted), used only at
        materialization boundaries (to_local)."""
        if self._live is None:
            return self._n
        if self._exact_cache is None:
            self._exact_cache = int(self.backend.host_read(self._live))
        return self._exact_cache

    def exact_size(self) -> int:
        if self._local is not None:
            return self._local.size
        return self._exact_n()

    def size_hint(self) -> int:
        if self._local is not None:
            return self._local.size
        if self._exact_cache is not None:
            return self._exact_cache
        return self._n

    def branch_empty(self) -> bool:
        if self._local is not None:
            return self._local.size == 0
        mode = self.backend.count_mode
        if self._live is not None and (mode is None or mode[0] == "record"):
            # ADVICE r5: a table that escaped its fused activation (e.g.
            # a generic-replay query result reused as a plain input) only
            # knows a served UPPER bound in _n — it can be non-zero for
            # an actually-empty table with no violation check running.
            # The branch needs the exact count: pay the sync.  This
            # applies in RECORD mode too: consume_pred would bake the
            # stale bound into the recording as an "exact" branch, wrong
            # on the recording run and on every replay of it.
            host_empty = self._exact_n() == 0
        else:
            host_empty = self._n == 0
        return self.backend.consume_pred(
            host_empty,
            lambda: (self._live if self._live is not None
                     else jnp.int32(self._n)) == 0)

    def device_sync(self) -> None:
        """Completion barrier for PROFILE (obs/): block until every
        column buffer (and the live-count scalar) has materialized.  No
        transfer, no ``consume_count`` — safe under fused replay, it
        only serializes the async dispatch stream."""
        if self._local is not None:
            return
        try:
            with timed_span(SYNC_SPAN) as t:
                self._block_until_ready()
            self.backend.account_wait(t.seconds)
        except Exception:  # pragma: no cover — profiling must not fail a query
            pass

    def _block_until_ready(self) -> None:
        for col in self._cols.values():
            col.data.block_until_ready()
            col.valid.block_until_ready()
            if col.lens is not None:
                col.lens.block_until_ready()
        if self._live is not None and hasattr(self._live,
                                             "block_until_ready"):
            self._live.block_until_ready()

    def prime_exact(self, viol) -> bool:
        """Read the generic-replay violation flag batched with this
        table's exact live count in ONE transfer; primes the exact-count
        cache when the flag is clear (so a later ``to_maps`` pays no
        second round trip).  Returns the flag's truth value.  Falls back
        to a plain flag read when there is nothing to batch.  Either way
        it is the ONE counted sync of a generic replay."""
        read = self.backend.host_read
        if self._live is None or self._exact_cache is not None:
            return bool(read(viol))
        both = read(jnp.stack([jnp.asarray(viol).astype(jnp.int32),
                               jnp.asarray(self._live).astype(jnp.int32)]))
        bad = bool(both[0])
        if not bad:
            self._exact_cache = int(both[1])
        return bad

    # -- shape ----------------------------------------------------------

    @property
    def columns(self) -> Tuple[str, ...]:
        if self._local is not None:
            return self._local.columns
        return tuple(self._cols.keys())

    @property
    def size(self) -> int:
        if self._local is not None:
            return self._local.size
        return self._n

    def column_type(self, col: str) -> CypherType:
        if self._local is not None:
            return self._local.column_type(col)
        return self._cols[col].ctype

    @property
    def nbytes(self) -> int:
        """Exact device-buffer bytes of the columns (data + validity +
        list lengths), padding included — what an operator reading this
        table pulls through HBM."""
        if self._local is not None:
            return self._local.nbytes
        total = 0
        for col in self._cols.values():
            total += col.data.nbytes + col.valid.nbytes
            if col.lens is not None:
                total += col.lens.nbytes
        return total

    # -- column ops ------------------------------------------------------

    @_spanned("caps_tpu.table.select")
    def select(self, cols: Sequence[str]) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.select(cols))
        missing = [c for c in cols if c not in self._cols]
        if missing:
            raise KeyError(f"missing columns {missing}; have {self.columns}")
        return self._with_cols({c: self._cols[c] for c in cols})

    def rename(self, mapping: Mapping[str, str]) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.rename(mapping))
        out = {mapping.get(c, c): col for c, col in self._cols.items()}
        if len(out) != len(self._cols):
            raise ValueError(f"rename collision: {mapping}")
        return self._with_cols(out)

    def copy_column(self, src: str, dst: str) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.copy_column(src, dst))
        out = dict(self._cols)
        out[dst] = self._cols[src]
        return self._with_cols(out)

    def with_literal_column(self, name, value, ctype) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(
                self._local.with_literal_column(name, value, ctype))
        try:
            col = self.backend.place_column(
                literal_column(value, ctype, self.capacity,
                               self.backend.pool))
        except ValueError as ex:
            return self._fallback(str(ex)).with_literal_column(
                name, value, ctype)
        out = dict(self._cols)
        out[name] = col
        return self._with_cols(out)

    def with_row_index(self, name: str) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.with_row_index(name))
        col = self.backend.place_column(
            Column("int", jnp.arange(self.capacity, dtype=jnp.int64),
                   jnp.ones(self.capacity, bool), CTInteger))
        out = dict(self._cols)
        out[name] = col
        return self._with_cols(out)

    @_spanned("caps_tpu.table.with_column")
    def with_column(self, name, expr: Expr, header: RecordHeader,
                    parameters, ctype) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.with_column(
                name, expr, header, parameters, ctype))
        try:
            compiler = DeviceExprCompiler(self._cols, self.capacity, header,
                                          parameters, self.backend.pool,
                                          self.row_ok)
            col = compiler.compile(expr)
        except UnsupportedOnDevice as ex:
            return self._fallback(str(ex)).with_column(
                name, expr, header, parameters, ctype)
        self._raise_row_errors(compiler)
        out = dict(self._cols)
        out[name] = col
        return self._with_cols(out)

    def _raise_row_errors(self, compiler: DeviceExprCompiler) -> None:
        """Per-row runtime errors (e.g. division by zero): pay ONE host
        sync only when the compiled expression contains an error site,
        and raise the oracle's error class so all backends agree."""
        if compiler.error_mask is None:
            return
        n_err = self.backend.consume_count(
            compiler.error_mask.sum(dtype=jnp.int32))
        if int(n_err):
            from caps_tpu.backends.local.expr import ExprEvalError
            raise ExprEvalError(compiler.error_what)

    # -- row ops ---------------------------------------------------------

    @_spanned("caps_tpu.table.filter")
    def filter(self, expr: Expr, header: RecordHeader,
               parameters, keep=None) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(
                self._local.filter(expr, header, parameters, keep))
        try:
            compiler = DeviceExprCompiler(self._cols, self.capacity, header,
                                          parameters, self.backend.pool,
                                          self.row_ok)
            pred = compiler.compile(expr)
            if pred.kind != "bool":
                raise UnsupportedOnDevice("filter predicate is not boolean")
        except UnsupportedOnDevice as ex:
            return self._fallback(str(ex)).filter(expr, header, parameters,
                                                  keep)
        self._raise_row_errors(compiler)
        mask = pred.data & pred.valid & self.row_ok
        return self._compact(mask, keep)

    def drop_in(self, col: str, values) -> "DeviceTable":
        """Tombstone mask (relational/updates.py snapshot overlay): drop
        rows whose ``col`` is in ``values``, entirely on-device.  The id
        set is padded to a size bucket with a never-matching sentinel,
        so the compiled isin+compact program is shared across snapshots
        whose tombstone counts land in the same bucket — the
        pad-and-mask discipline, applied to deletes."""
        vals = sorted(int(v) for v in values)
        if not vals:
            return self
        if self._local is not None:
            return self._wrap_local(self._local.drop_in(col, vals))
        c = self._cols[col]
        cap = self.backend.bucket(len(vals))
        # pad by repeating a real entry: duplicates change nothing, and
        # no sentinel value needs to be reserved in the id domain
        padded = np.full(cap, vals[0], dtype=np.int64)
        padded[:len(vals)] = vals
        hit = jnp.isin(c.data, jnp.asarray(padded)) & c.valid
        return self._compact(self.row_ok & ~hit)

    def _kept(self, keep, also=()) -> Dict[str, Column]:
        """The columns a join or filter gathers: those in ``keep``
        (None: all) or ``also``; the rest are counted as pruned."""
        if keep is None:
            return self._cols
        want = set(keep).union(also)
        cols = {c: col for c, col in self._cols.items() if c in want}
        self.backend.pruned_columns += len(self._cols) - len(cols)
        return cols

    def _compact(self, mask: jnp.ndarray, keep=None) -> "DeviceTable":
        count = K.mask_count(mask)
        new_n, live = self.backend.consume_rows(count)
        out_cap = self.backend.bucket(new_n)
        idx = self.backend.compact_indices(mask, out_cap)
        idx = self.backend.place_rows(idx)
        return DeviceTable(self.backend,
                           self._gather(self._kept(keep), idx),
                           new_n, live=live)

    def _gather(self, cols: Dict[str, Column], idx) -> Dict[str, Column]:
        self.backend.gathered_columns += len(cols)
        return _gather_cols(cols, idx)

    def join(self, other: Table, how: str,
             pairs: Sequence[Tuple[str, str]], keep=None) -> "DeviceTable":
        if self._local is not None or (isinstance(other, DeviceTable)
                                       and other.is_local):
            return self._wrap_local(self.to_local().join(
                self._coerce_local(other), how, pairs, keep))
        assert isinstance(other, DeviceTable)
        shared = set(self.columns) & set(other.columns)
        if shared:
            raise ValueError(f"join column collision: {shared}")
        try:
            if how == "cross":
                return self._cross_join(other, keep)
            return self._sort_merge_join(other, how, pairs, keep)
        except UnsupportedOnDevice as ex:
            return self._wrap_local(self.to_local().join(
                other.to_local(), how, pairs, keep))

    def _join_key(self, col: Column, side: str = "l") -> jnp.ndarray:
        if col.kind in ("id", "int", "str", "bool"):
            return col.data.astype(jnp.int64)
        if col.kind == "float":
            # Monotone float64 -> int64 bit transform: order-preserving, so
            # the sort/search machinery works unchanged.  -0.0 is folded
            # into +0.0 first (they must join), and NaN maps to a per-side
            # sentinel so NaN never matches anything (incl. other NaNs).
            x = jnp.where(col.data == 0.0, 0.0, col.data)
            bits = x.view(jnp.int64)
            key = jnp.where(bits < 0, jnp.int64(-(2**63)) - bits, bits)
            nan_sent = K._L_NAN if side == "l" else K._R_NAN
            return jnp.where(jnp.isnan(col.data), nan_sent, key)
        raise UnsupportedOnDevice(f"join key of kind {col.kind}")

    def _cached_right_sort(self, other: "DeviceTable", rcol: Column):
        """Sort of the build side, memoized on the column object: static
        scan tables (the relationship table every Expand hop probes) are
        sorted once per graph, not once per hop."""
        key = (other._n,)
        cached = getattr(rcol, "_join_sort", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        r_ok = rcol.valid & other.row_ok
        rk = jnp.where(r_ok, self._join_key(rcol, side="r"), K._R_NULL)
        # through the shared sort gate, so the build-side sort rides the
        # bitonic kernel where _sort_perm picks it (lax.sort otherwise)
        perm = other._sort_perm([rk])
        res = (rk[perm], perm)
        rcol._join_sort = (key, res)
        return res

    def _csr_for(self, other: "DeviceTable", rcol: Column):
        """The HBM-resident CSR for a build-side column, if an ingest
        hook (DeviceTableFactory.prepare_rel_table / prepare_node_table)
        attached one to this very ``Column`` and the table still has the
        shape it was built for.  ``select`` and ``rename`` keep the
        ``Column``, so a scan of an ingested table finds it; whatever
        writes new columns (a filter, a union, the snapshot overlay)
        has none, and the join searches."""
        if not self.backend.config.use_csr:
            return None
        cached = getattr(rcol, "_csr", None)
        if cached is not None and cached[0] == (other._n,):
            return cached[1]
        return None

    def _masked_left_key(self, lcol: Column) -> jnp.ndarray:
        """Probe key with null values folded to the never-matching
        sentinel.  Liveness (row_ok) stays separate from key validity so
        LEFT joins retain null-key rows (SQL/openCypher: an unmatched —
        including null-keyed — left row survives null-extended)."""
        return jnp.where(lcol.valid, self._join_key(lcol), K._L_NULL)

    def _sort_merge_join(self, other: "DeviceTable", how: str,
                         pairs: Sequence[Tuple[str, str]],
                         keep=None) -> "DeviceTable":
        lc, rc = pairs[0]
        lcol, rcol = self._cols[lc], other._cols[rc]
        l_ok = self.row_ok
        left_join = how == "left"
        csr = self._csr_for(other, rcol)
        if csr is None:
            # No resident adjacency to probe: on a 1-D mesh, schedule the
            # collectives by hand (radix exchange / broadcast join) instead
            # of leaving the layout to GSPMD (parallel/dist_join.py).
            dist = self._dist_join(other, how, pairs)
            if dist is not None:
                return dist if keep is None else dist.select(keep)
        if csr is not None:
            perm = csr.perm
        else:
            with profiler_span("caps_tpu.table.join.right_sort"):
                rk_sorted, perm = self._cached_right_sort(other, rcol)
        with profiler_span("caps_tpu.table.join.probe"):
            if csr is not None:
                # CSR probe: two indptr gathers per row, no sort, no search
                self.backend.index_probes += 1
                counts, lo = csr.probe(self._masked_left_key(lcol), l_ok)
            else:
                self.backend.search_probes += 1
                counts, lo = K.probe_count(self._masked_left_key(lcol), l_ok,
                                           rk_sorted)
            total_dev = K.join_total(counts, l_ok, left_join)
            total, live = self.backend.consume_rows(total_dev)
        out_cap = self.backend.bucket(total)
        with profiler_span("caps_tpu.table.join.expand"):
            if self.backend.use_expand_kernel(out_cap):
                l_idx, r_idx, out_valid, r_matched = \
                    OPS.join_expand_via_positions(
                        counts, lo, perm, l_ok, out_cap, left_join,
                        interpret=OPS.default_interpret())
            else:
                l_idx, r_idx, out_valid, r_matched, _ = K.join_expand(
                    counts, lo, perm, l_ok, out_cap, left_join)
            l_idx = self.backend.place_rows(l_idx)
            r_idx = self.backend.place_rows(r_idx)
        with profiler_span("caps_tpu.table.join.gather"):
            # where _gather_tree's whole-column int64 splits are dispatched.
            # Only live columns are written: the probe read the first
            # pair's keys above, the extra pairs' keys stay until
            # _extra_pair_filter has read them.
            extra = [c for p in pairs[1:] for c in p]
            out_cols = self._gather(self._kept(keep, extra), l_idx)
            right = self._gather(other._kept(keep, extra), r_idx)
            for c, col in right.items():
                out_cols[c] = Column(col.kind, col.data, col.valid & r_matched,
                                     col.ctype, col.lens)
        out = DeviceTable(self.backend, out_cols, total, live=live)
        return out._extra_pair_filter(pairs, left_join, keep)

    def _extra_pair_filter(self, pairs: Sequence[Tuple[str, str]],
                           left_join: bool, keep=None) -> "DeviceTable":
        """Extra equality pairs: post-filter (the first pair drove the
        merge); the last one narrows to ``keep``."""
        out = self
        last = len(pairs) - 1
        for i, (lc2, rc2) in enumerate(pairs[1:], start=1):
            a, b = out._cols[lc2], out._cols[rc2]
            if a.kind == "float" or b.kind == "float":
                # NaN == NaN is False here, matching join semantics
                eq = (a.data.astype(jnp.float64)
                      == b.data.astype(jnp.float64)) & a.valid & b.valid
            else:
                eq = (a.data.astype(jnp.int64) == b.data.astype(jnp.int64)) \
                    & a.valid & b.valid
            if left_join:
                # unmatched left rows keep their single null-extended row
                rows = eq | ~out._cols[rc2].valid
            else:
                rows = eq
            out = out._compact(rows & out.row_ok,
                               keep if i == last else None)
        return out

    @staticmethod
    def _pad_rows_np(arr: jnp.ndarray, cap: int) -> jnp.ndarray:
        if arr.shape[0] == cap:
            return arr
        pad = cap - arr.shape[0]
        return jnp.concatenate(
            [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)])

    def _detect_hot_keys(self, l_key, l_ok, n: int, keep_top: int = 0):
        """Host-side probe-key sample → (sorted hot-key array, auto salt).
        A key is hot when its estimated frequency exceeds
        ``join_hot_factor`` × the per-device fair share; the suggested
        salt spreads the hottest key back under the fair share
        (SURVEY.md §5.8 'skew handled by salting hot keys').
        ``keep_top``: when no key crosses the threshold, still return the
        ``keep_top`` most frequent sampled keys (manual-salt mode must
        engage on the heaviest keys)."""
        cfg = self.backend.config
        H = cfg.join_hot_capacity
        S = min(4096, int(l_key.shape[0]))
        # one routed host materialization: record/replay-aware (a fused
        # replay serves the recorded sample sync-free) and counted in
        # be.syncs like every other device->host round trip
        sample, ok = self.backend.consume_obj(
            lambda: (np.asarray(l_key[:S]), np.asarray(l_ok[:S])))
        live = sample[ok]
        if live.shape[0] == 0:
            return np.zeros((0,), np.int64), 1
        vals, counts = np.unique(live, return_counts=True)
        fair = max(1.0, live.shape[0] / n)
        hot_mask = counts > cfg.join_hot_factor * fair
        hot_vals = vals[hot_mask]
        if hot_vals.shape[0] > H:  # keep the heaviest H
            order = np.argsort(counts[hot_mask])[::-1][:H]
            hot_vals = hot_vals[order]
        salt = 1
        if hot_vals.shape[0]:
            need = int(np.ceil(counts.max() / fair))
            salt = 2
            while salt < min(n, need):
                salt *= 2
            salt = min(salt, n)
        elif keep_top:
            hot_vals = vals[np.argsort(counts)[::-1][:keep_top]]
        return np.sort(hot_vals.astype(np.int64)), salt

    def _dist_join(self, other: "DeviceTable", how: str,
                   pairs: Sequence[Tuple[str, str]]
                   ) -> Optional["DeviceTable"]:
        """Hand-scheduled distributed join over a 1-D or 2-D mesh
        (parallel/dist_join.py): broadcast join for small build sides,
        all_to_all radix exchange with SURGICAL hot-key salting (only
        detected-hot keys replicate) otherwise.  Capacities pad to a
        shard multiple; list columns ride the exchange as matrix
        payloads.  Returns None when the shape/config rules it out — the
        caller then stays on the single-program GSPMD path."""
        be = self.backend
        cfg = be.config
        if be.mesh is None or how not in ("inner", "left"):
            return None
        n = be.n_shards
        if n <= 1:
            return None
        axis = be.axis if len(be.mesh.axis_names) == 1 \
            else tuple(be.mesh.axis_names)
        lc, rc = pairs[0]
        lcol, rcol = self._cols[lc], other._cols[rc]
        try:
            # null keys fold to the sentinel; liveness stays separate so
            # LEFT joins retain null-key rows (see _masked_left_key)
            l_key = jnp.where(lcol.valid, self._join_key(lcol, side="l"),
                              K._L_NULL)
            r_key = self._join_key(rcol, side="r")
        except UnsupportedOnDevice:
            return None
        from caps_tpu.parallel import dist_join as DJ
        l_ok = self.row_ok
        r_ok = rcol.valid & other.row_ok
        left_join = how == "left"

        # pad both sides to a shard multiple (virtual rows: ok=False)
        cap_l = -(-self.capacity // n) * n
        cap_r = -(-other.capacity // n) * n
        l_key = self._pad_rows_np(l_key, cap_l)
        l_ok = self._pad_rows_np(l_ok, cap_l)
        r_key = self._pad_rows_np(r_key, cap_r)
        r_ok = self._pad_rows_np(r_ok, cap_r)

        def flatten(cols, names, cap):
            arrs, layout = [], []
            for c in names:
                col = cols[c]
                arity = 2 + (col.lens is not None)
                arrs.append(self._pad_rows_np(col.data, cap))
                arrs.append(self._pad_rows_np(col.valid, cap))
                if col.lens is not None:
                    arrs.append(self._pad_rows_np(col.lens, cap))
                layout.append((c, arity))
            return arrs, layout

        l_names, r_names = list(self._cols), list(other._cols)
        l_arrs, l_layout = flatten(self._cols, l_names, cap_l)
        r_arrs, r_layout = flatten(other._cols, r_names, cap_r)
        n_l, n_r = len(l_arrs), len(r_arrs)

        KEY_OK_BYTES = 9  # int64 key + bool validity channel

        def row_bytes(arrs) -> int:
            return KEY_OK_BYTES + sum(
                a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
                for a in arrs)

        # strategy comes from the SAME model function the planner's
        # EXPLAIN annotation consults (relational/cost.py) — thresholds
        # are model inputs, and the runtime call prices ACTUAL row
        # counts where the plan-time call priced estimates.  "salted"
        # resolves on the radix path below once the hot-key sample
        # confirms (or refutes) the sketch's skew prediction.
        from caps_tpu.relational.cost import choose_dist_strategy
        strategy, decision = choose_dist_strategy(self._n, other._n,
                                                  n, cfg)
        be.last_dist_decision = {"strategy": strategy, **decision}
        if strategy == "broadcast":
            prog1 = DJ.make_broadcast_join(be.mesh, axis, n_l, n_r,
                                           1, left_join, True)
            (max_total, live_r) = prog1(l_key, l_ok, r_key, r_ok,
                                        *l_arrs, *r_arrs)
            out_cap_dev = be.bucket(max(1, be.consume_count(max_total, relation="cap")))
            prog2 = DJ.make_broadcast_join(be.mesh, axis, n_l, n_r,
                                           out_cap_dev, left_join, False)
            res = prog2(l_key, l_ok, r_key, r_ok, *l_arrs, *r_arrs)
            # each device receives the other (n-1) shards of the build
            # side; the count phase gathers only key+ok, the expand phase
            # the full payload.  Wire estimate = padded buffers; payload =
            # device-measured live rows (round-5 VERDICT item 7).
            wire = (KEY_OK_BYTES + row_bytes(r_arrs)) * cap_r * (n - 1)
            be.ici_bytes += wire
            # live_r = global live build rows; each is gathered to the
            # other n-1 devices (same convention as the wire estimate)
            payload = (KEY_OK_BYTES + row_bytes(r_arrs)) \
                * be.consume_count(live_r, relation="stat") * (n - 1)
            be.ici_payload_bytes += payload
            be.broadcast_joins += 1
            # per-execution span (obs/): the SAME accounting that feeds
            # MULTICHIP_*.json wire-estimate brackets, as a tracer event
            tr = active_tracer()
            if tr.enabled:
                tr.event("dist_join.broadcast", kind="collective",
                         bytes=wire, payload_bytes=payload, shards=n)
        else:
            manual = cfg.join_salt > 1
            # manual salt must engage even when detection finds no
            # outlier: fall back to salting the heaviest sampled key
            hot_np, auto_salt = self._detect_hot_keys(
                l_key, l_ok, n, keep_top=1 if manual else 0)
            salt = cfg.join_salt if manual else auto_salt
            # salt must divide the device count for distinct sub-bucket
            # targets (power-of-2 meshes: round down)
            salt = max(1, min(salt, n))
            while n % salt:
                salt -= 1
            H = max(1, cfg.join_hot_capacity)
            hot_keys = np.full((H,), np.iinfo(np.int64).max, np.int64)
            hot_keys[:hot_np.shape[0]] = hot_np[:H]
            hot_keys = jnp.asarray(np.sort(hot_keys))

            local_cap = max(cap_l, cap_r) // n
            bin_cap = min(local_cap, max(8, -(-local_cap * 2 // n)))
            # hot sub-buckets carry only the replicated hot build rows
            hot_bin_cap = bin_cap if salt <= 1 else \
                min(local_cap, max(8, bin_cap // 2))
            wire_total = 0  # across bin-widening retries, = ici_bytes delta
            while True:
                prog1 = DJ.make_radix_join_phase1(
                    be.mesh, axis, n, n_l, n_r,
                    tuple(str(a.dtype) for a in l_arrs),
                    tuple(str(a.dtype) for a in r_arrs), bin_cap, salt,
                    hot_bin_cap)
                outs = prog1(hot_keys, l_key, l_ok, r_key, r_ok,
                             *l_arrs, *r_arrs)
                (lok_r, counts, lo, perm, rok_r,
                 max_total, max_left, dropped, sent_l, sent_r) = outs[:10]
                payload = outs[10:]
                # of each device's n bins, n-1 cross ICI (bin i stays home
                # on device i); hot sub-buckets are the smaller buffers
                wire = (
                    row_bytes(l_arrs) * bin_cap
                    + row_bytes(r_arrs)
                    * (bin_cap + (salt - 1) * hot_bin_cap)
                ) * n * (n - 1)
                be.ici_bytes += wire
                wire_total += wire
                if be.consume_count(dropped, relation="exact") == 0:
                    break
                if bin_cap >= local_cap and hot_bin_cap >= local_cap:
                    return None  # safe bound exceeded: should not happen
                bin_cap = min(local_cap, bin_cap * 2)
                hot_bin_cap = min(local_cap, hot_bin_cap * 2)
            # device-measured payload: live rows that left their home
            payload_bytes = (
                row_bytes(l_arrs) * be.consume_count(sent_l, relation="stat")
                + row_bytes(r_arrs) * be.consume_count(sent_r, relation="stat"))
            be.ici_payload_bytes += payload_bytes
            tr = active_tracer()
            if tr.enabled:
                tr.event("dist_join.radix", kind="collective",
                         bytes=wire_total, payload_bytes=payload_bytes,
                         shards=n, salt=salt)
            total_dev = be.consume_count(max_left if left_join else max_total,
                                         relation="cap")
            out_cap_dev = be.bucket(max(1, total_dev))
            prog2 = DJ.make_radix_join_phase2(be.mesh, axis, n_l, n_r,
                                              out_cap_dev, left_join)
            res = prog2(lok_r, counts, lo, perm, rok_r, *payload)
            be.dist_joins += 1
            if salt > 1:
                be.salted_joins += 1

        l_valid, r_valid = res[0], res[1]
        datas = res[2:]
        out_cols: Dict[str, Column] = {}
        i = 0
        for (c, arity), side_valid, cols in \
                [(x, l_valid, self._cols) for x in l_layout] + \
                [(x, r_valid, other._cols) for x in r_layout]:
            col = cols[c]
            lens = datas[i + 2] if arity == 3 else None
            out_cols[c] = Column(col.kind, datas[i],
                                 datas[i + 1] & side_valid, col.ctype, lens)
            i += arity
        cap_out = int(l_valid.shape[0])
        tmp = DeviceTable(be, out_cols, cap_out)  # rows valid where l_valid
        out = tmp._compact(l_valid)
        return out._extra_pair_filter(pairs, left_join)

    def _cross_join(self, other: "DeviceTable", keep=None) -> "DeviceTable":
        total = self._n * other._n
        out_cap = self.backend.bucket(total)
        # per-live-left-row pair count: the exact device count when the
        # right side rides generic replay (other._n is then only a
        # served upper bound), the host int otherwise
        count_b = other._live if other._live is not None else other._n
        counts = jnp.where(self.row_ok, count_b, 0)
        offsets = jnp.cumsum(counts)
        t = jnp.arange(out_cap)
        l_idx = jnp.clip(jnp.searchsorted(offsets, t, side="right"),
                         0, max(0, self.capacity - 1))
        seg_start = jnp.where(l_idx > 0, offsets[l_idx - 1], 0)
        within = (t - seg_start) % max(1, other.capacity)
        out_cols = self._gather(self._kept(keep), l_idx)
        out_cols.update(self._gather(other._kept(keep), within))
        live = (offsets[-1].astype(jnp.int32)
                if (self._live is not None or other._live is not None)
                and self.capacity > 0 else None)
        return DeviceTable(self.backend, out_cols, total, live=live)

    def union_all(self, other: Table) -> "DeviceTable":
        if self._local is not None or (isinstance(other, DeviceTable)
                                       and other.is_local):
            return self._wrap_local(self.to_local().union_all(
                self._coerce_local(other)))
        assert isinstance(other, DeviceTable)
        if set(self.columns) != set(other.columns):
            raise ValueError(f"union column mismatch: {self.columns} vs "
                             f"{other.columns}")
        total = self._n + other._n
        out_cap = self.backend.bucket(total)
        out: Dict[str, Column] = {}
        for c in self.columns:
            a, b = self._cols[c], other._cols[c]
            if a.kind != b.kind:
                numeric = {"id", "int", "float"}
                if a.kind in numeric and b.kind in numeric:
                    target = "float" if "float" in (a.kind, b.kind) else "int"
                    a, b = a.astype_kind(target), b.astype_kind(target)
                else:
                    return self._fallback(
                        f"union kind mismatch {a.kind}/{b.kind}").union_all(other)
            out[c] = _concat_columns(a, self._n, b, other._n, out_cap,
                                     a.ctype.join(b.ctype))
        if self._live is None and other._live is None:
            return DeviceTable(self.backend, out, total)
        # generic replay: either side's live prefix may be shorter than
        # its served n, leaving a dead gap in the middle of the concat —
        # close it with a sync-free same-capacity compaction
        live_a = (self._live if self._live is not None
                  else jnp.int32(self._n))
        live_b = (other._live if other._live is not None
                  else jnp.int32(other._n))
        t = jnp.arange(out_cap)
        mask = (t < live_a) | ((t >= self._n) & (t < self._n + live_b))
        idx = self.backend.compact_indices(mask, out_cap)
        return DeviceTable(self.backend, self._gather(out, idx), total,
                           live=(live_a + live_b).astype(jnp.int32))

    def _sort_perm(self, keys: List[jnp.ndarray]) -> jnp.ndarray:
        """Stable multi-key sort permutation: the Pallas bitonic kernel
        on supported tile capacities (compiled TPU only, see
        ops/kernel_table.py), the lax.sort twin otherwise."""
        cap = self.capacity
        from caps_tpu.ops import sort as S
        if S.sort_cap_supported(cap) and self.backend.use_kernel("sort"):
            self.backend.kernel_launches["sort"] += 1
            return S.sort_perm_pallas(keys, cap)
        return K.sort_perm(keys, cap)

    @_spanned("caps_tpu.table.distinct")
    def distinct(self) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.distinct())
        try:
            keys = [(~self.row_ok).astype(jnp.int64)]
            for col in self._cols.values():
                keys.extend(_sort_keys(col, ascending=True,
                                       nulls_last=True, pool=self.backend.pool))
            perm = self._sort_perm(keys)
        except UnsupportedOnDevice as ex:
            return self._fallback(str(ex)).distinct()
        sorted_cols = self._gather(self._cols, perm)
        change = K.neighbor_change_keys([k[perm] for k in keys])
        # the sort puts dead rows last, so the sorted live mask is the
        # row_ok PREFIX (includes the generic-replay live count, which a
        # plain host row_mask would not)
        keep = change & self.row_ok[perm]
        tmp = DeviceTable(self.backend, sorted_cols, self._n,
                          live=self._live)
        return tmp._compact(keep)

    @_spanned("caps_tpu.table.order_by")
    def order_by(self, items: Sequence[Tuple[str, bool]]) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.order_by(items))
        try:
            keys = [(~self.row_ok).astype(jnp.int64)]
            for col_name, asc in items:
                col = self._cols[col_name]
                keys.extend(_sort_keys(col, ascending=asc, nulls_last=asc,
                                       pool=self.backend.pool))
            perm = self._sort_perm(keys)
        except UnsupportedOnDevice as ex:
            return self._fallback(str(ex)).order_by(items)
        return DeviceTable(self.backend, self._gather(self._cols, perm),
                           self._n, live=self._live)

    def skip(self, n: int) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.skip(n))
        n = max(0, n)
        new_n = max(0, self._n - n)
        out_cap = self.backend.bucket(new_n)
        idx = jnp.arange(out_cap) + n
        idx = jnp.clip(idx, 0, max(0, self.capacity - 1))
        live = (jnp.maximum(self._live - n, 0).astype(jnp.int32)
                if self._live is not None else None)
        return DeviceTable(self.backend, self._gather(self._cols, idx),
                           new_n, live=live)

    def limit(self, n: int) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.limit(n))
        new_n = min(max(0, n), self._n)
        out_cap = self.backend.bucket(new_n)
        idx = jnp.clip(jnp.arange(out_cap), 0, max(0, self.capacity - 1))
        live = (jnp.minimum(self._live, n).astype(jnp.int32)
                if self._live is not None else None)
        return DeviceTable(self.backend, self._gather(self._cols, idx),
                           new_n, live=live)

    # -- aggregation ------------------------------------------------------

    @_spanned("caps_tpu.table.group")
    def group(self, by: Sequence[str], aggs: Sequence[AggSpec]) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.group(by, aggs))
        try:
            return self._group_device(by, aggs)
        except UnsupportedOnDevice as ex:
            return self._fallback(str(ex)).group(by, aggs)

    def _group_device(self, by: Sequence[str],
                      aggs: Sequence[AggSpec]) -> "DeviceTable":
        fast = self._group_dense_pallas(by, aggs)
        if fast is not None:
            return fast
        cap = self.capacity
        pool = self.backend.pool
        if by:
            keys = [(~self.row_ok).astype(jnp.int64)]
            for c in by:
                keys.extend(_sort_keys(self._cols[c], True, True, pool))
            perm = self._sort_perm(keys)
            sorted_cols = self._gather(self._cols, perm)
            row_ok_sorted = self.row_ok[perm]
            change = K.neighbor_change_keys(
                [k[perm] for k in keys[1:]]) & row_ok_sorted
            seg_id = jnp.clip(jnp.cumsum(change.astype(jnp.int32)) - 1, 0, None)
            n_groups, groups_live = self.backend.consume_rows(
                K.mask_count(change))
        else:
            sorted_cols = dict(self._cols)
            seg_id = jnp.zeros(cap, jnp.int32)
            n_groups, groups_live = 1, None
            change = jnp.zeros(cap, bool).at[0].set(True) \
                if cap > 0 else jnp.zeros(cap, bool)
            row_ok_sorted = self.row_ok
        out_cap = self.backend.bucket(n_groups)
        if by:
            start_idx = self.backend.compact_indices(change, out_cap)
        else:
            start_idx = jnp.zeros(out_cap, jnp.int32)

        out: Dict[str, Column] = {}
        for c in by:
            col = sorted_cols[c]
            g = Column(col.kind, col.data[start_idx], col.valid[start_idx],
                       col.ctype, col.lens[start_idx] if col.lens is not None
                       else None)
            out[c] = g
        num_segments = out_cap

        # DISTINCT aggregation: one extra stable sort per distinct column
        # marks the FIRST occurrence of each (group, value); the agg then
        # runs with that mask ANDed in (oracle semantics: dedupe keeps the
        # first occurrence, so collect order matches too).
        group_keys_sorted = [k[perm] for k in keys] if by else []
        firstocc_cache: Dict[str, jnp.ndarray] = {}

        def firstocc_for(col_name: str) -> jnp.ndarray:
            if col_name not in firstocc_cache:
                col = sorted_cols[col_name]
                vk = _sort_keys(col, True, True, pool)
                combined = group_keys_sorted + vk
                p2 = self._sort_perm(combined)
                ch2 = K.neighbor_change_keys([k[p2] for k in combined])
                firstocc_cache[col_name] = \
                    jnp.zeros(cap, bool).at[p2].set(ch2)
            return firstocc_cache[col_name]

        for a in aggs:
            if a.kind in ("percentile_cont", "percentile_disc"):
                out[a.name] = self._percentile_agg(
                    a, sorted_cols, group_keys_sorted, seg_id, num_segments,
                    row_ok_sorted, n_groups, start_idx,
                    firstocc=firstocc_for(a.col) if a.distinct else None)
                continue
            extra = firstocc_for(a.col) if a.distinct else None
            out[a.name] = self._one_agg(a, sorted_cols, seg_id, num_segments,
                                        row_ok_sorted, n_groups,
                                        firstocc=extra, start_idx=start_idx)
        return DeviceTable(self.backend, out, n_groups, live=groups_live)

    def _percentile_agg(self, a: AggSpec, cols: Dict[str, Column],
                        group_keys_sorted, seg_id, num_segments: int,
                        row_ok, n_groups: int, start_idx,
                        firstocc=None) -> Column:
        """percentileDisc/percentileCont on device: one extra stable sort
        by (group keys, value) puts each group's valid values ascending at
        the head of its row block, so the percentile is a rank gather —
        disc picks the ceil(p·n) nearest rank (Neo4j semantics, matching
        the oracle), cont lerps between the straddling ranks.  The re-sort
        is group-major with the same keys, so each group's block keeps the
        caller's offsets (``start_idx``).  DISTINCT passes ``firstocc``:
        duplicate occurrences are excluded and pushed to the block tail by
        an extra sort key so rank positions stay contiguous."""
        group_live = jnp.arange(num_segments) < n_groups
        col = cols[a.col]
        if col.kind not in ("int", "float", "id", "bool"):
            raise UnsupportedOnDevice(f"{a.kind} over kind {col.kind}")
        pool = self.backend.pool
        vk = _sort_keys(col, True, True, pool)
        # grouped: group_keys_sorted[0] is already the ~row_ok key;
        # ungrouped it must be added — capacity-padding rows LOOK valid
        # (compaction duplicates row 0) and would interleave the run
        lead = (list(group_keys_sorted) if group_keys_sorted
                else [(~row_ok).astype(jnp.int64)])
        ok_full = col.valid & row_ok
        if firstocc is not None:
            ok_full = ok_full & firstocc
            # non-first duplicates must not occupy rank positions: sort
            # them to each group's block tail
            lead = lead + [(~ok_full).astype(jnp.int64)]
        p2 = self._sort_perm(lead + vk)
        ok = ok_full[p2]
        seg2 = seg_id[p2]  # still non-decreasing: stable + group-major
        values = col.data[p2]
        counts = K.sorted_segment_agg(ok, ok, seg2, num_segments, "count")
        starts = start_idx.astype(jnp.int64)
        p = float(a.percentile or 0.0)
        cap_idx = values.shape[0] - 1
        if a.kind == "percentile_disc":
            # nearest-rank (Neo4j semantics): 1-based rank ceil(p*n)
            rank = jnp.ceil(p * counts.astype(jnp.float64)).astype(jnp.int64)
            r = jnp.clip(jnp.maximum(rank, 1) - 1, 0,
                         jnp.maximum(counts - 1, 0))
            data = values[jnp.clip(starts + r, 0, cap_idx)]
            return Column(col.kind, data, (counts > 0) & group_live,
                          col.ctype)
        pos = p * jnp.maximum(counts - 1, 0).astype(jnp.float64)
        lo = jnp.floor(pos).astype(jnp.int64)
        hi = jnp.minimum(lo + 1, jnp.maximum(counts - 1, 0))
        frac = pos - lo.astype(jnp.float64)
        vlo = values[jnp.clip(starts + lo, 0, cap_idx)].astype(jnp.float64)
        vhi = values[jnp.clip(starts + hi, 0, cap_idx)].astype(jnp.float64)
        data = vlo * (1.0 - frac) + vhi * frac
        from caps_tpu.okapi.types import CTFloat
        return Column("float", data, (counts > 0) & group_live, CTFloat)

    def _group_dense_pallas(self, by: Sequence[str],
                            aggs: Sequence[AggSpec]
                            ) -> Optional["DeviceTable"]:
        """Sort-free group-by over a dictionary-coded key: the string pool
        makes group keys a *dense* int domain, so grouping is a Pallas
        histogram (caps_tpu/ops/segment.py) — no lax.sort, no scatter.
        Returns None when the shape doesn't fit (the sorted path takes
        it — a static choice; a kernel failure raises)."""
        if len(by) != 1 or not self.backend.use_kernel("segment"):
            return None
        if any(a.distinct or a.kind == "collect" for a in aggs):
            return None  # sorted path handles distinct/collect
        key_col = self._cols.get(by[0])
        if key_col is None or key_col.kind not in ("str", "bool"):
            return None
        domain = len(self.backend.pool) if key_col.kind == "str" else 2
        S = domain + 1  # one slot for the null-key group
        if S > 4096 or S > self.capacity * 64:
            return None
        for a in aggs:
            if a.kind not in ("count_star", "count", "min", "max"):
                return None
            if a.kind in ("min", "max"):
                c = self._cols.get(a.col)
                if c is None or c.kind not in ("int", "id"):
                    return None
        row_ok = self.row_ok
        # int64 min/max ride the i32 kernel only when the values fit
        for c in {a.col for a in aggs if a.kind in ("min", "max")}:
            col = self._cols[c]
            if col.kind == "int":
                ok = col.valid & row_ok
                lo = self.backend.consume_count(
                    jnp.min(jnp.where(ok, col.data, 0)), relation="lo")
                hi = self.backend.consume_count(
                    jnp.max(jnp.where(ok, col.data, 0)), relation="cap")
                if not (-2**31 < lo and hi < 2**31):
                    return None

        interp = OPS.default_interpret()
        backend = self.backend
        sharded = (backend.mesh is not None
                   and self.capacity % backend.n_shards == 0)

        def agg_kernel(codes_, ok_, vals_, kind_):
            backend.kernel_launches["segment"] += 1
            if sharded:
                return OPS.dense_segment_agg_sharded(
                    backend.mesh, backend.axis, codes_, ok_, vals_, S, kind_,
                    interpret=interp)
            return OPS.dense_segment_agg(codes_, ok_, vals_, S, kind_,
                                         interpret=interp)

        codes = jnp.where(key_col.valid & row_ok,
                          key_col.data.astype(jnp.int32), domain)
        counts_all = agg_kernel(codes, row_ok, codes, "count")
        count_cache: Dict[str, jnp.ndarray] = {}

        def count_of(col_name: str) -> jnp.ndarray:
            if col_name not in count_cache:
                col = self._cols[col_name]
                count_cache[col_name] = agg_kernel(
                    codes, col.valid & row_ok, codes, "count")
            return count_cache[col_name]

        out: Dict[str, Column] = {}
        live = jnp.ones(S, bool)
        if key_col.kind == "str":
            out[by[0]] = Column("str", jnp.arange(S, dtype=jnp.int32),
                                jnp.arange(S) < domain, key_col.ctype)
        else:
            out[by[0]] = Column("bool", jnp.arange(S) == 1,
                                jnp.arange(S) < domain, key_col.ctype)
        for a in aggs:
            if a.kind == "count_star":
                out[a.name] = Column("int", counts_all.astype(jnp.int64),
                                     live, CTInteger)
            elif a.kind == "count":
                out[a.name] = Column("int",
                                     count_of(a.col).astype(jnp.int64),
                                     live, CTInteger)
            else:  # min / max over int/id
                col = self._cols[a.col]
                vals = col.data.astype(jnp.int32)
                agg = agg_kernel(
                    codes, col.valid & row_ok, vals,
                    "min_i32" if a.kind == "min" else "max_i32")
                has = count_of(a.col) > 0
                out[a.name] = Column(col.kind, agg.astype(
                    jnp.int64 if col.kind == "int" else jnp.int32),
                    has, col.ctype)
        dense = DeviceTable(self.backend, out, S)
        return dense._compact(counts_all > 0)

    def _one_agg(self, a: AggSpec, cols: Dict[str, Column], seg_id,
                 num_segments: int, row_ok, n_groups: int,
                 firstocc=None, start_idx=None) -> Column:
        group_live = jnp.arange(num_segments) < n_groups
        if a.kind == "count_star":
            data = K.sorted_segment_agg(row_ok, row_ok, seg_id,
                                        num_segments, "count")
            return Column("int", data, group_live, CTInteger)
        col = cols[a.col]
        ok = col.valid & row_ok
        if firstocc is not None:
            ok = ok & firstocc
        if a.kind == "count":
            data = K.sorted_segment_agg(ok, ok, seg_id, num_segments, "count")
            return Column("int", data, group_live, CTInteger)
        if a.kind == "collect":
            return self._collect_agg(a, col, ok, seg_id, num_segments,
                                     group_live, start_idx)
        if col.kind == "list":
            raise UnsupportedOnDevice(f"{a.kind} over list column")
        if a.kind == "first":
            data, has = K.segment_agg(col.data, ok, seg_id, num_segments,
                                      "first")
            return Column(col.kind, data, has & group_live, col.ctype)
        if col.kind == "str" and a.kind in ("min", "max"):
            rank = jnp.asarray(self.backend.pool.rank_array())
            if rank.shape[0] == 0:
                return Column("str", jnp.zeros(num_segments, jnp.int32),
                              jnp.zeros(num_segments, bool), col.ctype)
            ranks = rank[jnp.clip(col.data, 0, rank.shape[0] - 1)]
            agg = K.segment_agg(ranks.astype(jnp.int64), ok, seg_id,
                                num_segments, a.kind)
            counts = K.segment_agg(ranks, ok, seg_id, num_segments, "count")
            inv = jnp.argsort(rank).astype(jnp.int32)
            safe = jnp.clip(agg, 0, inv.shape[0] - 1).astype(jnp.int32)
            return Column("str", inv[safe], (counts > 0) & group_live,
                          col.ctype)
        if col.kind not in ("int", "float", "id", "bool"):
            raise UnsupportedOnDevice(f"{a.kind} over kind {col.kind}")
        values = col.data
        counts = K.segment_agg(values, ok, seg_id, num_segments, "count")
        if a.kind == "sum":
            if col.kind in ("int", "bool"):
                data = K.sorted_segment_agg(values.astype(jnp.int64), ok,
                                            seg_id, num_segments, "sum")
            else:
                data = K.segment_agg(values, ok, seg_id, num_segments, "sum")
            return Column(col.kind if col.kind != "bool" else "int",
                          data, group_live,
                          a.result_type or col.ctype)
        if a.kind in ("min", "max"):
            data = K.segment_agg(values, ok, seg_id, num_segments, a.kind)
            return Column(col.kind, data, (counts > 0) & group_live, col.ctype)
        if a.kind == "avg":
            s = K.segment_agg(values.astype(jnp.float64), ok, seg_id,
                              num_segments, "sum")
            data = s / jnp.maximum(counts, 1)
            from caps_tpu.okapi.types import CTFloat
            return Column("float", data, (counts > 0) & group_live, CTFloat)
        if a.kind == "stdev":
            v = values.astype(jnp.float64)
            s = K.segment_agg(v, ok, seg_id, num_segments, "sum")
            s2 = K.segment_agg(v * v, ok, seg_id, num_segments, "sum")
            nn = jnp.maximum(counts, 1).astype(jnp.float64)
            var = jnp.maximum(0.0, (s2 - s * s / nn) / jnp.maximum(nn - 1, 1))
            data = jnp.sqrt(var)
            data = jnp.where(counts > 1, data, 0.0)
            from caps_tpu.okapi.types import CTFloat
            return Column("float", data, (counts > 0) & group_live, CTFloat)
        raise UnsupportedOnDevice(f"aggregation {a.kind}")

    def _collect_agg(self, a: AggSpec, col: Column, ok, seg_id,
                     num_segments: int, group_live, start_idx) -> Column:
        """collect(x) on device: per-group value lists laid out as a
        (groups, max_len) int32 matrix via one flat scatter.  Kept rows
        are in group-sorted (stable) order, i.e. original row order within
        each group — the oracle's collect order."""
        from caps_tpu.backends.tpu.column import list_elem_kind
        if col.kind not in ("id", "int", "str", "bool"):
            raise UnsupportedOnDevice(f"collect over kind {col.kind}")
        if a.result_type is None or list_elem_kind(a.result_type) is None:
            raise UnsupportedOnDevice("collect to host-only list type")
        if col.kind == "int":
            lo = self.backend.consume_count(
                jnp.min(jnp.where(ok, col.data, 0)), relation="lo")
            hi = self.backend.consume_count(
                jnp.max(jnp.where(ok, col.data, 0)), relation="cap")
            if not (-2**31 < lo and hi < 2**31):
                raise UnsupportedOnDevice("collect of int64-range values")
        counts = K.segment_agg(col.data, ok, seg_id, num_segments, "count")
        max_len = self.backend.consume_count(
            jnp.max(counts) if num_segments else jnp.int64(0),
            relation="cap")
        L = max(1, int(max_len))
        # rank of each kept row within its segment
        c = jnp.cumsum(ok.astype(jnp.int32))
        sp = start_idx[jnp.clip(seg_id, 0, start_idx.shape[0] - 1)]
        base = jnp.where(sp > 0, c[jnp.maximum(sp - 1, 0)], 0)
        within = c - 1 - base
        sentinel = num_segments * L
        flat_idx = jnp.where(ok, seg_id * L + within, sentinel)
        vals32 = (col.data != 0).astype(jnp.int32) if col.kind == "bool" \
            else col.data.astype(jnp.int32)
        flat = jnp.zeros(sentinel + 1, jnp.int32).at[flat_idx].set(vals32)
        data = flat[:-1].reshape(num_segments, L)
        return Column("list", data, group_live, a.result_type,
                      counts.astype(jnp.int32))

    # -- lists -----------------------------------------------------------

    def explode(self, list_col: str, out_col: str,
                out_type: CypherType) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.explode(list_col, out_col,
                                                        out_type))
        col = self._cols.get(list_col)
        if col is None or col.kind != "list":
            return self._fallback("explode of non-list column").explode(
                list_col, out_col, out_type)
        ok = col.valid & self.row_ok
        total, live = self.backend.consume_rows(
            jnp.where(ok, col.lens, 0).sum())
        out_cap = self.backend.bucket(total)
        row, within, out_valid, _ = K.explode_expand(col.lens, ok, out_cap)
        rest = {c: v for c, v in self._cols.items() if c != list_col}
        out_cols = self._gather(rest, row)
        values = col.data[row, jnp.clip(within, 0, col.data.shape[1] - 1)]
        out_kind = kind_for(out_type)
        if out_kind == "object":
            return self._fallback("explode to host-only element type"
                                  ).explode(list_col, out_col, out_type)
        from caps_tpu.backends.tpu.column import _DTYPES
        if out_kind == "bool":
            values = values != 0
        else:
            values = values.astype(_DTYPES[out_kind])
        out_cols[out_col] = Column(out_kind, values, out_valid, out_type)
        return DeviceTable(self.backend, out_cols, total, live=live)

    def pack_list(self, cols: Sequence[str], out_col: str,
                  out_type: CypherType) -> "DeviceTable":
        if self._local is not None:
            return self._wrap_local(self._local.pack_list(cols, out_col,
                                                          out_type))
        cap = self.capacity
        if not cols:
            data = jnp.zeros((cap, 1), jnp.int32)
            lens = jnp.zeros(cap, jnp.int32)
        else:
            parts = []
            valids = []
            for c in cols:
                col = self._cols[c]
                if col.kind not in ("id", "int"):
                    return self._fallback("pack_list of non-id column"
                                          ).pack_list(cols, out_col, out_type)
                parts.append(col.data.astype(jnp.int32))
                valids.append(col.valid)
            stacked = jnp.stack(parts, axis=1)          # (cap, k)
            vstacked = jnp.stack(valids, axis=1)
            # compact valid entries to the left per-row
            order = jnp.argsort(~vstacked, axis=1, stable=True)
            data = jnp.take_along_axis(stacked, order, axis=1)
            lens = vstacked.sum(axis=1).astype(jnp.int32)
        out = dict(self._cols)
        out[out_col] = Column("list", data, jnp.ones(cap, bool), out_type,
                              lens)
        return self._with_cols(out)

    # -- materialization --------------------------------------------------

    def column_values(self, col: str) -> List[Any]:
        if self._local is not None:
            return self._local.column_values(col)
        return self._column_to_host(self._cols[col], self._exact_n())

    def host_column(self, col: str):
        """(values, ok) numpy host view of an integer column — the
        ingest-time mirror when present (Column.host), else one device
        read each.  ``ok`` folds in row validity.  None when the column
        has no host-plannable integer representation; host plan builders
        (count pushdown, ring var-expand) key off this."""
        if self._local is not None:
            return None
        c = self._cols.get(col)
        if c is None or c.kind not in ("id", "int"):
            return None
        d, v = c.host_arrays()
        # _exact_n, not _n: under generic replay the served bound covers
        # dead-gap rows whose gathered values LOOK valid — a host plan
        # builder (ring var-expand seeds) must never see them.  The sync
        # this costs is already a host materialization site.
        return d, v & (np.arange(c.capacity) < self._exact_n())

    def device_column(self, col: str):
        """(data, valid, live_row_count) without host materialization —
        the async result surface: callers can keep results on device and
        batch their transfers (each device→host read is a full transport
        round trip).  live_row_count is a host int in eager mode but a
        DEVICE scalar for a table produced under generic fused replay
        (where the host only knows an upper bound) — callers must treat
        it as array-like and fold it into their batched transfer."""
        if self._local is not None:
            raise UnsupportedOnDevice("table is in host-fallback mode")
        c = self._cols[col]
        return c.data, c.valid, (self._live if self._live is not None
                                 else self._n)


@jax.jit
def _gather_tree(arrays, idx):
    """One fused dispatch for a whole-table gather: every per-column
    row-gather rides a single XLA executable instead of 2-3 dispatches per
    column (a launch costs the host ~0.2 ms, PERF.md §6)."""
    return jax.tree_util.tree_map(lambda a: a[idx], arrays)


def _gather_cols(cols: Dict[str, Column], idx: jnp.ndarray
                 ) -> Dict[str, Column]:
    arrays = {}
    for c, col in cols.items():
        arrays[c] = ((col.data, col.valid, col.lens) if col.kind == "list"
                     else (col.data, col.valid))
    gathered = _gather_tree(arrays, idx)
    out = {}
    for c, col in cols.items():
        g = gathered[c]
        if col.kind == "list":
            out[c] = Column(col.kind, g[0], g[1], col.ctype, g[2])
        else:
            out[c] = Column(col.kind, g[0], g[1], col.ctype)
    return out


def _concat_columns(a: Column, n_a: int, b: Column, n_b: int, out_cap: int,
                    ctype: CypherType) -> Column:
    if a.kind == "list":
        la = a.data.shape[1]
        lb = b.data.shape[1]
        width = max(la, lb)
        da = jnp.pad(a.data[:n_a], ((0, 0), (0, width - la)))
        db = jnp.pad(b.data[:n_b], ((0, 0), (0, width - lb)))
        data = jnp.concatenate([da, db], axis=0)
        data = jnp.pad(data, ((0, out_cap - n_a - n_b), (0, 0)))
        lens = jnp.concatenate([a.lens[:n_a], b.lens[:n_b]])
        lens = jnp.pad(lens, (0, out_cap - n_a - n_b))
        valid = jnp.concatenate([a.valid[:n_a], b.valid[:n_b]])
        valid = jnp.pad(valid, (0, out_cap - n_a - n_b))
        return Column("list", data, valid, ctype, lens)
    data = jnp.concatenate([a.data[:n_a], b.data[:n_b]])
    data = jnp.pad(data, (0, out_cap - n_a - n_b))
    valid = jnp.concatenate([a.valid[:n_a], b.valid[:n_b]])
    valid = jnp.pad(valid, (0, out_cap - n_a - n_b))
    return Column(a.kind, data, valid, ctype)


def _sort_keys(col: Column, ascending: bool, nulls_last: bool,
               pool) -> List[jnp.ndarray]:
    """Transform one column into (null_key, data_key) int64/float64 arrays
    for an ascending lexicographic sort."""
    if col.kind == "list":
        raise UnsupportedOnDevice("sorting by list column")
    null_key = (~col.valid).astype(jnp.int64)
    if not nulls_last:
        null_key = -null_key
    if col.kind == "str":
        rank = jnp.asarray(pool.rank_array())
        if rank.shape[0] == 0:
            data = col.data.astype(jnp.int64)
        else:
            data = rank[jnp.clip(col.data, 0, rank.shape[0] - 1)].astype(jnp.int64)
    elif col.kind == "bool":
        data = col.data.astype(jnp.int64)
    elif col.kind == "float":
        data = col.data
    else:
        data = col.data.astype(jnp.int64)
    if not ascending:
        data = -data
    # nulls must not influence the data key
    data = jnp.where(col.valid, data, 0)
    return [null_key, data]


class DeviceTableFactory(TableFactory):
    def __init__(self, backend: DeviceBackend):
        self.backend = backend
        self._local = LocalTableFactory()

    def prepare_rel_table(self, rel_table) -> None:
        """Ingest-time physical layout: build HBM-resident CSR adjacency
        over the relationship table's source and target columns (C++
        csr_build on the host when available, one numpy sort otherwise).
        Every later Expand hop against this table probes ``indptr``
        instead of sorting + binary-searching the edge list."""
        m = rel_table.mapping
        self._attach_csr(rel_table.table, (m.source_col, m.target_col))

    def prepare_node_table(self, node_table) -> None:
        """The same index over a node table's id column: the join that
        closes a hop on ``(b)`` probes it instead of binary-searching the
        sorted ids.  Not on a mesh, where that join is the one
        ``DeviceTable._dist_join`` schedules by hand (it is skipped when
        an index is found)."""
        if self.backend.mesh is None:
            self._attach_csr(node_table.table, (node_table.mapping.id_col,))

    def _attach_csr(self, t, names) -> None:
        """Hang a ``DeviceCSR`` on each named id/int ``Column`` of a
        resident device table, once, as ``col._csr = ((n,), csr)``: the
        form ``DeviceTable._csr_for`` reads.  ``csr`` is None where
        ``build_csr`` refuses the key domain (negative or too sparse):
        joins against that column keep the search."""
        if not self.backend.config.use_csr:
            return
        if not isinstance(t, DeviceTable) or t.is_local:
            return
        for name in names:
            col = t._cols.get(name)
            if col is None or col.kind not in ("id", "int"):
                continue
            if getattr(col, "_csr", None) is not None:
                continue
            csr = OPS.build_csr(col.data, col.valid & t.row_ok, t._n)
            col._csr = ((t._n,), csr)

    def from_columns(self, data: Mapping[str, Sequence[Any]],
                     types: Mapping[str, CypherType]) -> DeviceTable:
        n = len(next(iter(data.values()))) if data else 0
        cap = self.backend.bucket(n)
        cols: Dict[str, Column] = {}
        # Failure containment: a mid-ingest device failure (OOM during
        # placement, a flaky transport) must not leave the strings this
        # ingest interned behind — pool growth is the fused executor's
        # replayability fence, and leaked growth from a FAILED ingest
        # would silently invalidate every recorded size stream.
        pool_mark = self.backend.pool.mark()
        try:
            for c, values in data.items():
                ctype = types[c]
                if kind_for(ctype) == "object":
                    # host-table fallback: the local table stores raw
                    # python values, so codes interned for the discarded
                    # device columns roll back too (same fence argument)
                    self.backend.pool.rollback(pool_mark)
                    local = self._local.from_columns(data, types)
                    return DeviceTable(self.backend, local=local)
                try:
                    col = make_column(list(values), ctype, cap,
                                      self.backend.pool)
                except ValueError:
                    # values the device encoding rejects (int32-overflowing
                    # list elements, null-in-list, oversized ids): host table
                    self.backend.pool.rollback(pool_mark)
                    local = self._local.from_columns(data, types)
                    return DeviceTable(self.backend, local=local)
                cols[c] = self.backend.place_column(col)
        except Exception:
            self.backend.pool.rollback(pool_mark)
            raise
        return DeviceTable(self.backend, cols, n)

    def unit(self) -> DeviceTable:
        return DeviceTable(self.backend, {}, 1)

    def empty(self, cols: Sequence[str],
              types: Mapping[str, CypherType]) -> DeviceTable:
        out: Dict[str, Column] = {}
        cap = self.backend.bucket(0)
        for c in cols:
            ctype = types.get(c, CTInteger)
            if kind_for(ctype) == "object":
                local = self._local.empty(cols, types)
                return DeviceTable(self.backend, local=local)
            out[c] = make_column([], ctype, cap, self.backend.pool)
        return DeviceTable(self.backend, out, 0)

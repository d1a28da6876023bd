"""Engine configuration and debug flags.

The reference used a small homegrown flag registry backed by JVM system
properties (PrintTimings/PrintIr/PrintLogicalPlan/PrintRelationalPlan/...)
plus the SparkConf passed to the session builder (ref:
okapi-api/.../okapi/impl/configuration/ — reconstructed, mount empty;
SURVEY.md §5.6).  Here: one frozen dataclass with env-var overrides.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v is not None else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v is not None else default


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    # Debug printing (the reference's PrintIr / PrintLogicalPlan / ... flags)
    print_timings: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PRINT_TIMINGS", False))
    print_ir: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PRINT_IR", False))
    print_logical_plan: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PRINT_LOGICAL", False))
    print_relational_plan: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PRINT_RELATIONAL", False))

    # Device backend tuning
    # Row-count buckets: device tables are padded up to the next bucket so
    # query programs compile once per (plan, bucket) key.
    bucket_sizes: Tuple[int, ...] = (256, 1024, 4096, 16384, 65536, 262144, 1048576)
    # Mesh shape for sharded execution; () = single device.
    mesh_shape: Tuple[int, ...] = ()
    mesh_axis: str = "shard"
    # Kernel switches (pallas kernels fall back to jnp when off)
    use_pallas: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_USE_PALLAS", True))
    # HBM-resident CSR index over an ingested table's id columns (a
    # relationship table's source and target, a node table's id) as the
    # scan's physical layout (ops/expand.py DeviceCSR); joins against it
    # probe indptr instead of sorting + binary-searching the table.
    use_csr: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_USE_CSR", True))
    # Aggregate pushdown (relational/count_pattern.py): lower count-only
    # pattern chains to SpMV over the adjacency instead of join+count.
    use_count_pushdown: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_COUNT_PUSHDOWN", True))
    # Worst-case-optimal multiway joins (relational/wcoj.py, ROADMAP
    # item 4): detected cyclic MATCH segments (chain + closing edges)
    # substitute a leapfrog-style multiway intersection over sorted
    # edge keys for the binary join cascade — enumeration AND counting.
    # Cost-selected when the model is on; off = the cascade everywhere
    # (the reference side of tests/test_wcoj.py's parity checks).
    use_wcoj: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_WCOJ", True))
    # Cost-based planning (relational/cost.py + relational/stats.py,
    # ROADMAP item 3): ingest-time cardinality/degree/skew sketches seed
    # a tensor-path cost model that (a) re-roots Expand chains at their
    # cheaper end (logical/optimizer.py), (b) chooses count-pushdown vs
    # cascade and the sharded distribution strategy, and (c) stamps
    # per-operator row estimates so opstats.divergences measures MODEL
    # error and a diverging cached family re-plans itself.  Off = the
    # fixed heuristics (the reference side of tests/test_cost.py).
    use_cost_model: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_COST_MODEL", True))
    # Divergence-triggered re-planning: model-divergent executions per
    # plan family before its cached plan retires through the quarantine
    # path and re-plans with calibrated statistics.  0 disables.
    replan_threshold: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_REPLAN_THRESHOLD", 2))
    # Distributed joins (parallel/dist_join.py, SURVEY.md §5.8): on a 1-D
    # mesh, large-large joins ride an all_to_all radix exchange (each row
    # crosses ICI once) and small build sides an explicit all_gather
    # broadcast join.  Build sides at or under this many rows broadcast
    # instead of exchanging (Spark's autoBroadcastJoinThreshold analog,
    # in rows).
    # With the cost model on this is a model INPUT — the broadcast
    # prior — not a hard cutover (relational/cost.py
    # choose_dist_strategy); <= 0 disables broadcasting either way.
    broadcast_join_threshold: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_BROADCAST_ROWS", 4096))
    # Skew salting for the radix exchange (surgical: ONLY detected-hot
    # keys replicate).  join_salt > 1 forces that salt factor; 1 = pick
    # automatically from the probe-key sample (salt stays 1 when no key
    # exceeds join_hot_factor x the per-device fair share).
    join_salt: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_JOIN_SALT", 1))
    # A sampled key is "hot" when its frequency exceeds this multiple of
    # the per-device fair share (SURVEY.md §5.8 skew handling).
    join_hot_factor: float = dataclasses.field(
        default_factory=lambda: _env_float("CAPS_TPU_JOIN_HOT_FACTOR", 4.0))
    # At most this many hot keys ride the device-resident hot set.
    join_hot_capacity: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_JOIN_HOT_CAP", 16))
    # Fused executor (backends/tpu/fused.py): record data-dependent sizes
    # on a query's first run, replay them sync-free on repeats.
    use_fused: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_USE_FUSED", True))
    # Compile-cache capacity (query programs keyed by plan+bucket shapes)
    compile_cache_size: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_COMPILE_CACHE", 512))
    # Prepared-statement plan cache (relational/plan_cache.py): repeated
    # parameterized queries skip parse/IR/logical/relational planning
    # entirely on a hit — the last un-amortized scalar hot path in the
    # pipelined serving mode.  Keys are value-independent (query text +
    # graph + catalog fingerprint + parameter signature).
    use_plan_cache: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PLAN_CACHE", True))
    # Max cached plans per session (LRU evicted beyond this).
    plan_cache_size: int = dataclasses.field(
        default_factory=lambda: _env_int("CAPS_TPU_PLAN_CACHE_SIZE", 256))
    # Debug assertion hook for the generic-replay __obj__ invariant
    # (backends/tpu/fused.py): an obj served under generic replay that no
    # downstream relation-checked consume guards raises at query end.
    debug_obj_guard: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_DEBUG_OBJ_GUARD", False))
    # Determinism check (SURVEY.md §5.2): run each query twice and compare
    # result digests; raises NondeterministicResultError on mismatch.
    determinism_check: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_DETERMINISM_CHECK", False))
    # Observability (caps_tpu/obs/): ambient tracing for EVERY query.
    # Off by default — the disabled tracer costs one attribute check per
    # instrumented site (<5% overhead budget); PROFILE force-enables it
    # for its one query regardless of this flag.
    trace: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_TRACE", False))
    # PROFILE granularity: sync the device after each operator so per-op
    # spans carry real device time (post-block_until_ready deltas).  Off,
    # the dispatch stream stays async (what steady-state fused replay
    # actually runs) and the TPU session reports device time as ONE
    # per-replay aggregate span — per-op numbers are then host dispatch
    # times and are labeled as such, never silently wrong (docs/tpu.md).
    profile_sync_each_op: bool = dataclasses.field(
        default_factory=lambda: _env_bool("CAPS_TPU_PROFILE_SYNC", True))

    def bucket_for(self, n: int) -> int:
        for b in self.bucket_sizes:
            if n <= b:
                return b
        # Beyond the largest bucket: round up to the next power of two.
        b = self.bucket_sizes[-1]
        while b < n:
            b *= 2
        return b


DEFAULT_CONFIG = EngineConfig()

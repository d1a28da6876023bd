"""Benchmark: 2-hop friend-of-friend MATCH (config 1, scaled) on the TPU
backend, end-to-end through the full engine pipeline.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

metric: edges-joined/sec through the two expand joins of
    MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WHERE a.name = $seed
    RETURN count(*)
value: median over warm iterations (planning + device execution).
vs_baseline: speedup over the in-repo pure-Python oracle backend on the
    same query (the reference publishes no numbers — BASELINE.md — so the
    oracle is the only measurable baseline; it is measured on a subsample
    and scaled per-edge).

Platform: one process, which owns the chip.  Without a TPU the run exits
non-zero — unless the caller set ``JAX_PLATFORMS=cpu`` themselves, and
then the JSON line says ``"platform": "cpu"`` and carries no device
metric (``achieved_gbps``/``hbm_frac``).  Nothing here chooses a platform.

Capture robustness:
  * a SIGALRM deadline (BENCH_DEADLINE_S, default 280 s) plus an atexit
    hook print the best-so-far JSON line even if iterations overrun or
    the process is about to be killed — partial results carry an honest
    metric label and the process exits non-zero;
  * compile time (first run) is reported separately from steady-state in
    the extra "compile_s" field, per BASELINE.md's protocol.

Modes: ``python bench.py``           config 1 (2-hop foaf)
       ``python bench.py triangle``  config 4 (RMAT triangle count)
       ``python bench.py ldbc``      configs 2-3 (LDBC IS/IC p50/p95)
       ``python bench.py serve``     config 5 (QueryServer load: closed-
                                     and open-loop, latency percentiles,
                                     batch and shed behavior)
       ``python bench.py serve --cache``
                                     config 11 (snapshot-keyed result
                                     caching: Zipf-skewed repeated-read
                                     soak cache-on vs cache-off, digest
                                     parity, zero stale reads under
                                     concurrent writes, budget bound)
       ``python bench.py serve --devices N``
                                     config 7 (device fault domains:
                                     serve QPS scaling 1 -> N replica
                                     devices, then availability with one
                                     device killed mid-run)
       ``python bench.py faults``    config 6 (serve under injected
                                     transient faults: availability,
                                     retry overhead, breaker behavior)
       ``python bench.py updates``   config 8 (live updates: 8-client
                                     mixed read/write soak under ~20%
                                     injected write aborts — availability,
                                     reader digest stability, compaction
                                     backlog; --write-fraction F)
       ``python bench.py cyclic``    config 10 (cyclic patterns:
                                     triangle/diamond/4-cycle enumeration
                                     + counting, WCOJ multiway join vs
                                     the forced binary cascade across a
                                     density sweep + an LDBC-shaped
                                     skewed graph — digest-exact parity,
                                     growth-with-density curves)
"""
from __future__ import annotations

import atexit
import json
import os
import signal
import statistics
import subprocess
import sys
import threading
import time

_T0 = time.time()
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S", "280"))

# Best-so-far result; the deadline handler / atexit hook prints this if the
# normal path doesn't get there first.
_result = {
    "metric": "2-hop foaf MATCH (no measurement completed)",
    "value": 0.0,
    "unit": "edges/s",
    "vs_baseline": 0.0,
}
_printed = False
_emit_lock = threading.Lock()


def _emit():
    global _printed
    # the whole check-mutate-print must hold the lock: the watchdog
    # mutates _result["metric"] before calling here, and a snapshot
    # printed outside the lock could carry its label onto a completed run
    with _emit_lock:
        if _printed:
            return
        _printed = True
        print(json.dumps(_result), flush=True)


def _remaining() -> float:
    return DEADLINE_S - (time.time() - _T0)


def _on_alarm(signum, frame):
    # Signal handlers run ON the interrupted thread: if that frame is
    # inside _emit holding the (non-reentrant) lock, blocking here would
    # deadlock and mutating the metric would mislabel the completed run.
    # Non-blocking acquire: on failure the interrupted print is already
    # in progress — return and let it finish.
    global _printed
    if not _emit_lock.acquire(blocking=False):
        return
    partial = not _printed
    try:
        if partial:
            _printed = True
            tag = ("deadline hit"
                   if signum == getattr(signal, "SIGALRM", None)
                   else "terminated")
            _result["metric"] += f" [{tag}; partial]"
            print(json.dumps(_result), flush=True)
    finally:
        _emit_lock.release()
    os._exit(1 if partial else 0)  # a run that did not finish fails


def _install_guards():
    atexit.register(_emit)
    try:
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(max(1, int(DEADLINE_S)))
        signal.signal(signal.SIGTERM, _on_alarm)
    except (ValueError, AttributeError):
        pass  # non-main thread / platform without signals
    # Last-resort watchdog: SIGALRM only fires between bytecodes, so a
    # main thread blocked inside a device call that never returns
    # (block_until_ready is not interruptible) would never emit.
    # A daemon thread still runs then (device waits release the GIL) and
    # force-prints the best-so-far result before killing the process.
    def _watchdog():
        global _printed
        time.sleep(DEADLINE_S + 20)
        # label-mutate and print under ONE lock hold, or a completed run
        # emitting concurrently could pick up the partial label
        with _emit_lock:
            partial = not _printed
            if partial:
                _printed = True
                # cannot distinguish a hung device call from a merely-
                # slow run from here — label it as the deadline it is
                _result["metric"] += " [watchdog deadline; partial]"
                print(json.dumps(_result), flush=True)
        os._exit(1 if partial else 0)

    threading.Thread(target=_watchdog, daemon=True).start()


# Peak HBM bandwidth by ``device_kind`` (GB/s).  Source: Google Cloud
# documentation, "TPU v5e" (819 GB/s per chip).  A kind that is not here
# is an error, not a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def _platform() -> str:
    """The platform this run measures, from the one process that runs
    it.  No TPU → exit non-zero, unless the caller themselves asked for
    the CPU with ``JAX_PLATFORMS=cpu`` (a smoke run, labeled as such).
    A CPU run gets virtual devices (same trick as tests/conftest.py) so
    meshed paths — ``serve --shards N`` — exercise the real shard_map
    programs."""
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if asked_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu" and not asked_cpu:
        sys.exit(f"bench: no TPU (jax.devices()[0] is {device.platform}); "
                 f"set JAX_PLATFORMS=cpu yourself for a CPU smoke run")
    _result["platform"] = device.platform
    _result["device_kind"] = device.device_kind
    _result["device_count"] = len(jax.devices())
    return device.platform


from caps_tpu.datasets.foaf import (  # noqa: E402
    PARAM_QUERY, QUERY, build_graph, expected_paths,
)


def run_query(graph):
    return graph.cypher(QUERY).records.to_maps()[0]["c"]


def measure_rtt_floor() -> float:
    """Flat device→host round-trip cost (seconds): on remote transports
    every result read pays this regardless of payload, so it is the hard
    floor of per-query latency and is reported separately."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    f = jax.jit(lambda v: (v + 1).sum())
    x = jnp.ones((1024,), jnp.int32)
    np.asarray(f(x))  # warm compile + first transfer
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def run_pipelined(graph, expected: int, batch: int) -> float:
    """Throughput mode: dispatch ``batch`` full queries (each one runs
    parse→plan→device execution), keep every result on device, and read
    them back in ONE transfer.  Returns seconds per query.  This is the
    honest pipelined number a latency-bound transport allows: all device
    work is real and verified, only result delivery is batched."""
    import jax.numpy as jnp
    import numpy as np
    from caps_tpu.ir import exprs as E
    outs = []
    t0 = time.perf_counter()
    for _ in range(batch):
        rec = graph.cypher(QUERY).records
        data, _valid, n = rec.table.device_column(
            rec.header.column(E.Var("c")))
        outs.append(data[0])
    counts = np.asarray(jnp.stack(outs))
    elapsed = time.perf_counter() - t0
    assert (counts == expected).all(), (counts, expected)
    return elapsed / batch


def run_prepared_pipelined(session, graph, seeds, expected, batch: int):
    """Prepared/repeat-query mode: ONE PreparedQuery, rotating $seed
    bindings, results kept on device and read back in one transfer (same
    protocol as run_pipelined so the numbers compare).

    Measures the SAME varying-$seed workload twice after a shared warmup
    (which converges the plan cache AND the fused executor's
    param-generic size stream over every seed): once with the plan cache
    disabled — per-query planning un-amortized — and once through the
    cache.  The delta isolates the planning amortization.  Returns
    (cached seconds/query, uncached seconds/query, info dict).

    Cache/planning counters come from ``session.metrics_snapshot()``
    diffs (caps_tpu/obs/) — the bench no longer hand-rolls its own
    before/after counter plumbing."""
    import jax.numpy as jnp
    import numpy as np
    from caps_tpu.ir import exprs as E
    from caps_tpu.obs import diff_snapshots
    prep = session.prepare(PARAM_QUERY, graph=graph)
    snap0 = session.metrics_snapshot()
    for s in seeds:
        # warmup: 1 plan-cache miss total, and one fused recording per
        # seed value (the generic stream's caps widen to the max)
        assert prep.run({"seed": s}).records.to_maps()[0]["c"] == expected[s]

    def one_phase(n):
        outs, want = [], []
        t0 = time.perf_counter()
        for i in range(n):
            seed = seeds[i % len(seeds)]
            rec = prep.run({"seed": seed}).records
            data, _valid, _n = rec.table.device_column(
                rec.header.column(E.Var("c")))
            outs.append(data[0])
            want.append(expected[seed])
        counts = np.asarray(jnp.stack(outs))
        elapsed = time.perf_counter() - t0
        assert (counts == np.asarray(want)).all(), (counts, want)
        return elapsed / n

    session.plan_cache.enabled = False
    try:
        uncached_s = one_phase(batch)
    finally:
        session.plan_cache.enabled = True
    prep_s = one_phase(batch)
    delta = diff_snapshots(snap0, session.metrics_snapshot())
    hits = delta["plan_cache.hits"]
    misses = delta["plan_cache.misses"]
    saved = delta["plan_cache.saved_s"]
    attempts = hits + misses
    cold_s = saved / hits if hits else 0.0  # one cold plan's frontend cost
    info = {
        "plan_cache_hit_rate": round(hits / attempts, 4) if attempts else 0.0,
        # planning seconds actually paid through the cache, amortized
        "plan_s_amortized": round(cold_s * misses / attempts, 6)
        if attempts else 0.0,
        "plan_cache_saved_s": round(saved, 4),
        # sync-free replays over the measured interval, same snapshot
        "fused_generic_replays": delta.get("fused.generic_replays", 0),
    }
    return prep_s, uncached_s, info


def time_fn(run, iters: int, min_time_left: float = 5.0):
    """Median over up to ``iters`` runs, stopping early if the deadline is
    near.  Returns (median_s, completed_iters)."""
    times = []
    for _ in range(iters):
        if times and _remaining() < min_time_left:
            break
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), len(times)


def edges_joined(src, dst, names) -> int:
    """Edges processed by the two expand joins: each hop probes the full
    relationship table (TEPS-style traversed-edges metric), plus the rows
    the joins emit."""
    import numpy as np
    n_edges = len(src)
    is_seed = np.array([names[s] == "Alice" for s in src])
    hop1_out = int(is_seed.sum())
    cnt1 = np.bincount(dst[is_seed], minlength=len(names))
    hop2_out = int(cnt1[src].sum())
    return 2 * n_edges + hop1_out + hop2_out


def run_triangle_config(on_tpu: bool):
    """Benchmark config 4 (BASELINE.md): triangle count on an RMAT edge
    list via the cyclic multiway-join path.  Selected with
    ``python bench.py triangle [scale]``."""
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.datasets.graph500 import (
        TRIANGLE_QUERY, count_triangles_reference, triangle_graph,
    )
    scale = int(sys.argv[2]) if len(sys.argv) > 2 else (14 if on_tpu else 12)
    _result["metric"] = (f"edges-joined/sec, triangle RMAT scale-{scale} "
                         "(no measurement completed)")
    session = TPUCypherSession()
    graph, lo, hi = triangle_graph(session, scale=scale, edgefactor=8)
    run = lambda: graph.cypher(TRIANGLE_QUERY).records.to_maps()[0]["triangles"]
    t0 = time.perf_counter()
    got = run()  # warms the compile caches
    compile_s = time.perf_counter() - t0
    _result.update({
        "metric": f"edges-joined/sec, triangle RMAT scale-{scale} "
                  f"(compile only, {'tpu' if on_tpu else 'cpu'})",
        "value": round(3 * len(lo) / compile_s, 1),
        "compile_s": round(compile_s, 2),
    })
    med, iters = time_fn(run, iters=5)
    if scale <= 12:
        assert got == count_triangles_reference(lo, hi)
    value = 3 * len(lo) / med
    _result.update({
        "metric": f"edges-joined/sec, triangle count RMAT scale-{scale} "
                  f"ef8 ({len(lo)} edges, triangles={got}, iters={iters}, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": round(value, 1),
        "unit": "edges/s",
        "vs_baseline": 0.0,
    })
    _emit()


def run_ldbc_config(on_tpu: bool):
    """Benchmark configs 2-3 (BASELINE.md): LDBC short reads IS1-IS7 and
    complex reads IC1-IC14 with per-query p50/p95 over warm iterations."""
    _result["metric"] = "LDBC IS/IC suite (no measurement completed)"
    try:
        from caps_tpu.datasets.ldbc import run_ldbc_bench
    except ImportError as ex:
        _result["metric"] = f"LDBC IS/IC suite (unavailable: {ex})"
        _emit()
        return
    scale = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0
    # result_sink=_result: every completed query lands in the best-so-far
    # dict, so a deadline abort emits honest partial results.
    report = run_ldbc_bench(scale=scale, on_tpu=on_tpu,
                            remaining_s=_remaining, result_sink=_result)
    _result.update(report)
    _emit()


def _percentiles(samples):
    if not samples:
        return {}
    xs = sorted(samples)
    pick = lambda q: xs[min(len(xs) - 1, int(q * len(xs)))]
    return {"p50_s": round(pick(0.50), 5), "p95_s": round(pick(0.95), 5),
            "p99_s": round(pick(0.99), 5)}


def run_serve_config(on_tpu: bool):
    """Benchmark config 5: the serving tier (caps_tpu/serve/) under load.

    One prepared parameterized query, rotating $seed bindings:

    * closed loop — C client threads, each submit→wait→repeat: the
      sustainable throughput number (``value``, queries/s) plus
      p50/p95/p99 client latency;
    * open loop — Poisson arrivals at ~2x the closed-loop rate against
      a small queue: queue depth, micro-batch coalescing, and the
      admission controller's shed rate under genuine overload.

    vs_baseline = served throughput over single-threaded sequential
    ``PreparedQuery.run`` on the same session (the pre-serving path).
    """
    import re as _re
    import threading as _th
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.obs import diff_snapshots
    from caps_tpu.obs.telemetry import SLOConfig
    from caps_tpu.serve import Overloaded, QueryServer, ServerConfig

    _result.update({"metric": "serve QPS (no measurement completed)",
                    "unit": "queries/s"})
    rng = np.random.RandomState(42)
    if on_tpu:
        n_people, n_edges, n_seeds = 100_000, 500_000, 20
    else:
        n_people, n_edges, n_seeds = 10_000, 50_000, 10
    n_people = int(os.environ.get("BENCH_N_PEOPLE", n_people))
    n_edges = int(os.environ.get("BENCH_N_EDGES", n_edges))
    session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(session, n_people, n_edges,
                                         n_seeds, rng)
    seen, seeds = set(), []
    for nm in names:
        if nm not in seen:
            seen.add(nm)
            seeds.append(nm)
        if len(seeds) == 4:
            break
    if "Alice" not in seeds:
        seeds[0] = "Alice"
    exp = expected_paths(src, dst, names, seeds)
    prep = session.prepare(PARAM_QUERY, graph=graph)
    t0 = time.perf_counter()
    for s_ in seeds:  # warm: plan cache + fused recordings per seed
        assert prep.run({"seed": s_}).records.to_maps()[0]["c"] == exp[s_]
    compile_s = time.perf_counter() - t0
    _result["compile_s"] = round(compile_s, 2)

    # Sequential baseline: single caller, prepared path (what serving
    # replaces).  Small count — it only anchors vs_baseline.
    seq_n = 30
    t0 = time.perf_counter()
    for j in range(seq_n):
        seed = seeds[j % len(seeds)]
        rows = prep.run({"seed": seed}).records.to_maps()
        assert rows[0]["c"] == exp[seed]
    seq_qps = seq_n / (time.perf_counter() - t0)

    # -- closed loop ---------------------------------------------------
    positional = [a for a in sys.argv[2:] if not a.startswith("--")]
    clients = int(positional[0]) if positional else 8
    per_client = int(os.environ.get("BENCH_SERVE_REQS", "40"))
    server = QueryServer(session, graph=graph, config=ServerConfig(
        workers=2, max_queue=256, max_batch=16, batch_window_s=0.001,
        slo=SLOConfig(latency_target_s=1.0, latency_objective=0.95,
                      availability_objective=0.99),
        # capture everything: the bench proves the slow-query ledger
        # pipeline end to end (ISSUE 10 acceptance)
        slow_query_threshold_s=0.0))
    latencies, errors = [], []

    def client(i):
        try:
            for j in range(per_client):
                seed = seeds[(i + j) % len(seeds)]
                h = server.submit(PARAM_QUERY, {"seed": seed})
                rows = h.rows()
                assert rows[0]["c"] == exp[seed]
                latencies.append(h.info["latency_s"])
        except Exception as ex:  # surfaced in the metric label
            errors.append(repr(ex))

    snap0 = session.metrics_snapshot()
    threads = [_th.Thread(target=client, args=(i,)) for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    closed_s = time.perf_counter() - t0
    closed = diff_snapshots(snap0, session.metrics_snapshot())
    closed_qps = len(latencies) / closed_s if closed_s else 0.0
    _result.update({
        "metric": f"serve QPS, closed-loop {clients} clients x "
                  f"{per_client} reqs, 2-hop foaf $seed "
                  f"({n_people} nodes, {n_edges} edges, "
                  f"{'tpu' if on_tpu else 'cpu'}"
                  + (f", errors={len(errors)}" if errors else "") + ")",
        "value": round(closed_qps, 1),
        "vs_baseline": round(closed_qps / seq_qps, 3) if seq_qps else 0.0,
        "sequential_qps": round(seq_qps, 1),
        "closed_loop_batch_mean": round(
            closed.get("serve.batch_size.sum", 0)
            / max(1, closed.get("serve.batch_size.count", 1)), 3),
        "closed_loop_batch_max": closed.get("serve.batch_size.max", 0),
        **_percentiles(latencies),
    })
    # windowed telemetry + SLO burn rate, SERVER-side (obs/telemetry.py)
    # — not recomputed from the client-side latency list above
    report = server.health_report()
    win, slo = report["window"], report["slo"]
    _result.update({
        "telemetry_window_s": win["window_s"],
        "telemetry_qps": win["qps"],
        "telemetry_p50_s": win["latency"]["p50_s"],
        "telemetry_p95_s": win["latency"]["p95_s"],
        "telemetry_p99_s": win["latency"]["p99_s"],
        "telemetry_queue_wait_p95_s": win["queue_wait"]["p95_s"],
        "telemetry_batch_occupancy": round(win["batch_occupancy"], 3),
        "slo_latency_compliance": slo["latency_compliance"],
        "slo_latency_burn_rate": slo["latency_burn_rate"],
        "slo_availability": slo["availability"],
        "slo_availability_burn_rate": slo["availability_burn_rate"],
        "slo_within_budget": slo["within_budget"],
        "batching": server.stats()["batching"],
    })

    # -- open loop: Poisson arrivals over capacity ---------------------
    if _remaining() > 15:
        small = QueryServer(session, graph=graph, config=ServerConfig(
            workers=2, max_queue=32, max_batch=16, batch_window_s=0.001))
        rate = max(50.0, 2.0 * closed_qps)
        duration = min(3.0, max(1.0, _remaining() - 10))
        handles, shed, depth_samples = [], 0, []
        snap1 = session.metrics_snapshot()
        t0 = time.perf_counter()
        next_t = t0
        k = 0
        while time.perf_counter() - t0 < duration:
            next_t += rng.exponential(1.0 / rate)
            lag = next_t - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            try:
                handles.append(small.submit(
                    PARAM_QUERY, {"seed": seeds[k % len(seeds)]}))
            except Overloaded:
                shed += 1
            k += 1
            if k % 8 == 0:
                depth_samples.append(small.admission.depth())
        for h in handles:
            h.wait(timeout=30)
        small.shutdown()
        open_delta = diff_snapshots(snap1, session.metrics_snapshot())
        total = len(handles) + shed
        _result.update({
            "open_loop_rate_qps": round(rate, 1),
            "open_loop_shed_rate": round(shed / total, 4) if total else 0.0,
            "open_loop_queue_depth_mean": round(
                sum(depth_samples) / len(depth_samples), 2)
            if depth_samples else 0.0,
            "open_loop_queue_depth_max": max(depth_samples, default=0),
            # histogram sum/count ARE interval-diffable (a running max
            # is not), so the open loop's coalescing reports as a mean
            "open_loop_batch_mean": round(
                open_delta.get("serve.batch_size.sum", 0)
                / max(1, open_delta.get("serve.batch_size.count", 1)), 3),
            "open_loop_completed": open_delta.get("serve.completed", 0),
        })

    # -- flight recorder: 8-client soak with an injected breaker trip --
    if _remaining() > 12:
        from caps_tpu.testing.faults import failing_operator
        poison_q = ("MATCH (p:Person) WHERE p.age > $min "
                    "RETURN p.name AS n ORDER BY n LIMIT 3")

        def soak_client(i):
            for j in range(6):
                try:
                    if (i + j) % 2:
                        server.run(poison_q, {"min": j})
                    else:
                        server.run(PARAM_QUERY,
                                   {"seed": seeds[j % len(seeds)]})
                except Exception:
                    pass  # failures are the point of this phase

        with failing_operator("OrderBy", exc=RuntimeError("bench poison"),
                              n_times=None):
            soakers = [_th.Thread(target=soak_client, args=(i,))
                       for i in range(8)]
            for t in soakers:
                t.start()
            for t in soakers:
                t.join()
        dumps = server.telemetry.flight_dumps
        failing_recs = [r for d in dumps for r in d["records"]
                        if r.get("attempts")]
        _result.update({
            "flight_dumps": len(dumps),
            "flight_dump_reasons": sorted({d["reason"] for d in dumps}),
            "flight_records_with_attempts": len(failing_recs),
            "flight_attempt_modes": sorted({a["mode"]
                                            for r in failing_recs
                                            for a in r["attempts"]}),
        })

    # -- warm path: ragged bucket batching + shape-churn soak ----------
    # (ISSUE 11 acceptance): 8 clients churn bindings WITHIN warmed
    # shape buckets across 4 DISTINCT query texts on a ragged server —
    # compile.recompiles must stay flat (~0) and distinct texts must
    # demonstrably share batches, both read from the telemetry surfaces.
    if _remaining() > 25:
        churn_qs = [
            (f"MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
             f"WHERE a.name = $seed AND b.age >= {18 + k} "
             f"RETURN count(*) AS c") for k in range(4)]
        ragged = QueryServer(session, graph=graph, config=ServerConfig(
            workers=2, max_queue=4096, max_batch=16,
            batch_window_s=0.001, ragged_batching=True))
        for q_ in churn_qs:  # warm every (text, binding) combo once
            for s_ in seeds:
                ragged.run(q_, {"seed": s_})
        snap_c = session.metrics_snapshot()
        churn_per = int(os.environ.get("BENCH_CHURN_REQS", "24"))

        def churn_client(i):
            for j in range(churn_per):
                try:
                    ragged.run(churn_qs[(i + j) % len(churn_qs)],
                               {"seed": seeds[(i * churn_per + j)
                                              % len(seeds)]})
                except Exception:
                    pass  # shed under load is fine; recompiles are not

        churners = [_th.Thread(target=churn_client, args=(i,))
                    for i in range(8)]
        for t in churners:
            t.start()
        for t in churners:
            t.join()
        churn_delta = diff_snapshots(snap_c, session.metrics_snapshot())
        churn_recompiles = churn_delta.get("compile.recompiles", 0)
        c_batches = churn_delta.get("serve.batch_size.count", 0)
        c_members = churn_delta.get("serve.batch_size.sum", 0)
        # distinct-text packing proof: a preloaded queue of alternating
        # texts must coalesce into shared batches (occupancy > 1)
        packed = QueryServer(session, graph=graph, start=False,
                             config=ServerConfig(workers=1, max_batch=16,
                                                 ragged_batching=True))
        hs = [packed.submit(churn_qs[i % len(churn_qs)],
                            {"seed": seeds[i % len(seeds)]})
              for i in range(8)]
        packed.start()
        packed.shutdown()
        distinct_max = max(h.info["batch_size"] for h in hs)
        ragged.shutdown()
        assert churn_recompiles == 0, \
            f"shape churn within buckets recompiled {churn_recompiles}x"
        assert distinct_max > 1, "distinct texts never shared a batch"
        _result.update({
            "churn_requests": 8 * churn_per,
            "churn_recompiles": churn_recompiles,
            "churn_batch_occupancy": round(c_members / c_batches, 3)
            if c_batches else 0.0,
            "ragged_distinct_text_batch_max": distinct_max,
        })

    # -- observed-statistics store + Prometheus exposition -------------
    ops_summary = session.op_stats.summary()
    families = session.op_stats.stats()
    _result.update({
        "opstats_families": ops_summary["families"],
        "opstats_operators": ops_summary["operators"],
        "opstats_divergences": ops_summary["divergences"],
        # every executed plan family holds per-operator actual rows
        "opstats_all_families_have_rows": all(
            ops and all(st["executions"] >= 1 for st in ops.values())
            for ops in families.values()),
    })
    text = server.metrics_text()
    sample_re = _re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"\})? '
        r'[0-9eE.+\-]+$')
    samples = 0
    for line in text.splitlines():
        if not line or line.startswith("# TYPE "):
            continue
        assert sample_re.match(line), f"unparseable exposition: {line!r}"
        samples += 1
    _result["expose_text_samples"] = samples

    # -- resource ledger: compile + memory + slow-query log (ISSUE 10) -
    compile_view = server.stats()["compile"]
    _result.update({
        "compile_total_s": compile_view["total_s"],
        "compile_events": compile_view["events"],
        "compile_recompiles": compile_view["recompiles"],
        # per-family compile seconds (the AOT-warmup target list)
        "compile_by_family": {fam[:60]: e["total_s"]
                              for fam, e in
                              compile_view["by_family"].items()},
    })
    assert compile_view["total_s"] > 0, "no compile charge recorded"
    mem = server.stats()["memory"]
    _result.update({
        "mem_plan_cache_bytes": mem["plan_cache_bytes"],
        "mem_string_pool_bytes": mem["string_pool_bytes"],
        "mem_graph_bytes": mem["graphs"].get("default", {}).get("bytes", 0),
        "mem_device_bytes_in_use": mem["device_bytes_in_use"],
        "mem_devices_reporting": sum(
            1 for d in mem["devices"].values() if d.get("available")),
    })
    assert mem["plan_cache_bytes"] > 0 and _result["mem_graph_bytes"] > 0
    slow = [r for r in server.slow_queries()
            if r["outcome"] == "ok" and r["ledger"]["bytes_in"] > 0]
    assert slow, "no slow-query record with a non-empty ledger captured"
    srec = slow[0]
    assert srec["ledger"]["peak_rows"] > 0 and srec.get("plan") \
        and srec.get("operators"), "slow record missing detail"
    _result.update({
        "slowlog_records": len(server.slow_queries()),
        "slowlog_sample_ledger": srec["ledger"],
        "event_log_events": sorted({e["event"] for e in server.events()}),
    })
    # warmed server: every hot family compiled on this process
    warm = server.warmup_report()
    assert warm["cold_families"] == [], warm["cold_families"]
    _result.update({
        "warmup_hot_families": warm["hot_families"],
        "warmup_cold_hot_families": len(warm["cold_families"]),
    })
    server.shutdown()

    # -- cold-process restart against the persisted plan store ---------
    # (``serve --cold-process``): persist this process's warm state,
    # re-launch a FRESH process that warms from the store, and record
    # its first-query latency / compile charge / recompiles next to the
    # warmed-server telemetry above.
    if "--cold-process" in sys.argv and _remaining() > 30:
        import tempfile
        from caps_tpu.relational.plan_store import (PlanStore,
                                                    collect_warm_state)
        store_path = os.path.join(
            tempfile.mkdtemp(prefix="caps_planstore_"), "plans.json")
        saved = PlanStore(store_path,
                          registry=session.metrics_registry).save(
            collect_warm_state(session, graph=graph))
        try:
            assert saved, "plan store save failed"
            # the child inherits JAX_PLATFORMS=cpu (main() refused this
            # mode on TPU: this process holds the chip)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "serve",
                 "--cold-child", store_path, str(n_people),
                 str(n_edges), str(n_seeds)],
                capture_output=True, text=True,
                timeout=max(20.0, _remaining() - 5))
            child = json.loads(proc.stdout.strip().splitlines()[-1])
            _result["cold_process"] = child
            _result["cold_process_compile_cut"] = round(
                1.0 - (child.get("first_query_compile_s") or 0.0)
                / max(compile_s, 1e-9), 4)
        except Exception as ex:
            _result["cold_process"] = {
                "error": f"{type(ex).__name__}: {str(ex)[:200]}"}
    _emit()


def run_cold_child(store_path: str, n_people: int, n_edges: int,
                   n_seeds: int):
    """The fresh process of ``serve --cold-process``: same graph data
    (same rng), a server that warms from the persisted plan store at
    start, then the first client queries — the numbers that prove (or
    disprove) the cold-cliff kill.  Prints ONE JSON line for the parent
    to merge."""
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.serve import QueryServer, ServerConfig, WarmupConfig

    rng = np.random.RandomState(42)
    t_proc = time.perf_counter()
    session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(session, n_people, n_edges,
                                         n_seeds, rng)
    ingest_s = time.perf_counter() - t_proc
    server = QueryServer(session, graph=graph, config=ServerConfig(
        workers=2, max_queue=256, max_batch=16, batch_window_s=0.001,
        ragged_batching=True,
        warmup=WarmupConfig(store_path=store_path, background=False,
                            save_on_shutdown=False)))
    wreport = server.warmer.report()
    # first query = the warmed binding of the canonical family (the
    # store knows which binding it recorded)
    binding, stored = {"seed": "Alice"}, []
    with open(store_path, encoding="utf-8") as f:
        for fam in json.load(f).get("families", []):
            if fam["query"] == PARAM_QUERY:
                binding = fam["params"]
                stored = fam.get("bindings") or []
                break
    exp = expected_paths(src, dst, names, [binding["seed"]])
    t0 = time.perf_counter()
    h = server.submit(PARAM_QUERY, binding)
    rows = h.rows()
    first_s = time.perf_counter() - t0
    # a SIBLING warmed binding (the store keeps the compile-charging
    # rotation) must also charge zero; an UNSEEN binding's residual
    # charge is reported separately — it is the per-value count-fused
    # closure build, the honest leftover cost
    sibling = next((b for b in stored if b != binding), binding)
    h_sib = server.submit(PARAM_QUERY, sibling)
    h_sib.rows()
    seen = {b.get("seed") for b in stored}
    other = next((nm for nm in names if nm not in seen), "Alice")
    h2 = server.submit(PARAM_QUERY, {"seed": other})
    h2.rows()
    out = {
        "store_loaded": (wreport.get("store") or {}).get("loaded"),
        "warmup_s": wreport.get("seconds"),
        "warmup_families": wreport.get("families_total"),
        "warmup_completed": wreport.get("completed"),
        "warmup_streams_seeded": wreport.get("streams_seeded"),
        "warmup_converged": wreport.get("converged"),
        "ingest_s": round(ingest_s, 3),
        "first_query_s": round(first_s, 5),
        "first_query_latency_s": round(h.info["latency_s"], 5),
        "first_query_compile_s": h.info["ledger"]["compile_s"],
        "warmed_sibling_compile_s": h_sib.info["ledger"]["compile_s"],
        "unseen_binding_compile_s": h2.info["ledger"]["compile_s"],
        "first_query_ok": rows[0]["c"] == exp[binding["seed"]],
        "recompiles": server.stats()["compile"]["recompiles"],
        "telemetry_p99_s":
            server.health_report()["window"]["latency"]["p99_s"],
    }
    server.shutdown()
    print(json.dumps(out), flush=True)


def run_serve_cache_config(on_tpu: bool):
    """Benchmark config 11: snapshot-keyed result caching
    (``serve --cache``, ISSUE 17).

    Zipf-skewed repeated-read soak (8 closed-loop clients, skew ~1.1
    over 32 distinct ``$seed`` bindings) against the SAME request
    sequence twice — once with the result cache off, once on — then a
    concurrent-writes phase on a versioned graph.  Asserted acceptance:

    * hit ratio >= 0.8 on the skewed soak;
    * p50 on cache hits >= 5x lower than the uncached p50;
    * digest-exact parity: every cached answer equals the uncached
      answer for the same binding (and the host oracle);
    * zero stale reads while a writer commits concurrently — every
      read's rows equal the serial state at its admission-time
      snapshot version, with caching ON;
    * ``rescache.bytes`` never exceeds the configured budget at any
      sampled point;
    * ``telemetry_qps`` uplift > 1x with the cache on.
    """
    import threading as _th
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.relational.result_cache import ResultCacheConfig
    from caps_tpu.relational.updates import versioned
    from caps_tpu.serve import QueryServer, ServerConfig
    from caps_tpu.serve.fleet import rows_digest
    from caps_tpu.testing.factory import create_graph

    _result.update({"metric": "result-cache hit ratio "
                              "(no measurement completed)",
                    "unit": "fraction", "value": 0.0})
    rng = np.random.RandomState(42)
    if on_tpu:
        n_people, n_edges = 50_000, 250_000
    else:
        n_people, n_edges = 8_000, 40_000
    n_people = int(os.environ.get("BENCH_N_PEOPLE", n_people))
    n_edges = int(os.environ.get("BENCH_N_EDGES", n_edges))
    session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(session, n_people, n_edges, 4,
                                         rng)

    # 32 distinct bindings; rank r drawn with p(r) ~ 1/(r+1)^1.1 — the
    # repeated-read skew the cache exists for.
    keys, seen = [], set()
    for nm in names:
        if nm not in seen:
            seen.add(nm)
            keys.append(nm)
        if len(keys) == 32:
            break
    exp = expected_paths(src, dst, names, keys)
    clients = 8
    per_client = int(os.environ.get("BENCH_CACHE_REQS", "40"))
    total = clients * per_client
    w = 1.0 / np.power(np.arange(1, len(keys) + 1), 1.1)
    ranks = rng.choice(len(keys), size=total, p=w / w.sum())
    sequence = [keys[r] for r in ranks]

    prep = session.prepare(PARAM_QUERY, graph=graph)
    for nm in keys:  # warm plan + fused caches: steady-state baseline
        assert prep.run({"seed": nm}).records.to_maps()[0]["c"] == exp[nm]

    digests, dig_lock = {}, _th.Lock()

    def soak(server, record_hits):
        latencies, hit_lat, hits, errors = [], [], [], []

        def client(i):
            try:
                for j in range(per_client):
                    seed = sequence[i * per_client + j]
                    h = server.submit(PARAM_QUERY, {"seed": seed})
                    rows = h.rows(timeout=60)
                    assert rows[0]["c"] == exp[seed], (seed, rows)
                    d = rows_digest(rows)
                    with dig_lock:
                        if seed in digests:  # parity across runs AND hits
                            assert digests[seed] == d, seed
                        else:
                            digests[seed] = d
                        latencies.append(h.info["latency_s"])
                        if h.info.get("cache") == "hit":
                            hits.append(1)
                            hit_lat.append(h.info["latency_s"])
                        if record_hits and server.result_cache is not None:
                            assert (server.result_cache.bytes
                                    <= server.result_cache.config
                                    .budget_bytes), "budget exceeded"
            except Exception as ex:
                errors.append(repr(ex))

        threads = [_th.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        qps = server.health_report()["window"]["qps"]
        return latencies, hit_lat, len(hits), errors, elapsed, qps

    # -- phase 1: cache OFF (the device-dwell baseline) ----------------
    off = QueryServer(session, graph=graph, config=ServerConfig(
        workers=2, max_queue=4096, max_batch=16, batch_window_s=0.001))
    off_lat, _hl, off_hits, off_err, off_s, off_qps = soak(off, False)
    off.shutdown()
    assert off_hits == 0 and not off_err, (off_hits, off_err[:3])

    # -- phase 2: cache ON, identical sequence -------------------------
    budget = 4 << 20
    on = QueryServer(session, graph=graph, config=ServerConfig(
        workers=2, max_queue=4096, max_batch=16, batch_window_s=0.001,
        result_cache=ResultCacheConfig(budget_bytes=budget)))
    on_lat, hit_lat, n_hits, on_err, on_s, on_qps = soak(on, True)
    rstats = on.result_cache.stats()
    assert not on_err, on_err[:3]
    hit_ratio = n_hits / total if total else 0.0
    p50_off = _percentiles(off_lat).get("p50_s", 0.0)
    p50_hit = _percentiles(hit_lat).get("p50_s", 0.0)
    assert hit_ratio >= 0.8, f"hit ratio {hit_ratio:.3f} < 0.8"
    assert p50_hit > 0 and p50_off / p50_hit >= 5.0, \
        f"hit p50 {p50_hit} not 5x under uncached p50 {p50_off}"
    assert rstats["bytes"] <= budget, rstats
    qps_uplift = on_qps / off_qps if off_qps else 0.0
    assert qps_uplift > 1.0, (on_qps, off_qps)
    on.shutdown()

    # -- phase 3: concurrent writes, zero stale reads, caching ON ------
    vg = versioned(session, create_graph(
        session, "CREATE (:Seed {k:-1, v:-1})"))
    wserver = QueryServer(session, graph=vg, config=ServerConfig(
        workers=2, max_queue=4096,
        result_cache=ResultCacheConfig(budget_bytes=budget)))
    write_log, observations, log_lock = {}, [], _th.Lock()
    n_writes = 24
    read_hits = [0]

    def writer():
        for j in range(n_writes):
            res = wserver.submit("CREATE (:Item {k:$k, v:$v})",
                                 {"k": j, "v": j * 7}).result(timeout=60)
            with log_lock:
                write_log[res.metrics["snapshot_version"]] = (j, j * 7)

    def reader(i):
        for j in range(48):
            h = wserver.submit("MATCH (n:Item) RETURN n.k AS k, "
                               "n.v AS v")
            rows = h.rows(timeout=60)
            with log_lock:
                observations.append(
                    (h.info["snapshot_version"],
                     frozenset((r["k"], r["v"]) for r in rows)))
                if h.info.get("cache") == "hit":
                    read_hits[0] += 1
            assert (wserver.result_cache.bytes
                    <= wserver.result_cache.config.budget_bytes)

    wt = _th.Thread(target=writer)
    readers = [_th.Thread(target=reader, args=(i,)) for i in range(4)]
    for t in [wt] + readers:
        t.start()
    for t in [wt] + readers:
        t.join()
    stale = 0
    for version, got in observations:
        want = frozenset(kv for v, kv in write_log.items()
                         if v <= version)
        if got != want:
            stale += 1
    wstats = wserver.result_cache.stats()
    wserver.shutdown()
    assert stale == 0, f"{stale} stale reads under concurrent writes"
    assert len(write_log) == n_writes, len(write_log)

    _result.update({
        "metric": f"result-cache hit ratio, zipf(1.1) over "
                  f"{len(keys)} bindings, {clients} clients x "
                  f"{per_client} reqs "
                  f"({'tpu' if on_tpu else 'cpu'})",
        "value": round(hit_ratio, 4),
        "unit": "fraction",
        "vs_baseline": round(p50_off / p50_hit, 1) if p50_hit else 0.0,
        "requests_per_run": total,
        "cache_hits": n_hits,
        "p50_uncached_s": p50_off,
        "p50_hit_s": p50_hit,
        "hit_speedup_p50": round(p50_off / p50_hit, 1) if p50_hit else 0.0,
        **{"off_" + k: v for k, v in _percentiles(off_lat).items()},
        **{"on_" + k: v for k, v in _percentiles(on_lat).items()},
        "telemetry_qps_off": off_qps,
        "telemetry_qps_on": on_qps,
        "telemetry_qps_uplift": round(qps_uplift, 2),
        "budget_bytes": budget,
        "rescache_bytes_final": rstats["bytes"],
        "rescache_insertions": rstats["insertions"],
        "rescache_evictions": rstats["evictions"],
        "subplan_hits": rstats["subplan_hits"],
        "write_phase_reads": len(observations),
        "write_phase_read_hits": read_hits[0],
        "write_phase_stale_reads": stale,
        "write_phase_retired": wstats["retired"],
        "digest_parity": True,
    })
    _emit()


def run_serve_devices_config(on_tpu: bool, devices_n: int):
    """Benchmark config 7: device fault domains (``serve --devices N``).

    Phase A measures closed-loop serve QPS (8 clients, prepared
    parameterized 2-hop foaf) at 1 device and at N replica devices —
    the scaling acceptance (``qps_by_devices``, ``qps_scaling``).  On
    CPU the replicas are simulated devices (distinct sessions, distinct
    compiled state — serve/devices.py); on TPU they pin to real
    ``jax.devices()``.

    Phase B re-runs the closed loop on the N-device server with one
    device KILLED mid-run (``testing.faults.device_loss``): value =
    availability — the fraction of requests resolving with correct
    rows while the dead device quarantines and work redistributes to
    the N-1 survivors.  Per-device health/quarantine counters are
    reported from ``server.stats()['devices']``.
    """
    import threading as _th
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.serve import (QueryServer, RetryPolicy, ServeError,
                                ServerConfig)
    from caps_tpu.testing.faults import device_loss

    _result.update({"metric": "serve QPS by devices "
                              "(no measurement completed)",
                    "unit": "queries/s", "value": 0.0})
    rng = np.random.RandomState(42)
    if on_tpu:
        n_people, n_edges = 200_000, 1_000_000
    else:
        n_people, n_edges = 100_000, 500_000
    n_people = int(os.environ.get("BENCH_N_PEOPLE", n_people))
    n_edges = int(os.environ.get("BENCH_N_EDGES", n_edges))
    session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(session, n_people, n_edges,
                                         10, rng)
    # FOUR distinct plan families (the b.age constant differs in the
    # query TEXT): same-family requests coalesce into one device's
    # micro-batch, so a single family would let the 1-device server
    # amortize everything into big batches and hide the parallelism —
    # a mixed-family load is what N independent dispatch streams are
    # FOR.  count(*) keeps materialization trivial; the two expand
    # joins dominate, and that device compute runs GIL-free.
    fams = [(f"MATCH (a:Person)-[:KNOWS]->(b) "
             f"WHERE a.age > $min AND b.age < {85 - k} "
             f"RETURN count(*) AS c") for k in range(4)]
    binding = {"min": 30}
    t0 = time.perf_counter()
    exp = {q: graph.cypher(q, binding).records.to_maps() for q in fams}
    _result["compile_s"] = round(time.perf_counter() - t0, 2)

    clients = 8
    per_client = int(os.environ.get("BENCH_SERVE_REQS", "12"))
    total = clients * per_client

    def closed_loop(server):
        latencies, outcomes = [], []

        def client(i):
            for j in range(per_client):
                q = fams[(i + j) % len(fams)]
                try:
                    h = server.submit(q, binding)
                    rows = h.rows(timeout=180)
                    outcomes.append("ok" if rows == exp[q] else "wrong")
                    latencies.append(h.info["latency_s"])
                except ServeError as ex:
                    outcomes.append(type(ex).__name__)
                except Exception as ex:  # untyped = availability failure
                    outcomes.append(f"UNTYPED:{type(ex).__name__}")
        threads = [_th.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, outcomes, latencies

    def make_server(n):
        return QueryServer(session, graph=graph, config=ServerConfig(
            devices=n, max_queue=4096, max_batch=8,
            device_failure_threshold=2, device_cooldown_s=30.0,
            retry=RetryPolicy(max_attempts=5, backoff_base_s=0.002,
                              backoff_max_s=0.05)))

    # -- phase A: QPS scaling with device count ------------------------
    qps_by_devices = {}
    server = None
    for n in sorted({1, max(1, devices_n)}):
        if server is not None:
            server.shutdown()
        server = make_server(n)
        closed_loop(server)  # warm every replica's plan cache/compiles
        elapsed, outcomes, lats = closed_loop(server)
        ok = sum(1 for o in outcomes if o == "ok")
        qps_by_devices[n] = round(ok / elapsed, 1) if elapsed else 0.0
        _result.update({
            "metric": f"serve QPS scaling, closed-loop {clients} clients "
                      f"x {per_client} reqs, devices 1->{devices_n} "
                      f"({n_people} nodes, {n_edges} edges, "
                      f"{'tpu' if on_tpu else 'cpu-simulated-devices'})",
            "value": qps_by_devices[max(qps_by_devices)],
            "qps_by_devices": qps_by_devices,
            "qps_scaling": round(
                qps_by_devices[max(qps_by_devices)]
                / qps_by_devices[1], 3) if qps_by_devices.get(1) else 0.0,
            **{f"devices_{n}_{k}": v
               for k, v in _percentiles(lats).items()},
        })

    # -- phase B: availability with one of N devices killed mid-run ----
    victim = 1 if devices_n > 1 else 0
    if devices_n > 1 and _remaining() > 15:
        # kill the victim's WHOLE operator stream: the count families
        # execute the SpMV pushdown (CountPatternOp) on this backend,
        # everything else scans — hook both
        with device_loss(victim, op_name="CountPattern") as b1, \
                device_loss(victim, op_name="Scan") as b2:
            elapsed, outcomes, _lats = closed_loop(server)
            health = dict(server.device_health())
        budget_injected = b1.injected + b2.injected
        ok = sum(1 for o in outcomes if o == "ok")
        untyped = sum(1 for o in outcomes if o.startswith("UNTYPED"))
        devs = server.stats()["devices"]
        _result.update({
            "value": round(ok / total, 4) if total else 0.0,
            "unit": "fraction",
            "metric": _result["metric"].replace(
                "serve QPS scaling",
                "serve availability with 1 device killed mid-run; "
                "QPS scaling"),
            "device_loss_injected": budget_injected,
            "device_loss_ok": ok,
            "device_loss_untyped_errors": untyped,
            "device_loss_qps": round(ok / elapsed, 1) if elapsed else 0.0,
            "victim_health_during_fault": health.get(victim),
            "victim_quarantines": devs[victim]["quarantines"],
            "per_device_requests": {d["device"]: d["requests"]
                                    for d in devs},
            "server_health_during_fault": "degraded"
            if health.get(victim) != "healthy" else "healthy",
        })
    if server is not None:
        server.shutdown()
    _emit()


def run_serve_shards_config(on_tpu: bool, shards_n: int):
    """Benchmark config 9: sharded serving (``serve --shards N``).

    The capacity acceptance (ROADMAP item 2): the source graph lives in
    HOST memory (built on the local oracle session — the snapshot base),
    and the server fronts it with a shard group of N member devices
    whose per-member page budget is the *simulated HBM budget* — sized
    so the WHOLE graph is ~N× larger than any single member may hold
    resident.  Phase A measures closed-loop QPS over a mixed
    single-shard (partition-property equality → owning member) +
    cross-shard (2-hop traversal → the group's mesh-sharded session)
    workload, with paging gauges proving every member stayed within
    budget.  Phase B kills one shard member mid-run
    (``testing.faults.shard_loss``, bounded — the 'recovered device'):
    value = availability, the fraction of requests resolving with
    correct rows while the victim's group degrades, rebuilds from the
    host slices, and reinstates; group health transitions and
    ``telemetry_p99`` are reported from the server surfaces.
    """
    import threading as _th
    import numpy as np
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.serve import (QueryServer, RetryPolicy, ServeError,
                                ServerConfig)
    from caps_tpu.serve.shards import ShardGroupConfig
    from caps_tpu.testing.faults import shard_loss

    _result.update({"metric": "sharded serve availability "
                              "(no measurement completed)",
                    "unit": "fraction", "value": 0.0})
    rng = np.random.RandomState(42)
    if on_tpu:
        n_people, n_edges = 100_000, 500_000
    else:
        n_people, n_edges = 20_000, 100_000
    n_people = int(os.environ.get("BENCH_N_PEOPLE", n_people))
    n_edges = int(os.environ.get("BENCH_N_EDGES", n_edges))
    shards_n = max(2, int(shards_n))
    # the source graph lives on the HOST (local oracle session): device
    # residency is owned entirely by the group's members
    host_session = LocalCypherSession()
    graph, src, dst, names, _ = build_graph(host_session, n_people,
                                         n_edges, 10, rng)
    session = TPUCypherSession()

    Q_NAME = ("MATCH (n:Person) WHERE n.name = $seed "
              "RETURN count(*) AS c")
    seeds = [f"p{i}" for i in (1, 7, 13)] + ["Alice"]
    exp_name = {s: sum(1 for nm in names if nm == s) for s in seeds}
    exp_cross = expected_paths(src, dst, names, seeds)

    # simulated HBM budget: the whole graph is ~N× one member's budget
    from caps_tpu.serve.shards import partition_graph
    parts_probe = partition_graph(graph, shards_n * 3, "name")
    total_bytes = sum(p.host_nbytes() for p in parts_probe)
    # budget BELOW one member's fair share: the group must page cold
    # partitions through host memory to serve the whole graph
    budget = int(total_bytes / shards_n * 0.9) + 1
    server = QueryServer(session, graph=graph, config=ServerConfig(
        shards=shards_n, max_queue=4096, max_batch=8,
        shard_config=ShardGroupConfig(
            name="bench", partition_property="name",
            partitions_per_member=3, page_budget_bytes=budget,
            member_failure_threshold=2, member_cooldown_s=0.05),
        breaker_threshold=1000,
        retry=RetryPolicy(max_attempts=40, backoff_base_s=0.002,
                          backoff_max_s=0.05)))
    group = server.shard_groups[0]
    assert group.health() == "healthy"

    clients = 8
    per_client = int(os.environ.get("BENCH_SERVE_REQS", "12"))
    total = clients * per_client

    def closed_loop():
        latencies, outcomes = [], []

        def client(i):
            for j in range(per_client):
                seed = seeds[(i + j) % len(seeds)]
                try:
                    if (i + j) % 3 == 0:     # cross-shard traversal
                        h = server.submit(PARAM_QUERY, {"seed": seed})
                        want = exp_cross[seed]
                    else:                    # single-shard routed
                        h = server.submit(Q_NAME, {"seed": seed})
                        want = exp_name[seed]
                    rows = h.rows(timeout=300)
                    outcomes.append("ok" if rows[0]["c"] == want
                                    else "wrong")
                    latencies.append(h.info["latency_s"])
                except ServeError as ex:
                    outcomes.append(type(ex).__name__)
                except Exception as ex:  # untyped = availability failure
                    outcomes.append(f"UNTYPED:{type(ex).__name__}")
        threads = [_th.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, outcomes, latencies

    # -- phase A: capacity + QPS on the healthy group ------------------
    closed_loop()  # warm every routed member's plan cache + compiles
    elapsed, outcomes, lats = closed_loop()
    ok = sum(1 for o in outcomes if o == "ok")
    shard_stats = server.stats()["shards"][0]
    paging = shard_stats["paging"]
    resident_max = max(m["resident_bytes"]
                       for m in shard_stats["members"])
    telem = server.stats()["telemetry"]
    _result.update({
        "metric": f"sharded serve: {shards_n}-member group, graph "
                  f"~{round(total_bytes / budget, 2)}x one member's "
                  f"simulated HBM budget, 8-client closed loop "
                  f"({n_people} nodes, {n_edges} edges, "
                  f"{'tpu' if on_tpu else 'cpu-simulated-devices'})",
        "qps": round(ok / elapsed, 1) if elapsed else 0.0,
        "graph_host_bytes": int(total_bytes),
        "member_budget_bytes": int(budget),
        "graph_vs_budget_ratio": round(total_bytes / budget, 3),
        "resident_bytes_max_member": int(resident_max),
        "members_within_budget": bool(resident_max <= budget),
        "paging_faults": paging["faults"],
        "paging_spills": paging["spills"],
        "paging_host_bytes": paging["host_bytes"],
        "cross_shard_meshed": shard_stats["cross_shard_meshed"],
        "requests_single": session.metrics_snapshot()
        .get("shard.requests.single", 0),
        "requests_cross": session.metrics_snapshot()
        .get("shard.requests.cross", 0),
        "telemetry_p99": (telem.get("latency") or {}).get("p99_s"),
        **{f"healthy_{k}": v for k, v in _percentiles(lats).items()},
    })

    # -- phase B: one shard member killed mid-run ----------------------
    if _remaining() > 20:
        with shard_loss("bench", 0, n_times=8,
                        op_name="Scan") as budget_inj:
            elapsed, outcomes, lats = closed_loop()
        ok = sum(1 for o in outcomes if o == "ok")
        untyped = sum(1 for o in outcomes if o.startswith("UNTYPED"))
        # let the background rebuild finish before reading final state
        deadline = time.perf_counter() + 10
        while server.stats()["shards"][0]["state"] != "healthy" \
                and time.perf_counter() < deadline:
            time.sleep(0.05)
        shard_stats = server.stats()["shards"][0]
        _result.update({
            "value": round(ok / total, 4) if total else 0.0,
            "metric": _result["metric"].replace(
                "8-client closed loop",
                "availability with 1 shard member killed mid-run, "
                "8-client closed loop"),
            "shard_loss_injected": budget_inj.injected,
            "shard_loss_ok": ok,
            "shard_loss_untyped_errors": untyped,
            "shard_loss_qps": round(ok / elapsed, 1) if elapsed else 0.0,
            "group_transitions": [t["state"] for t in
                                  shard_stats["transitions"]],
            "group_state_final": shard_stats["state"],
            "victim_rebuilds": shard_stats["members"][0]["rebuilds"],
            "victim_quarantines":
                shard_stats["members"][0]["quarantines"],
            "loss_telemetry_p99": (server.stats()["telemetry"]
                                   .get("latency") or {}).get("p99_s"),
            **{f"loss_{k}": v for k, v in _percentiles(lats).items()},
        })
    server.shutdown()
    _emit()


def run_faults_config(on_tpu: bool):
    """Benchmark config 6: the serving tier under injected faults
    (ISSUE 5 — failure containment).

    Phase A runs the closed-loop prepared workload fault-free; phase B
    repeats it with single-shot transient device faults
    (``failing_operator("Filter", n_times=~20% of requests)``) so the
    worker's retry/backoff path carries a fifth of the traffic.

    value = availability under faults: the fraction of requests that
    resolved to a correct result or a typed ServeError (worker deaths /
    hung handles would show up here).  retry_overhead_p50 = faulted p50
    latency / clean p50 latency.  A final probe permanently breaks one
    query family and reports how many attempts its breaker needed to
    trip while the main family kept serving.
    """
    import threading as _th
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.obs import diff_snapshots
    from caps_tpu.serve import (QueryServer, RetryPolicy, ServeError,
                                ServerConfig)
    from caps_tpu.testing.faults import failing_operator

    _result.update({"metric": "serve availability under faults "
                              "(no measurement completed)",
                    "unit": "fraction", "value": 0.0})
    rng = np.random.RandomState(42)
    if on_tpu:
        n_people, n_edges, n_seeds = 50_000, 250_000, 20
    else:
        n_people, n_edges, n_seeds = 5_000, 25_000, 10
    session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(session, n_people, n_edges,
                                         n_seeds, rng)
    seeds = ["Alice"] + sorted({n for n in names if n != "Alice"})[:3]
    exp = expected_paths(src, dst, names, seeds)
    prep = session.prepare(PARAM_QUERY, graph=graph)
    for s_ in seeds:  # warm plan cache + fused recordings
        assert prep.run({"seed": s_}).records.to_maps()[0]["c"] == exp[s_]

    # The faulted workload must actually EXECUTE the operator the
    # injector hooks: the 2-hop count rides the SpMV count pushdown
    # (no FilterOp in its plan), so the fault phases serve a
    # filter/order/limit family instead and the 2-hop prepared family
    # doubles as the healthy-family probe in phase C.
    FQ = ("MATCH (p:Person) WHERE p.age > $min "
          "RETURN p.name AS n ORDER BY n LIMIT 5")
    bindings = [{"min": m} for m in (20, 35, 50, 65)]
    exp_rows = {b["min"]: graph.cypher(FQ, b).records.to_maps()
                for b in bindings}

    clients = 8
    per_client = int(os.environ.get("BENCH_FAULT_REQS", "25"))
    total = clients * per_client

    def closed_loop(server, latencies, outcomes):
        def client(i):
            for j in range(per_client):
                b = bindings[(i + j) % len(bindings)]
                try:
                    h = server.submit(FQ, b)
                    rows = h.rows(timeout=60)
                    ok = rows == exp_rows[b["min"]]
                    outcomes.append("ok" if ok else "wrong")
                    latencies.append(h.info["latency_s"])
                except ServeError as ex:
                    outcomes.append(type(ex).__name__)
                except Exception as ex:  # untyped = availability failure
                    outcomes.append(f"UNTYPED:{type(ex).__name__}")
        threads = [_th.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    config = ServerConfig(workers=2, max_queue=4096, max_batch=16,
                          breaker_threshold=8, breaker_cooldown_s=0.5,
                          retry=RetryPolicy(max_attempts=4,
                                            backoff_base_s=0.002,
                                            backoff_max_s=0.05))
    # -- phase A: fault-free baseline ----------------------------------
    server = QueryServer(session, graph=graph, config=config)
    clean_lat, clean_out = [], []
    clean_s = closed_loop(server, clean_lat, clean_out)
    clean_p = _percentiles(clean_lat)

    # -- phase B: ~20% of executions hit a transient device fault ------
    snap0 = session.metrics_snapshot()
    fault_lat, fault_out = [], []
    with failing_operator("Filter", every_n=5) as budget:
        fault_s = closed_loop(server, fault_lat, fault_out)
    n_faults = budget.injected
    delta = diff_snapshots(snap0, session.metrics_snapshot())
    resolved = sum(1 for o in fault_out
                   if o == "ok" or (o != "wrong"
                                    and not o.startswith("UNTYPED")))
    availability = resolved / total if total else 0.0
    fault_p = _percentiles(fault_lat)

    # -- phase C: permanently break ONE family, watch its breaker ------
    probe_q = ("MATCH (p:Person) WHERE p.age > $min "
               "RETURN p.name AS n ORDER BY n LIMIT 3")
    attempts_to_trip = 0
    with failing_operator("OrderBy", exc=RuntimeError("bench poison"),
                          n_times=None):
        for k in range(2 * config.breaker_threshold + 2):
            try:
                server.run(probe_q, {"min": 0})
            except ServeError as ex:
                attempts_to_trip = k + 1
                if type(ex).__name__ == "CircuitOpen":
                    break
        # the healthy family keeps serving while the probe family is open
        other_ok = prep.run({"seed": "Alice"}
                            ).records.to_maps()[0]["c"] == exp["Alice"]
    health = server.health()
    server.shutdown()

    _result.update({
        "metric": f"serve availability under ~20% transient faults, "
                  f"closed-loop {clients} clients x {per_client} reqs "
                  f"({n_people} nodes, {n_edges} edges, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": round(availability, 4),
        "unit": "fraction",
        "vs_baseline": 1.0,  # fault-free availability by construction
        "fault_injected": n_faults,
        "fault_success": sum(1 for o in fault_out if o == "ok"),
        "fault_typed_errors": sum(
            1 for o in fault_out
            if o not in ("ok", "wrong") and not o.startswith("UNTYPED")),
        "fault_untyped_errors": sum(
            1 for o in fault_out if o.startswith("UNTYPED")),
        "retries": delta.get("serve.retries", 0),
        "clean_qps": round(total / clean_s, 1) if clean_s else 0.0,
        "faulted_qps": round(total / fault_s, 1) if fault_s else 0.0,
        "clean_p50_s": clean_p.get("p50_s", 0.0),
        "faulted_p50_s": fault_p.get("p50_s", 0.0),
        "retry_overhead_p50": round(
            fault_p.get("p50_s", 0.0) / clean_p.get("p50_s", 1.0), 3)
        if clean_p.get("p50_s") else 0.0,
        "breaker_attempts_to_trip": attempts_to_trip,
        "breaker_health": health,
        "breaker_other_family_served": bool(other_ok),
    })
    _emit()


def run_plan_config(on_tpu: bool):
    """Benchmark config 9: cost-based planning vs forced heuristics
    (ISSUE 12 — relational/stats.py + relational/cost.py).

    Phase A builds a skewed LDBC-shaped graph (Zipfian KNOWS degrees and
    tag popularity, dense LIVES_IN/HAS_INTEREST fan-out, few Cities,
    unique names) on two sessions — one with the cost model, one with
    ``use_cost_model=False`` (the pre-item-3 fixed heuristics) — and
    runs five query families on both: three where the model should
    change the plan (chain re-roots at a selective far end) and two
    guards where it should NOT deviate (the fused count SpMV, a
    uniform-seed count).  Per family the verdict number is the median
    warm per-execution wall time, measured in rotations that ALTERNATE
    between the two live sessions so host-load drift cancels (per-op
    seconds in ``op_stats`` nest, so they distort ratios for deep plans
    — wall time is the honest win metric); the ``op_stats`` actuals
    ride along per family as the observed per-operator rows next to the
    model's estimates (the estimate-vs-actual surface the divergence
    detector reads).  Results are digest-checked binding-by-binding
    across the two sessions: a plan change that changed an answer would
    fail here, not regress silently.

    value = families where the planned strategy beats the heuristic by
    >= 1.25x; the run FAILS if fewer than 3 win or any family regresses
    past 1.25x the heuristic time.

    Phase B closes the feedback loop end to end: a stats-violating
    workload (``faults.stale_statistics`` distorts the sketch under a
    QueryServer) diverges the model, the family retires through the
    quarantine path, and the re-plan with honest statistics re-roots
    the chain — asserted from the structured event log
    (``replan.triggered`` -> ``replan.completed``) with the re-plan's
    compile seconds charged on the completing request.
    """
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.frontend.parser import normalize_query
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.serve import QueryServer, ServerConfig
    from caps_tpu.testing import faults
    from tests.util import make_graph

    _result.update({"metric": "cost-based planning vs heuristics "
                              "(no measurement completed)",
                    "unit": "families", "value": 0.0})
    if on_tpu:
        n_person, n_city, n_tag, m_knows = 100_000, 200, 1_000, 500_000
    else:
        n_person, n_city, n_tag, m_knows = 8_000, 40, 100, 32_000
    # dense many-to-many fan-out: each person LIVES_IN (residence
    # history) several cities and HAS_INTEREST in several tags, so the
    # heuristic's person-rooted chain joins the FULL edge table before
    # the selective filter prunes it — intermediates that cross
    # shape-bucket boundaries the re-rooted plan never reaches
    lives_k, interest_k = 3, 2

    def build(sess, seed=42):
        rng = np.random.RandomState(seed)
        tgt = (rng.zipf(1.5, m_knows) - 1) % n_person  # Zipfian in-degree
        src = rng.randint(0, n_person, m_knows)
        # Zipfian tag popularity
        tags = (rng.zipf(1.3, n_person * interest_k) - 1) % n_tag
        return make_graph(sess, {
            ("Person",): [{"_id": i, "name": f"p{i}",
                           "age": int(rng.randint(0, 80))}
                          for i in range(n_person)],
            ("City",): [{"_id": n_person + i, "name": f"c{i}"}
                        for i in range(n_city)],
            ("Tag",): [{"_id": n_person + n_city + i, "name": f"t{i}"}
                       for i in range(n_tag)],
        }, {
            "KNOWS": [(int(s), int(t), {}) for s, t in zip(src, tgt)],
            "LIVES_IN": [(i, n_person + int(c), {})
                         for i in range(n_person)
                         for c in rng.randint(0, n_city, lives_k)],
            "HAS_INTEREST": [(i, n_person + n_city
                              + int(tags[i * interest_k + j]), {})
                             for i in range(n_person)
                             for j in range(interest_k)],
        })

    FAMILIES = {
        # the model should re-root these chains at the selective far end
        "city_reroot": (
            "MATCH (p:Person)-[:LIVES_IN]->(c:City) "
            "WHERE c.name = $city RETURN p.name AS n",
            [{"city": f"c{i}"} for i in (3, 7, 11)]),
        "tag_reroot": (
            "MATCH (p:Person)-[:HAS_INTEREST]->(t:Tag) "
            "WHERE t.name = $tag RETURN p.name AS n",
            [{"tag": f"t{i}"} for i in (5, 9, 60)]),
        "twohop_reroot": (
            "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:LIVES_IN]->(c:City) "
            "WHERE c.name = $city RETURN a.name AS n",
            [{"city": f"c{i}"} for i in (3, 7, 11)]),
        # guards: the model should NOT deviate from the heuristic here
        "count_spmv_guard": (
            "MATCH (a:Person)-[:KNOWS]->(b) WHERE a.name = $name "
            "RETURN count(*) AS c",
            [{"name": f"p{i}"} for i in (17, 940, 2500)]),
        "uniform_guard": (
            "MATCH (a:Person)-[:KNOWS]->(b:Person) WHERE a.age > $min "
            "RETURN count(*) AS c",
            [{"min": m} for m in (20, 40, 60)]),
    }
    rotations = int(os.environ.get("BENCH_PLAN_ROTATIONS", "4"))

    # both sessions live side by side and the rotation loop alternates
    # between them, so host-load drift hits both plans equally — the
    # per-family verdict is a paired comparison, not two separated runs
    sessions = {}
    for label, cfg in (("planned", None),
                       ("heuristic", EngineConfig(use_cost_model=False))):
        session = TPUCypherSession(config=cfg) if cfg is not None \
            else TPUCypherSession()
        sessions[label] = (session, build(session))

    digests = {}
    for label, (session, graph) in sessions.items():
        digs = {}
        for fam_name, (q, binds) in FAMILIES.items():
            for b in binds:  # warm: plan + fused recordings per binding
                res = graph.cypher(q, b)
                digs[(fam_name, tuple(sorted(b.items())))] = sorted(
                    tuple(sorted(m.items()))
                    for m in res.records.to_maps())
        digests[label] = digs
    # exactness across the strategy change, binding by binding
    assert digests["planned"] == digests["heuristic"], \
        "planned and heuristic sessions disagree on results"

    rot_s = {label: {f: [] for f in FAMILIES} for label in sessions}
    for _ in range(rotations):
        for label, (session, graph) in sessions.items():
            for fam_name, (q, binds) in FAMILIES.items():
                t0 = time.perf_counter()
                for b in binds:
                    graph.cypher(q, b)
                rot_s[label][fam_name].append(
                    (time.perf_counter() - t0) / len(binds))
    # median is robust to a divergence-triggered cold re-plan landing
    # mid-measurement
    measured = {label: {f: statistics.median(rot_s[label][f])
                        for f in FAMILIES} for label in sessions}
    # the op_stats actuals the model's feedback loop reads: observed
    # per-operator rows next to the stamped estimates
    planned_session = sessions["planned"][0]
    planned_op_rows = {
        fam_name: {
            op: {"rows_mean": round(v["rows_mean"], 1),
                 **({"est_rows": v["est_rows"]}
                    if "est_rows" in v else {})}
            for op, v in planned_session.op_stats.stats(
                normalize_query(q)).items()}
        for fam_name, (q, _) in FAMILIES.items()}

    WIN, REGRESS = 1.25, 1.25
    families_out = {}
    wins, regressions = [], []
    for fam_name in FAMILIES:
        p = measured["planned"][fam_name]
        h = measured["heuristic"][fam_name]
        speedup = h / p if p else 0.0
        verdict = ("win" if speedup >= WIN
                   else "regression" if speedup < 1.0 / REGRESS
                   else "neutral")
        if verdict == "win":
            wins.append(fam_name)
        elif verdict == "regression":
            regressions.append(fam_name)
        families_out[fam_name] = {
            "planned_exec_s": round(p, 5),
            "heuristic_exec_s": round(h, 5),
            "speedup": round(speedup, 3), "verdict": verdict,
            # estimate-vs-actual per operator (the divergence surface)
            "op_rows": planned_op_rows.get(fam_name, {}),
        }
    assert not regressions, \
        f"planned plans regressed: {regressions} ({families_out})"
    assert len(wins) >= 3, \
        f"only {wins} beat the heuristics ({families_out})"

    # Phase B: divergence -> quarantine -> re-plan, observable end to end
    replan_out = {}
    if _remaining() > 30:
        session = TPUCypherSession()
        graph = build(session)
        q, binds = FAMILIES["city_reroot"]
        server = QueryServer(session, graph=graph,
                             config=ServerConfig(workers=2))
        try:
            with faults.stale_statistics(graph, scale=0.001):
                # the distorted prior keeps the written order; every
                # execution diverges from the model's tiny estimates.
                # Same binding twice: the second is an exact fused
                # replay, so the ONLY plan churn is the model's own
                # trigger (threshold 2) at the end of it.
                for _ in range(2):
                    server.submit(q, binds[0]).result()
            res = server.submit(q, binds[0]).result()  # the re-plan
            events = [e["event"] for e in server.event_log.records()
                      if e["event"].startswith("replan.")]
            assert events == ["replan.triggered", "replan.completed"], \
                events
            assert res.metrics["compile_s_charged"] > 0
            plan = res.plans["relational"]
            replan_out = {
                "replan_events": events,
                "replan_compile_s": round(
                    res.metrics["compile_s_charged"], 4),
                "replan_rerooted": plan.index("Scan(c") <
                plan.index("Scan(p"),
                "divergences": session.metrics_snapshot()
                ["opstats.divergences"],
            }
        finally:
            server.shutdown()

    _result.update({
        "metric": f"cost-based planning: query families beating forced "
                  f"heuristics at >={WIN}x "
                  f"(zipfian ldbc-shaped, {n_person} persons, "
                  f"{m_knows} knows edges, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": float(len(wins)),
        "unit": "families",
        "vs_baseline": round(max(f["speedup"]
                                 for f in families_out.values()), 3),
        "families": families_out,
        "wins": wins,
        "regressions": regressions,
        **replan_out,
    })
    _emit()


def run_updates_config(on_tpu: bool):
    """Benchmark config 8: live graph updates under serving load
    (ISSUE 8 — snapshot isolation + failure-atomic writes).

    8 closed-loop clients run a mixed read/write workload (write
    fraction configurable, default ~25%) against ONE versioned graph
    behind a QueryServer with the background compactor enabled, while
    ``abort_write`` injects transient aborts into ~20% of write
    commits.

    value = availability: the fraction of requests that resolved to a
    correct result or a typed ServeError.  reader_digest_stable = every
    reader's rows equal the serial state at its admission-time snapshot
    version (zero torn reads).  Also reports write/read p50, commit and
    rollback counts, compactions completed under load, and the final
    compaction backlog.
    """
    import threading as _th
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.obs import diff_snapshots
    from caps_tpu.relational.updates import versioned
    from caps_tpu.serve import (QueryServer, RetryPolicy, ServeError,
                                ServerConfig)
    from caps_tpu.testing.faults import abort_write
    from caps_tpu.testing.factory import create_graph

    _result.update({"metric": "mixed read/write availability "
                              "(no measurement completed)",
                    "unit": "fraction", "value": 0.0})
    wf = 0.25
    if "--write-fraction" in sys.argv:
        i = sys.argv.index("--write-fraction")
        if i + 1 < len(sys.argv):
            wf = float(sys.argv[i + 1])
    every_write = max(2, int(round(1.0 / max(wf, 0.01))))
    clients = 8
    per_client = int(os.environ.get("BENCH_UPDATE_REQS",
                                    "40" if on_tpu else "25"))
    total = clients * per_client

    session = TPUCypherSession()
    vg = versioned(session, create_graph(
        session, "CREATE (:Seed {k:-1, v:-1})"))
    server = QueryServer(session, graph=vg, config=ServerConfig(
        workers=2, max_queue=4096,
        retry=RetryPolicy(max_attempts=5, backoff_base_s=0.002,
                          backoff_max_s=0.05),
        compaction_threshold_rows=16, compaction_interval_s=0.005))

    write_log, observations, failures = {}, [], []
    log_lock = _th.Lock()
    write_lat, read_lat = [], []

    def client(i):
        for j in range(per_client):
            is_write = (i * per_client + j) % every_write == 0
            try:
                if is_write:
                    k = i * 100_000 + j
                    h = server.submit("CREATE (:Item {k:$k, v:$v})",
                                      {"k": k, "v": k * 7})
                    res = h.result(timeout=60)
                    with log_lock:
                        write_log[res.metrics["snapshot_version"]] = \
                            (k, k * 7)
                        write_lat.append(h.info["latency_s"])
                else:
                    h = server.submit(
                        "MATCH (n:Item) RETURN n.k AS k, n.v AS v")
                    rows = h.rows(timeout=60)
                    with log_lock:
                        observations.append(
                            (h.info["snapshot_version"],
                             frozenset((r["k"], r["v"]) for r in rows)))
                        read_lat.append(h.info["latency_s"])
            except ServeError:
                pass  # typed shed/deadline: availability still holds
            except Exception as ex:
                failures.append((i, j, type(ex).__name__, str(ex)[:120]))

    snap0 = session.metrics_snapshot()
    threads = [_th.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    with abort_write(session, after_n_columns=1, n_times=None,
                     every_n=5) as budget:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    elapsed = time.perf_counter() - t0
    delta = diff_snapshots(snap0, session.metrics_snapshot())
    server.shutdown()

    torn = 0
    for version, seen in observations:
        expected = frozenset(kv for v, kv in write_log.items()
                             if v <= version)
        if seen != expected:
            torn += 1
    resolved = total - len(failures)
    availability = resolved / total if total else 0.0

    _result.update({
        "metric": f"availability, 8-client mixed read/write soak "
                  f"(~{round(100 / every_write)}% writes, ~20% write "
                  f"aborts injected, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": round(availability, 4),
        "unit": "fraction",
        "vs_baseline": 1.0,
        "qps": round(total / elapsed, 1) if elapsed else 0.0,
        "writes_committed": len(write_log),
        "write_aborts_injected": budget.injected,
        "write_rollbacks": delta.get("updates.rolled_back", 0),
        "write_retries": delta.get("serve.retries", 0),
        "reader_digest_stable": torn == 0,
        "torn_reads": torn,
        "reads_observed": len(observations),
        "write_p50_s": _percentiles(write_lat).get("p50_s", 0.0),
        "read_p50_s": _percentiles(read_lat).get("p50_s", 0.0),
        "compactions_under_load": delta.get("compaction.runs", 0),
        "compaction_conflicts": delta.get("compaction.conflicts", 0),
        "compaction_backlog_rows": vg.delta_rows(),
        "untyped_failures": failures[:5],
    })
    _emit()


def run_cyclic_config(on_tpu: bool):
    """Config 10: the analytics-tier cyclic-pattern suite (ROADMAP
    item 4).  Triangle / diamond / 4-cycle ENUMERATION (not just
    counting) plus diamond/4-cycle counts, run on two sessions — the
    worst-case-optimal multiway join (relational/wcoj.py) and the
    forced binary cascade (``use_wcoj=False``) — in interleaved paired
    rotations with digest-exact parity asserted every time.  The sweep
    varies edge density: the cascade's open-pattern intermediates grow
    super-linearly with density while the WCOJ frontier tracks the true
    match count, so the speedup curve must GROW with density.  Count
    pushdown is off in both sessions so counting isolates the same
    wcoj-vs-cascade choice the enumeration measures."""
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.relational.session import result_digest

    if on_tpu:
        n_nodes, densities, rotations = 100_000, (4, 8, 16), 5
    else:
        n_nodes, densities, rotations = 3_000, (2, 4, 8), 3
    n_nodes = int(os.environ.get("BENCH_CYC_NODES", n_nodes))

    PATTERNS = {
        "triangle": ("MATCH (a:Person)-[r1:KNOWS]->(b)-[r2:KNOWS]->(c), "
                     "(a)-[r3:KNOWS]->(c) "),
        "diamond": ("MATCH (a:Person)-[r1:KNOWS]->(b)-[r2:KNOWS]->(d), "
                    "(a)-[r3:KNOWS]->(c)-[r4:KNOWS]->(d) "),
        "cycle4": ("MATCH (a:Person)-[r1:KNOWS]->(b)-[r2:KNOWS]->(c)"
                   "-[r3:KNOWS]->(d), (d)-[r4:KNOWS]->(a) "),
    }
    ENUM_RETURN = {"triangle": "RETURN id(a) AS x, id(b) AS y, id(c) AS z",
                   "diamond": "RETURN id(a) AS w, id(b) AS x, "
                              "id(c) AS y, id(d) AS z",
                   "cycle4": "RETURN id(a) AS w, id(b) AS x, "
                             "id(c) AS y, id(d) AS z"}
    COUNT_SHAPES = ("diamond", "cycle4")

    def build(session, rng, n, deg, zipf=False):
        m = n * deg
        if zipf:
            # LDBC-shaped skew: Zipfian out-endpoints (a few hub
            # accounts), uniform in-endpoints
            ranks = rng.zipf(1.3, size=m) % n
            src = ranks.astype(np.int64)
        else:
            src = rng.randint(0, n, m)
        dst = rng.randint(0, n, m)
        from caps_tpu.okapi.types import CTInteger, CTString
        from caps_tpu.relational.entity_tables import (
            NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
        )
        f = session.table_factory
        nt = NodeTable(
            NodeMapping.on("_id").with_implied_labels("Person")
            .with_property("name"),
            f.from_columns(
                {"_id": list(range(n)),
                 "name": [f"p{i}" for i in range(n)]},
                {"_id": CTInteger, "name": CTString}))
        rt = RelationshipTable(
            RelationshipMapping.on("KNOWS"),
            f.from_columns(
                {"_id": list(range(n, n + m)),
                 "_src": [int(x) for x in src],
                 "_tgt": [int(x) for x in dst]},
                {"_id": CTInteger, "_src": CTInteger, "_tgt": CTInteger}))
        return session.create_graph([nt], [rt])

    def paired_times(g_w, g_c, query, rounds):
        """Interleaved paired rotations, alternating which side goes
        first; device_sync so async dispatch can't flatter either."""
        times = {"wcoj": [], "cascade": []}

        def one(g, key):
            t0 = time.perf_counter()
            res = g.cypher(query)
            if res.records is not None:
                res.records.table.device_sync()
            times[key].append(time.perf_counter() - t0)
            return res

        for r in range(rounds):
            order = (("wcoj", g_w), ("cascade", g_c)) if r % 2 == 0 \
                else (("cascade", g_c), ("wcoj", g_w))
            for key, g in order:
                one(g, key)
        return (statistics.median(times["wcoj"]),
                statistics.median(times["cascade"]))

    curves: dict = {}
    parity_checked = 0
    explain_has_choice = False
    top_speedups: dict = {}
    for deg in densities:
        if _remaining() < 30:
            break
        cfg_w = EngineConfig(use_count_pushdown=False)
        cfg_c = EngineConfig(use_count_pushdown=False, use_wcoj=False)
        s_w, s_c = TPUCypherSession(cfg_w), TPUCypherSession(cfg_c)
        g_w = build(s_w, np.random.RandomState(17), n_nodes, deg)
        g_c = build(s_c, np.random.RandomState(17), n_nodes, deg)
        for name, match in PATTERNS.items():
            if _remaining() < 20:
                break
            q = match + ENUM_RETURN[name]
            if not explain_has_choice:
                exp = g_w.cypher("EXPLAIN " + q)
                explain_has_choice = (
                    "wcoj_strategy" in exp.plans.get("cost", "")
                    and "MultiwayJoin" in exp.plans.get("relational", ""))
                assert explain_has_choice, exp.plans
            r_w, r_c = g_w.cypher(q), g_c.cypher(q)  # warm + parity
            assert "MultiwayJoin" in [m["op"] for m in
                                      r_w.metrics["operators"]], name
            d_w, d_c = result_digest(r_w), result_digest(r_c)
            assert d_w == d_c, (name, deg)
            parity_checked += 1
            med_w, med_c = paired_times(g_w, g_c, q, rotations)
            entry = {"rows": r_w.records.size(),
                     "wcoj_s": round(med_w, 5),
                     "cascade_s": round(med_c, 5),
                     "speedup": round(med_c / med_w, 3) if med_w else 0.0}
            if name in COUNT_SHAPES and _remaining() > 15:
                qc = match + "RETURN count(*) AS c"
                rc_w, rc_c = g_w.cypher(qc), g_c.cypher(qc)
                assert (rc_w.records.to_maps() == rc_c.records.to_maps())
                cw, cc = paired_times(g_w, g_c, qc, max(2, rotations - 1))
                entry["count_speedup"] = round(cc / cw, 3) if cw else 0.0
            curves[f"{name}_deg{deg}"] = entry
            if deg == densities[-1]:
                top_speedups[name] = entry["speedup"]
    # LDBC-shaped skewed graph: one triangle-enumeration checkpoint
    ldbc_entry = None
    if _remaining() > 25:
        cfg_w = EngineConfig(use_count_pushdown=False)
        cfg_c = EngineConfig(use_count_pushdown=False, use_wcoj=False)
        s_w, s_c = TPUCypherSession(cfg_w), TPUCypherSession(cfg_c)
        deg = densities[len(densities) // 2]
        g_w = build(s_w, np.random.RandomState(23), n_nodes, deg, zipf=True)
        g_c = build(s_c, np.random.RandomState(23), n_nodes, deg, zipf=True)
        q = PATTERNS["triangle"] + ENUM_RETURN["triangle"]
        r_w, r_c = g_w.cypher(q), g_c.cypher(q)
        assert result_digest(r_w) == result_digest(r_c)
        parity_checked += 1
        med_w, med_c = paired_times(g_w, g_c, q, max(2, rotations - 1))
        ldbc_entry = {"rows": r_w.records.size(),
                      "wcoj_s": round(med_w, 5),
                      "cascade_s": round(med_c, 5),
                      "speedup": round(med_c / med_w, 3) if med_w else 0.0}

    # acceptance: the WCOJ path wins on >= 2 of 3 shapes at the top
    # density, digest-exact throughout, and the win grows with density.
    # Only enforced when the deadline let the sweep REACH the top
    # density — a truncated run degrades to a partial report like the
    # other configs instead of emitting nothing.
    wins = sum(1 for v in top_speedups.values() if v > 1.0)
    if top_speedups:
        assert wins >= 2, top_speedups
    growth = {}
    for name in PATTERNS:
        series = [curves[f"{name}_deg{d}"]["speedup"] for d in densities
                  if f"{name}_deg{d}" in curves]
        if len(series) >= 2:
            growth[name] = series
    grew = sum(1 for s in growth.values() if s[-1] > s[0])
    _result.update({
        "metric": f"cyclic-pattern WCOJ vs binary cascade "
                  f"({n_nodes} nodes, densities {list(densities)}, "
                  f"{'tpu' if on_tpu else 'cpu'}, "
                  f"parity_checks={parity_checked}, digest-exact)",
        "value": round(max(top_speedups.values(), default=0.0), 3),
        "unit": "x speedup (enumeration, top density)",
        "top_speedups": top_speedups,
        "growth_with_density": growth,
        "curves_grew": grew,
        "explain_renders_choice": explain_has_choice,
        "curves": curves,
        "vs_baseline": 0.0,
    })
    if ldbc_entry is not None:
        _result["ldbc_shaped_triangle"] = ldbc_entry
    _emit()


def run_algo_config(on_tpu: bool):
    """``bench.py algo`` — the CALL algo.* analytics tier (caps_tpu/algo):
    PageRank / WCC / BFS over the shared iterative-fixpoint executor on
    three generators — a DENSE tile-filling generator (few nodes, edge
    count approaching the capacity square, where the operator picks the
    matrix-product dense-tile program family), an LDBC-shaped uniform
    generator, and a Zipf-skew (hub-heavy) generator — device fixpoint
    vs FORCED host fallback (a permanent injected device fault — the
    NumPy twin serves every call) in interleaved paired rotations with
    result parity asserted every time.  Reported per procedure:
    iterations to convergence, edges/s per iteration, and the
    device-vs-host speedup; acceptance is the device pushdown beating
    the forced host path on the dense generator (the sparse edge-list
    generators are report-only on a CPU host, where XLA's scattered
    SpMV cannot beat NumPy's fused ufunc.at loop — the dense tile is
    the layout the matrix unit was built for)."""
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.testing import faults

    if on_tpu:
        n_nodes, deg, rotations = 200_000, 10, 5
    else:
        n_nodes, deg, rotations = 20_000, 10, 3
    n_nodes = int(os.environ.get("BENCH_ALGO_NODES", n_nodes))
    dense_nodes, dense_deg = 256, 192  # fills the 256-capacity tile

    GENS = {  # name -> (n, m, skew)
        "dense": (dense_nodes, dense_nodes * dense_deg, False),
        "ldbc": (n_nodes, n_nodes * deg, False),
        "zipf": (n_nodes, n_nodes * deg, True),
    }

    def build(session, rng, n_nodes, m, zipf=False):
        if zipf:
            src = (rng.zipf(1.3, size=m) % n_nodes).astype(np.int64)
        else:
            src = rng.randint(0, n_nodes, m)
        dst = rng.randint(0, n_nodes, m)
        from caps_tpu.okapi.types import CTInteger
        from caps_tpu.relational.entity_tables import (
            NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
        )
        f = session.table_factory
        nt = NodeTable(
            NodeMapping.on("_id").with_implied_labels("Person"),
            f.from_columns({"_id": list(range(n_nodes))},
                           {"_id": CTInteger}))
        rt = RelationshipTable(
            RelationshipMapping.on("KNOWS"),
            f.from_columns(
                {"_id": list(range(n_nodes, n_nodes + m)),
                 "_src": [int(x) for x in src],
                 "_tgt": [int(x) for x in dst]},
                {"_id": CTInteger, "_src": CTInteger, "_tgt": CTInteger}))
        return session.create_graph([nt], [rt])

    PROCS = {
        "pagerank": "CALL algo.pagerank() YIELD node, score "
                    "RETURN node, score",
        "wcc": "CALL algo.wcc() YIELD node, component "
               "RETURN node, component",
        "bfs": "CALL algo.bfs(0) YIELD node, dist RETURN node, dist",
    }
    # on the dense tile, pin pagerank to a fixed 64-iteration run
    # (tolerance 0 disables early exit): dense graphs converge in a
    # handful of rounds, which leaves the per-query pipeline overhead —
    # identical on both sides — dominating the measurement; fixed work
    # measures the iteration engines themselves
    DENSE_PROCS = dict(PROCS, pagerank=(
        "CALL algo.pagerank(0.85, 64, 0.0) YIELD node, score "
        "RETURN node, score"))

    def timed(g, query):
        t0 = time.perf_counter()
        res = g.cypher(query)
        if res.records is not None:
            res.records.table.device_sync()
        return res, time.perf_counter() - t0

    curves: dict = {}
    parity_checked = 0
    dense_speedups: dict = {}
    for gen, (gn, gm, skew) in GENS.items():
        if _remaining() < 30:
            break
        s = TPUCypherSession()
        g = build(s, np.random.RandomState(17), gn, gm, zipf=skew)
        for name, q in (DENSE_PROCS if gen == "dense" else PROCS).items():
            if _remaining() < 20:
                break
            prof = g.cypher("PROFILE " + q)  # warm (compile) + metrics
            (op,) = [x for x in prof.metrics["operators"]
                     if x["op"] == "AlgoProcedure"]
            assert op["strategy"] == "device-fixpoint", (gen, name, op)
            if gen == "dense":
                assert op["layout"] == "dense-tile", (name, op)
            iters = max(1, op["iterations"])
            device_rows = sorted(map(tuple, (r.items() for r in
                                             prof.records.to_maps())))
            with faults.failing_algo(n_times=None):
                host_res, _ = timed(g, q)  # warm the host twin too
                host_rows = sorted(map(tuple, (r.items() for r in
                                               host_res.records.to_maps())))
            assert host_rows == device_rows, (gen, name)
            parity_checked += 1
            times = {"device": [], "host": []}
            for r in range(rotations):
                first = r % 2 == 0
                for side in (("device", "host") if first
                             else ("host", "device")):
                    if side == "device":
                        _, dt = timed(g, q)
                        times["device"].append(dt)
                    else:
                        with faults.failing_algo(n_times=None):
                            _, ht = timed(g, q)
                        times["host"].append(ht)
            med_d = statistics.median(times["device"])
            med_h = statistics.median(times["host"])
            curves[f"{name}_{gen}"] = {
                "layout": op["layout"],
                "iterations": iters,
                "converged": bool(op["converged"]),
                "device_s": round(med_d, 5),
                "host_s": round(med_h, 5),
                "edges_per_s_per_iter": round(gm / (med_d / iters)),
                "speedup": round(med_h / med_d, 3) if med_d else 0.0,
            }
            if gen == "dense":
                dense_speedups[name] = curves[f"{name}_{gen}"]["speedup"]

    # acceptance: the device pushdown (dense-tile family) beats the
    # forced host path on the dense generator (only enforced when the
    # deadline let the sweep measure it)
    if dense_speedups:
        wins = sum(1 for v in dense_speedups.values() if v > 1.0)
        assert wins >= 1, dense_speedups
    _result.update({
        "metric": f"CALL algo.* device fixpoint vs forced host fallback "
                  f"(dense {dense_nodes}n/deg{dense_deg}, "
                  f"sparse {n_nodes}n/deg{deg}, "
                  f"{'tpu' if on_tpu else 'cpu'}, "
                  f"parity_checks={parity_checked})",
        "value": round(max(dense_speedups.values(), default=0.0), 3),
        "unit": "x speedup (dense generator)",
        "dense_speedups": dense_speedups,
        "curves": curves,
        "vs_baseline": 0.0,
    })
    _emit()


def run_fleet_config(on_tpu: bool, procs: int):
    """``bench.py fleet --procs N`` — multi-process scale-out (ISSUE 16).

    Spawns N REAL backend interpreters (serve/fleet.py spawn_backend —
    each child owns its GIL, its plan cache, its graph) behind one
    consistent-hash router and measures, on CPU-smoke acceptance:

      * read QPS over N processes >= 3x the single-process baseline on
        cache-resident families (the router restricted to one ring node
        IS the baseline — same wire, same client, same families);
      * availability 1.0 through one backend SIGKILLed mid-soak (the
        router degrades its ring segment and retries; every client
        request still succeeds);
      * cross-process read-your-writes: a write through the owner ships
        snapshots to every surviving peer within a measured lag, and
        every backend answers the read-back digest-exact.

    Children run the pure-Python local backend with a configured
    per-query device dwell (``BackendSpec.service_dwell_s`` — the
    TPU-serving model: a backend process spends a query's life WAITING
    on its device, and fleet scale-out buys parallel devices).  That
    keeps the scaling measurement about serving-path parallelism —
    deterministic even on a single-core CI host, where compute-bound
    QPS could never scale across processes — and keeps per-process jax
    warmup from drowning the soak inside the bench budget.
    """
    from caps_tpu.obs.metrics import MetricsRegistry
    from caps_tpu.serve.errors import ServeError
    from caps_tpu.serve.fleet import BackendSpec, spawn_backend
    from caps_tpu.serve.router import FleetRouter, RouterConfig

    procs = max(2, procs)
    dwell_s = 0.03
    gspec = {"kind": "foaf", "n_people": 200, "n_edges": 700, "seed": 11}
    q_read = ("MATCH (p:Person) WHERE p.age > $min "
              "RETURN p.name AS n ORDER BY n LIMIT 10")

    children = []
    backends = {}
    try:
        for i in range(procs):
            spec = BackendSpec(name=f"p{i}", backend="local", graph=gspec,
                               versioned=True, workers=2, max_queue=512,
                               service_dwell_s=dwell_s)
            proc, port = spawn_backend(spec)
            children.append((f"p{i}", proc))
            backends[f"p{i}"] = ("127.0.0.1", port)

        registry = MetricsRegistry()
        router = FleetRouter(backends, owner="p0",
                             config=RouterConfig(max_attempts=procs),
                             registry=registry)
        solo = FleetRouter({"p0": backends["p0"]},
                           registry=MetricsRegistry())

        # a BALANCED cache-resident family set: keep generating
        # candidate families until every backend primaries the same
        # number (the acceptance's premise is an evenly spread resident
        # working set; skew relief is the spill test's job, not this
        # measurement's)
        per_backend = 3
        groups = {name: [] for name in backends}
        i = 0
        while any(len(g) < per_backend for g in groups.values()) and i < 500:
            fam, params = f"fam-{i}", {"min": 20 + (i % 30)}
            primary = router.ring.preference(f"default|{fam}")[0]
            if len(groups[primary]) < per_backend:
                groups[primary].append((fam, params))
            i += 1
        families = [fp for g in groups.values() for fp in g]
        # warm every family on its home backend AND on the baseline node
        for fam, params in families:
            router.query(q_read, params, family=fam)
            solo.query(q_read, params, family=fam)

        counters = {"ok": 0, "fail": 0}
        lock = threading.Lock()

        def soak(target_router, seconds, kill_at=None):
            """One client thread per family group (the same client
            shape for baseline and fleet — only the ring size under
            the router differs)."""
            counters["ok"] = counters["fail"] = 0
            stop_at = time.perf_counter() + min(seconds, _remaining() - 40)
            killed = [False]

            def client(items):
                i = 0
                while time.perf_counter() < stop_at:
                    fam, params = items[i % len(items)]
                    i += 1
                    try:
                        target_router.query(q_read, params, family=fam)
                        with lock:
                            counters["ok"] += 1
                    except ServeError:
                        with lock:
                            counters["fail"] += 1
                    if kill_at is not None and not killed[0] and \
                            time.perf_counter() > kill_at:
                        with lock:
                            if not killed[0]:
                                killed[0] = True
                                children[-1][1].kill()  # never the owner

            ts = [threading.Thread(target=client, args=(g,), daemon=True)
                  for g in groups.values()]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            return counters["ok"], counters["fail"], dt

        ok1, _f1, dt1 = soak(solo, 2.5)
        qps_1 = ok1 / dt1
        okn, _fn, dtn = soak(router, 2.5)
        qps_n = okn / dtn
        scaling = qps_n / qps_1 if qps_1 else 0.0

        # kill-a-process soak: SIGKILL the last child mid-run; every
        # request must still complete (availability 1.0)
        kill_at = time.perf_counter() + 1.0
        oks, fails, _dts = soak(router, 2.5, kill_at=kill_at)
        availability = oks / (oks + fails) if (oks + fails) else 0.0

        # cross-process read-your-writes within the measured lag
        w = router.write("CREATE (z:Person {name: 'written-live', "
                         "age: 99})")
        lag_s = w["ship"]["lag_s"]
        q_check = ("MATCH (p:Person) WHERE p.age > 90 "
                   "RETURN p.name AS n ORDER BY n")
        digests = set()
        for name, state in router.stats()["backends"].items():
            if not state["live"]:
                continue
            rep = router._clients[name].call(
                "query", query=q_check, params={}, digest=True)
            assert any(r["n"] == "written-live" for r in rep["rows"]), name
            digests.add(rep["digest"])
        assert len(digests) == 1, "read-your-writes digest mismatch"

        telem = router._clients["p0"].call("telemetry")
        p99 = (telem.get("latency") or {}).get("p99_s")

        assert availability == 1.0, (oks, fails)
        if procs >= 4:
            assert scaling >= 3.0, (qps_1, qps_n)
        _result.update({
            "metric": f"fleet read QPS scaling, {procs} backend "
                      f"processes vs 1 (consistent-hash router, "
                      f"cache-resident families, "
                      f"{dwell_s * 1000:.0f}ms simulated device dwell "
                      f"per query, one backend SIGKILLed mid-soak, "
                      f"read-your-writes digest-exact, "
                      f"{'tpu' if on_tpu else 'cpu'})",
            "value": round(scaling, 3),
            "unit": "x QPS vs single process",
            "procs": procs,
            "fleet_qps_1": round(qps_1, 1),
            "fleet_qps_n": round(qps_n, 1),
            "availability": availability,
            "soak_requests": oks,
            "snapshot_lag_s": round(lag_s, 6),
            "snapshot_version": w["version"],
            "telemetry_p99": p99,
            "router": {k: v for k, v in registry.snapshot().items()
                       if k.startswith(("router.", "fleet."))},
            "vs_baseline": 0.0,
        })
        router.close()
        solo.close()
    finally:
        for _name, proc in children:
            proc.kill()
    _emit()


def run_durability_config(on_tpu: bool):
    """``bench.py durability`` — durable writes under owner loss
    (ISSUE 19).

    Spawns 3 REAL backend interpreters sharing one durable store
    (per-backend WAL + epoch-fenced lease), runs a write soak of
    idempotent per-id SETs with concurrent readers, SIGKILLs the write
    owner mid-soak, and measures:

      * recovery seconds — SIGKILL to the next acknowledged write (the
        router elects the peer with the longest replayed log, which
        claims the lease after the dead owner's TTL lapses);
      * zero acked-write loss — the surviving fleet's full-table digest
        equals a serial in-process oracle that applied exactly the
        acknowledged writes in order;
      * read availability 1.0 — every reader request through the soak
        (including the failover window) succeeds via ring retries;
      * the split-brain fence — the dead owner restarted as a zombie
        has its write frames refused with StaleEpoch (stale epoch AND
        no epoch), applying nothing;
      * sharded commits — CREATE/SET/DELETE through an in-process
        shard group is digest-equal to an unsharded versioned session.
    """
    import tempfile

    import caps_tpu
    from caps_tpu.obs.metrics import MetricsRegistry
    from caps_tpu.relational.session import result_digest
    from caps_tpu.relational.updates import VersionedGraph
    from caps_tpu.serve.errors import ServeError, StaleEpoch
    from caps_tpu.serve.fleet import (BackendSpec, rows_digest,
                                      spawn_backend)
    from caps_tpu.serve.router import FleetRouter, RouterConfig
    from caps_tpu.serve.shards import ShardGroup, ShardGroupConfig
    from caps_tpu.serve.wire import WireClient
    from caps_tpu.testing.factory import create_graph

    n_ids = 8
    create = "CREATE " + ", ".join(
        f"(p{i}:Person {{id: {i}, age: {20 + i}}})"
        for i in range(1, n_ids + 1))
    gspec = {"kind": "script", "create": create}
    q_write = "MATCH (p:Person {id: $id}) SET p.v = $v"
    q_read = ("MATCH (p:Person) WHERE p.age > $min "
              "RETURN p.name AS n ORDER BY n")
    q_all = ("MATCH (p:Person) RETURN p.id AS id, p.age AS age, "
             "p.v AS v ORDER BY id")

    store = tempfile.mkdtemp(prefix="caps-durability-")
    ttl_s = 1.0

    def durable_spec(name):
        return BackendSpec(name=name, backend="local", graph=gspec,
                           versioned=True, workers=2, max_queue=512,
                           durable_dir=store, wal_fsync="always",
                           lease_ttl_s=ttl_s)

    children = {}
    backends = {}
    router = None
    try:
        for name in ("d0", "d1", "d2"):
            proc, port = spawn_backend(durable_spec(name))
            children[name] = proc
            backends[name] = ("127.0.0.1", port)
        registry = MetricsRegistry()
        router = FleetRouter(backends, owner="d0",
                             config=RouterConfig(max_attempts=3,
                                                 failover_wait_s=15.0),
                             registry=registry)

        # -- write soak with a mid-run SIGKILL of the owner ------------
        soak_s = min(6.0, max(3.0, _remaining() - 120))
        kill_after_s = soak_s / 3.0
        reads = {"ok": 0, "fail": 0}
        stop = threading.Event()

        def reader(j):
            while not stop.is_set():
                try:
                    router.query(q_read, {"min": 20 + (j % n_ids)},
                                 family=f"fam-{j}")
                    reads["ok"] += 1
                except ServeError:
                    reads["fail"] += 1
                time.sleep(0.005)

        readers = [threading.Thread(target=reader, args=(j,), daemon=True)
                   for j in range(2)]
        for t in readers:
            t.start()

        acked = []
        killed_at = None
        recovered_at = None
        t0 = time.perf_counter()
        seq = 0
        while time.perf_counter() - t0 < soak_s and _remaining() > 60:
            now = time.perf_counter() - t0
            if killed_at is None and now >= kill_after_s:
                children["d0"].kill()  # SIGKILL, no drain, no fsync
                killed_at = time.perf_counter()
            params = {"id": 1 + seq % n_ids, "v": seq}
            try:
                # ship=False: peers catch up from the WAL at election
                # time; shipping every soak write would hide the log's
                # role in the recovery measurement
                router.write(q_write, params, ship=False)
            except ServeError:
                time.sleep(0.02)
                continue  # retry the SAME idempotent write until acked
            acked.append(params)
            if killed_at is not None and recovered_at is None:
                recovered_at = time.perf_counter()
            seq += 1
        stop.set()
        for t in readers:
            t.join()
        recovery_s = ((recovered_at - killed_at)
                      if killed_at and recovered_at else float("nan"))
        availability = (reads["ok"] / (reads["ok"] + reads["fail"])
                        if (reads["ok"] + reads["fail"]) else 0.0)

        # -- zero acked-write loss: digest parity vs a serial oracle ---
        oracle_session = caps_tpu.local_session(backend="local")
        oracle = VersionedGraph(oracle_session,
                                create_graph(oracle_session, create))
        for params in acked:
            oracle_session.cypher_on_graph(oracle, q_write, params)
        oracle_digest = rows_digest(
            oracle_session.cypher_on_graph(oracle, q_all).to_maps())
        survivor = router._clients[router.owner].call(
            "query", query=q_all, params={}, digest=True)
        digest_match = survivor["digest"] == oracle_digest

        # -- the fence: a restarted zombie owner applies nothing -------
        proc, port = spawn_backend(durable_spec("d0"))
        children["d0"] = proc
        router.write(q_write, {"id": 1, "v": seq}, ship=False)  # renew
        acked.append({"id": 1, "v": seq})
        fenced = []
        with WireClient("127.0.0.1", port) as zombie:
            version_before = zombie.call("ping")["snapshot_version"]
            for stale in (1, None):
                try:
                    fields = {} if stale is None else {"epoch": stale}
                    zombie.call("write", query=q_write,
                                params={"id": 2, "v": 10_000}, **fields)
                    fenced.append("APPLIED")
                except StaleEpoch:
                    fenced.append("StaleEpoch")
            version_after = zombie.call("ping")["snapshot_version"]
        zero_stale_writes = (fenced == ["StaleEpoch", "StaleEpoch"]
                            and version_after == version_before)

        # -- sharded commits: digest parity with an unsharded session --
        shard_writes = (
            ("CREATE (n:Person {id: 99, name: 'Zed', age: 1})", {}),
            ("MATCH (p:Person {id: 2}) SET p.age = 90", {}),
            ("MATCH (p:Person {id: 3}) DETACH DELETE p", {}),
        )
        s_sharded = caps_tpu.local_session(backend="local")
        group = ShardGroup(
            s_sharded, create_graph(s_sharded, create),
            ShardGroupConfig(name="g0", members=2,
                             partitions_per_member=2),
            registry=s_sharded.metrics_registry)
        s_plain = caps_tpu.local_session(backend="local")
        plain = VersionedGraph(s_plain, create_graph(s_plain, create))
        for q, p in shard_writes:
            group.execute(q, p)
            s_plain.cypher_on_graph(plain, q, p)
        sharded_parity = (
            result_digest(group.execute(q_all))
            == result_digest(s_plain.cypher_on_graph(plain, q_all)))
        group.close()

        assert availability == 1.0, reads
        assert digest_match, "acked writes lost across failover"
        assert zero_stale_writes, fenced
        assert sharded_parity, "sharded digest diverged from unsharded"
        _result.update({
            "metric": "durable-write failover: write owner SIGKILLed "
                      "mid-soak, peer with longest replayed WAL claims "
                      "the epoch-fenced lease (3 backend processes, "
                      "shared durable store, fsync=always, "
                      f"ttl={ttl_s:.0f}s, "
                      f"{'tpu' if on_tpu else 'cpu'})",
            "value": round(recovery_s, 3),
            "unit": "s from SIGKILL to next acked write",
            "acked_writes": len(acked),
            "acked_write_loss": 0 if digest_match else -1,
            "read_availability": availability,
            "reads_served": reads["ok"],
            "fence_probe": fenced,
            "new_owner": router.owner,
            "owner_epoch": router._owner_epoch,
            "failovers": registry.snapshot().get("router.failovers", 0),
            "sharded_parity": bool(sharded_parity),
            "vs_baseline": 0.0,
        })
    finally:
        if router is not None:
            router.close()
        for proc in children.values():
            proc.kill()
    _emit()


def run_chaos_config(on_tpu: bool, seed: int = 42):
    """``bench.py chaos`` — seeded chaos soak over a replicated-router
    fleet, with the ACTIVE ROUTER SIGKILLed mid-soak (ISSUE 20).

    Spawns 3 REAL durable backend interpreters + 2 REAL router
    interpreters (serve/ha.py) sharing one durable store, composes a
    deterministic fault schedule from ``--seed`` (client-side wire
    faults from the locked patch points, plus the pinned headline
    ``kill_router_active`` event), soaks reads and idempotent writes
    through a :class:`RouterSet`, and reports:

      * read availability + recovery seconds (SIGKILL of the active
        router to the next served read — the standby takes over within
        ~1 router-lease TTL);
      * zero acked-write loss — digest parity between the surviving
        fleet and a serial in-process oracle of exactly the acked
        statements;
      * the zombie-ROUTER fence — write frames stamped with the dead
        active's router epoch are refused with StaleEpoch (with and
        without a valid owner epoch), applying nothing;
      * hedged reads — a seeded ``slow_backend`` straggler on the
        primary ring node, read p99 hedging-on vs hedging-off on the
        same injection budget, hedge win rate, no result duplication;
      * schedule determinism — composing the same seed twice yields an
        identical schedule digest (printed for cross-run comparison).
    """
    import tempfile

    import caps_tpu
    from caps_tpu.obs.metrics import MetricsRegistry
    from caps_tpu.relational.updates import VersionedGraph
    from caps_tpu.serve.errors import ServeError, StaleEpoch
    from caps_tpu.serve.fleet import (BackendSpec, rows_digest,
                                      spawn_backend)
    from caps_tpu.serve.ha import RouterSet, RouterSpec, spawn_router
    from caps_tpu.serve.router import FleetRouter, RouterConfig
    from caps_tpu.serve.wire import WireClient
    from caps_tpu.testing.chaos import (ChaosInvariants, ChaosRunner,
                                        ChaosSchedule, slow_backend)
    from caps_tpu.testing.factory import create_graph

    n_ids = 8
    create = "CREATE " + ", ".join(
        f"(p{i}:Person {{id: {i}, age: {20 + i}}})"
        for i in range(1, n_ids + 1))
    gspec = {"kind": "script", "create": create}
    q_write = "MATCH (p:Person {id: $id}) SET p.v = $v"
    q_read = ("MATCH (p:Person) WHERE p.age > $min "
              "RETURN p.name AS n ORDER BY n")
    q_all = ("MATCH (p:Person) RETURN p.id AS id, p.age AS age, "
             "p.v AS v ORDER BY id")

    store = tempfile.mkdtemp(prefix="caps-chaos-")
    ttl_s = 1.0
    soak_s = min(6.0, max(3.0, _remaining() - 150))
    registry = MetricsRegistry()

    # same seed ⇒ identical schedule digest, attested before the soak
    schedule = ChaosSchedule.compose(
        seed, soak_s, n_events=6, headline="kill_router_active",
        registry=registry)
    digest_stable = (schedule.digest() == ChaosSchedule.compose(
        seed, soak_s, n_events=6, headline="kill_router_active",
        registry=registry).digest())

    backend_children = {}
    router_children = {}
    backends = {}
    routers = {}
    rset = None
    try:
        for name in ("d0", "d1", "d2"):
            proc, port = spawn_backend(BackendSpec(
                name=name, backend="local", graph=gspec, versioned=True,
                workers=2, max_queue=512, durable_dir=store,
                wal_fsync="always", lease_ttl_s=ttl_s))
            backend_children[name] = proc
            backends[name] = ("127.0.0.1", port)
        for name in ("r0", "r1"):
            proc, port = spawn_router(RouterSpec(
                name=name, backends=backends, durable_dir=store,
                owner="d0", lease_ttl_s=ttl_s, poll_s=0.1,
                failover_wait_s=15.0))
            router_children[name] = proc
            routers[name] = ("127.0.0.1", port)
        rset = RouterSet(routers, wait_s=10.0, registry=registry)
        deadline_poll = time.perf_counter() + 5.0
        while rset.active() is None:
            if time.perf_counter() > deadline_poll:
                raise RuntimeError("no router became active")
            time.sleep(0.05)

        invariants = ChaosInvariants(registry=registry)
        killed = {"name": None, "at": None, "epoch": None}
        recovered_at = None

        def kill_active_router(_ev):
            name = rset.active()
            if name is None or name not in router_children:
                name = next(iter(router_children))
            router_children[name].kill()  # SIGKILL: no drain, no byes
            killed["name"] = name
            killed["at"] = time.perf_counter()

        runner = ChaosRunner(
            schedule, actions={"kill_router_active": kill_active_router},
            registry=registry)

        reads = {"ok": 0, "fail": 0}
        stop = threading.Event()

        def reader(j):
            while not stop.is_set():
                try:
                    out = rset.query(q_read, {"min": 20 + (j % n_ids)},
                                     family=f"fam-{j}", wait_s=4.0)
                    reads["ok"] += 1
                    # version monotonicity is per BACKEND (a failover
                    # hop may land on a lagging peer — that's not a
                    # backend time-travelling), so key on both
                    invariants.note_read(
                        f"reader-{j}@{out.get('backend')}", True,
                        version=out.get("snapshot_version"))
                except ServeError:
                    reads["fail"] += 1
                    invariants.note_read(f"reader-{j}", False)
                time.sleep(0.005)

        readers = [threading.Thread(target=reader, args=(j,), daemon=True)
                   for j in range(2)]
        for t in readers:
            t.start()

        acked = []
        t0 = time.perf_counter()
        seq = 0
        with runner:
            while time.perf_counter() - t0 < soak_s and _remaining() > 90:
                runner.poll(time.perf_counter() - t0)
                params = {"id": 1 + seq % n_ids, "v": seq}
                try:
                    rset.write(q_write, params, ship=True, wait_s=4.0)
                except ServeError:
                    time.sleep(0.02)
                    continue  # retry the SAME idempotent write until acked
                acked.append(params)
                invariants.note_write_ack()
                if killed["at"] is not None and recovered_at is None:
                    recovered_at = time.perf_counter()
                seq += 1
            runner.poll(soak_s)  # fire any stragglers (incl. the kill)
            stop.set()
            for t in readers:
                t.join()
        recovery_s = ((recovered_at - killed["at"])
                      if killed["at"] and recovered_at else float("nan"))

        # -- zero acked-write loss: digest parity vs a serial oracle ---
        oracle_session = caps_tpu.local_session(backend="local")
        oracle = VersionedGraph(oracle_session,
                                create_graph(oracle_session, create))
        for params in acked:
            oracle_session.cypher_on_graph(oracle, q_write, params)
        oracle_digest = rows_digest(
            oracle_session.cypher_on_graph(oracle, q_all).to_maps())
        stats = rset.stats()
        owner = stats["owner"]
        survivor = WireClient(*backends[owner])
        observed = survivor.call("query", query=q_all, params={},
                                 digest=True)["digest"]

        # -- the zombie-ROUTER fence: the dead active's epoch stamps
        #    are refused by the backends, applying nothing ------------
        surviving_epoch = int(stats.get("epoch") or 0)
        stale_router_epoch = max(1, surviving_epoch - 1)
        owner_epoch = None
        lease_rec = None
        with open(os.path.join(store, "lease.json")) as f:
            lease_rec = json.load(f)
        owner_epoch = int(lease_rec["epoch"])
        fenced = []
        version_before = survivor.call("ping")["snapshot_version"]
        for fields in ({"router_epoch": stale_router_epoch},
                       {"router_epoch": stale_router_epoch,
                        "epoch": owner_epoch}):
            try:
                survivor.call("write", query=q_write,
                              params={"id": 2, "v": 10_000}, **fields)
                fenced.append("APPLIED")
            except StaleEpoch:
                fenced.append("StaleEpoch")
        version_after = survivor.call("ping")["snapshot_version"]
        survivor.close()
        zero_zombie_writes = (fenced == ["StaleEpoch", "StaleEpoch"]
                              and version_after == version_before)
        for _ in range(2):
            invariants.note_fence(zero_zombie_writes)

        report = invariants.report(
            availability_floor=0.5, oracle_digest=oracle_digest,
            observed_digest=observed)

        # -- hedged reads vs a seeded straggler ------------------------
        prim_key = FleetRouter.routing_key("default", "fam-hedge", q_read)
        hedge_stats = {}
        for label, hedge_on in (("off", False), ("on", True)):
            hreg = MetricsRegistry()
            hrouter = FleetRouter(
                backends, owner=owner,
                config=RouterConfig(
                    hedge_reads=hedge_on, hedge_max_fraction=1.0,
                    hedge_delay_s=0.01),
                registry=hreg)
            primary = hrouter.ring.preference(prim_key)[0]
            lat = []
            n_reads, n_slow = 40, 20
            with slow_backend(backends[primary][1], 0.08,
                              n_times=n_slow, every_n=2):
                for k in range(n_reads):
                    ts = time.perf_counter()
                    hrouter.query(q_read, {"min": 21},
                                  family="fam-hedge")
                    lat.append(time.perf_counter() - ts)
            lat.sort()
            snap = hreg.snapshot()
            hedge_stats[label] = {
                "p99_ms": round(lat[int(0.99 * (len(lat) - 1))] * 1e3, 2),
                "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                "hedges": snap.get("router.hedges", 0),
                "hedge_wins": snap.get("router.hedge_wins", 0),
            }
            hrouter.close()
        hedge_improved = (hedge_stats["on"]["p99_ms"]
                          < hedge_stats["off"]["p99_ms"])

        assert digest_stable, "same seed composed different schedules"
        assert report["ok"], report
        assert zero_zombie_writes, fenced
        _result.update({
            "metric": "router HA chaos soak: active router SIGKILLed "
                      "mid-schedule, standby takes the epoch-fenced "
                      "router lease (3 backend + 2 router processes, "
                      f"shared durable store, ttl={ttl_s:.0f}s, "
                      f"seed={seed}, "
                      f"{'tpu' if on_tpu else 'cpu'})",
            "value": round(recovery_s, 3),
            "unit": "s from router SIGKILL to next acked write",
            "schedule_digest": schedule.digest(),
            "schedule_events": len(schedule.events),
            "chaos_events_applied": len(runner.applied),
            "killed_router": killed["name"],
            "read_availability": round(report["availability"], 4),
            "reads_served": reads["ok"],
            "acked_writes": len(acked),
            "acked_write_loss": 0 if report["checks"].get(
                "acked_write_parity") else -1,
            "fence_probe": fenced,
            "invariants": report["checks"],
            "hedge_off_p99_ms": hedge_stats["off"]["p99_ms"],
            "hedge_on_p99_ms": hedge_stats["on"]["p99_ms"],
            "hedges": hedge_stats["on"]["hedges"],
            "hedge_wins": hedge_stats["on"]["hedge_wins"],
            "hedge_win_rate": round(
                hedge_stats["on"]["hedge_wins"]
                / max(1, hedge_stats["on"]["hedges"]), 3),
            "hedge_p99_improved": bool(hedge_improved),
            "vs_baseline": 0.0,
        })
    finally:
        if rset is not None:
            rset.close()
        for proc in router_children.values():
            proc.kill()
        for proc in backend_children.values():
            proc.kill()
    _emit()


def main():
    import numpy as np
    if len(sys.argv) > 1 and sys.argv[1] == "serve" \
            and "--cold-child" in sys.argv:
        # the fresh process of `serve --cold-process` (CPU-only: the
        # platform comes with the parent's environment)
        i = sys.argv.index("--cold-child")
        return run_cold_child(sys.argv[i + 1], int(sys.argv[i + 2]),
                              int(sys.argv[i + 3]), int(sys.argv[i + 4]))
    # before the guards: a refused run prints no result line at all
    on_tpu = _platform() == "tpu"
    if on_tpu and "--cold-process" in sys.argv:
        sys.exit("bench: serve --cold-process is CPU-only — its child "
                 "needs a device and this process holds the chip")
    _install_guards()
    if len(sys.argv) > 1 and sys.argv[1] == "triangle":
        return run_triangle_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "ldbc":
        return run_ldbc_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        if "--cache" in sys.argv:
            return run_serve_cache_config(on_tpu)
        if "--devices" in sys.argv:
            i = sys.argv.index("--devices")
            devices_n = int(sys.argv[i + 1]) if i + 1 < len(sys.argv) else 2
            return run_serve_devices_config(on_tpu, devices_n)
        if "--shards" in sys.argv:
            i = sys.argv.index("--shards")
            shards_n = int(sys.argv[i + 1]) if i + 1 < len(sys.argv) else 2
            return run_serve_shards_config(on_tpu, shards_n)
        return run_serve_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "faults":
        return run_faults_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "updates":
        return run_updates_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "plan":
        return run_plan_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "cyclic":
        return run_cyclic_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "algo":
        return run_algo_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        procs_n = 4
        if "--procs" in sys.argv:
            i = sys.argv.index("--procs")
            procs_n = int(sys.argv[i + 1]) if i + 1 < len(sys.argv) else 4
        return run_fleet_config(on_tpu, procs_n)
    if len(sys.argv) > 1 and sys.argv[1] == "durability":
        return run_durability_config(on_tpu)
    if len(sys.argv) > 1 and sys.argv[1] == "chaos":
        seed = 42
        if "--seed" in sys.argv:
            i = sys.argv.index("--seed")
            seed = int(sys.argv[i + 1]) if i + 1 < len(sys.argv) else 42
        return run_chaos_config(on_tpu, seed)

    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession

    rng = np.random.RandomState(42)
    # Scaled config 1, the same on every platform (a CPU smoke run sets
    # BENCH_N_PEOPLE/BENCH_N_EDGES to fit its budget)
    n_people, n_edges, n_seeds, iters = 1_000_000, 5_000_000, 100, 10
    n_people = int(os.environ.get("BENCH_N_PEOPLE", n_people))
    n_edges = int(os.environ.get("BENCH_N_EDGES", n_edges))

    tpu_session = TPUCypherSession()
    graph, src, dst, names, _ = build_graph(tpu_session, n_people, n_edges,
                                         n_seeds, rng)
    t0 = time.perf_counter()
    first = graph.cypher(QUERY)  # warms every compile cache on this path
    expected = first.records.to_maps()[0]["c"]
    compile_s = time.perf_counter() - t0
    # Roofline numerator from the RECORDING run: warm replays execute no
    # per-operator code, so their op_metrics (hence bytes) are empty.
    first_bytes = first.metrics.get("bytes_touched", 0)
    work = edges_joined(src, dst, names)
    _result.update({
        "metric": "edges-joined/sec, 2-hop foaf MATCH (compile-only run)",
        "value": round(work / compile_s, 1),
        "compile_s": round(compile_s, 2),
    })
    rtt_floor = measure_rtt_floor()
    med, done = time_fn(lambda: run_query(graph), iters=iters)
    per_query = work / med
    # Roofline column (round-4 VERDICT item 2): bytes the operators pull
    # through memory per query and the achieved bandwidth vs the chip's
    # HBM peak (v5e ~819 GB/s) — the utilization number that makes
    # kernel-quality regressions visible behind transport noise.
    bytes_touched = graph.cypher(QUERY).metrics.get("bytes_touched", 0) \
        or first_bytes
    _result["bytes_touched"] = int(bytes_touched)
    if on_tpu:  # device metrics exist only for a device
        kind = _result["device_kind"]
        if kind not in HBM_PEAK_GBPS:
            sys.exit(f"bench: no HBM peak for device_kind {kind!r}; "
                     f"add it to HBM_PEAK_GBPS with its source")
        achieved_gbps = bytes_touched / med / 1e9 if med else 0.0
        _result.update({
            "achieved_gbps": round(achieved_gbps, 3),
            "hbm_frac": round(achieved_gbps / HBM_PEAK_GBPS[kind], 5),
        })
    # Pipelined throughput: each query fully executes on device; results
    # are read back in one batched transfer (the per-read round trip —
    # rtt_floor_s — dominates sequential mode on remote transports).
    # Plan cache OFF here: this is the honest un-amortized planning
    # number the prepared mode below is compared against in-run.
    pipe_s = None
    if _remaining() > 30:
        tpu_session.plan_cache.enabled = False
        try:
            pipe_s = run_pipelined(graph, expected, batch=10)
        finally:
            tpu_session.plan_cache.enabled = True
    # Prepared/repeat-query mode: same pipelined protocol, ONE prepared
    # statement with rotating $seed bindings — planning amortizes via
    # the session plan cache (hit rate reported); the same workload is
    # also measured with the cache off for the in-run comparison.
    prep_s, prep_uncached_s, prep_info = None, None, {}
    if _remaining() > 25:
        seen: set = set()
        seeds = []
        for nm in names:
            if nm not in seen:
                seen.add(nm)
                seeds.append(nm)
            if len(seeds) == 4:
                break
        if "Alice" not in seeds:
            seeds[0] = "Alice"
        exp = expected_paths(src, dst, names, seeds)
        prep_s, prep_uncached_s, prep_info = run_prepared_pipelined(
            tpu_session, graph, seeds, exp, batch=10)
    mode = "pipelined x10" if pipe_s is not None else "sequential"
    value = work / (pipe_s if pipe_s is not None else med)
    fallbacks = tpu_session.fallback_count
    _result.update({
        "metric": f"edges-joined/sec, 2-hop foaf MATCH, {mode} "
                  f"({n_people} nodes, {n_edges} edges, "
                  f"{'tpu' if on_tpu else 'cpu'}, "
                  f"paths={expected}, device_fallbacks={fallbacks}, "
                  f"iters={done})",
        "value": round(value, 1),
        "steady_p50_s": round(med, 4),
        "sequential_edges_per_s": round(per_query, 1),
        "rtt_floor_s": round(rtt_floor, 5),
    })
    if pipe_s is not None:
        _result["pipelined_per_query_s"] = round(pipe_s, 5)
    if prep_s is not None:
        _result["pipelined_prepared_per_query_s"] = round(prep_s, 5)
        _result["pipelined_param_uncached_per_query_s"] = \
            round(prep_uncached_s, 5)
        _result["plan_cache_speedup"] = \
            round(prep_uncached_s / prep_s, 3) if prep_s else 0.0
        _result.update(prep_info)

    # Oracle baseline on a subsample, scaled per-edge (skip if the
    # deadline is close — the device number is the one that matters).
    vs_baseline = 0.0
    if _remaining() > 20:
        rng2 = np.random.RandomState(42)
        local_session = LocalCypherSession()
        b_people, b_edges, b_seeds = 2_000, 10_000, 2
        lgraph, lsrc, ldst, lnames, _ = build_graph(local_session, b_people,
                                                 b_edges, b_seeds, rng2)
        run_query(lgraph)  # warm
        t0 = time.perf_counter()
        run_query(lgraph)
        local_t = time.perf_counter() - t0
        local_rate = edges_joined(lsrc, ldst, lnames) / local_t
        vs_baseline = value / local_rate if local_rate else 0.0
    _result["vs_baseline"] = round(vs_baseline, 2)
    _emit()


if __name__ == "__main__":
    main()

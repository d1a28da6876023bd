"""chip_smoke.py — the quickest proof that the query path runs on the chip.

One process, one TPU chip (``--chips 4``: one four-chip host), the
engine's normal entry points at the size the benchmark's config 1 uses on
a chip: a 1,000,000-person / 5,000,000-edge friend-of-friend graph made
from ``--seed`` (caps_tpu/datasets/foaf.py), ingested into a default
``TPUCypherSession``, queried through ``graph.cypher`` and served through
``QueryServer``.  Every answer is compared with a numpy oracle computed
from the raw edge arrays.

Phases, each printing one JSON line before the final one:

    device   platform must be ``tpu``; native host runtime must be built
    ingest   build the graph; device bytes resident
    count    config 1 as the default planner runs it (at this size its
             cost model prices the 'Alice' seed at one row and takes the
             join cascade, not the count push-down)
    paths    two join-shaped reads: Expand materialisation, group-by,
             order-by — the path every LDBC read takes
    serve    the same reads through QueryServer from several threads
    assert-no-fallback
             nothing ran on the host oracle, and every Pallas kernel
             family the static table (ops/kernel_table.py) turns on for
             this device was launched
    count-pushdown
             config 1 as ONE fused SpMV program (CountPattern) — a second
             session with ``use_cost_model=False``, whose fixed heuristic
             always pushes the count down; the program every older chip
             row measured

``--chips 4`` runs none of those after ``device``; it builds the same
graph in ``mesh_shape=(4,)`` sessions and checks the sharded count
(distributed joins by default, the ring schedule with the push-down) and
paths against the same oracles.

The script never chooses a platform: without a TPU it exits non-zero at
``device`` and prints no result line.  Smaller ``--people``/``--edges``
are for rehearsals; the sizes that ran are printed.

Last line of stdout on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from typing import Callable, Dict, List, Optional


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def timed(fn: Callable[[], object]):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import importlib.metadata as md

    import jax
    from caps_tpu import native

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    emit("device", **device, **versions, native=native.available(),
         native_build_error=native.build_error,
         native_so=(os.path.basename(native.lib.__file__)
                    if native.available() else None),
         native_source_hash=native.source_hash(),
         compile_cache_dir_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    check(device["platform"] == "tpu",
          f"no accelerator: jax.devices() is {device['platform']}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, found {len(devices)}")
    check(native.available(),
          f"native host runtime not built: {native.build_error}")
    return device


class Loaded:
    """What ``ingest`` leaves for the query phases."""

    def __init__(self, session, graph, src, dst, names, ages):
        self.session, self.graph = session, graph
        self.src, self.dst, self.names, self.ages = src, dst, names, ages
        # 'Alice' fans out past the 1,024 bucket (Expand kernel); the two
        # single-person bindings land in the 256 bucket (twin by shape)
        singles: List[str] = []
        for s in src:
            nm = names[int(s)]
            if nm != "Alice" and nm not in singles:
                singles.append(nm)
            if len(singles) == 2:
                break
        self.seeds = [singles[0], "Alice", singles[1]]


def phase_ingest(people: int, edges: int, seed: int, config=None,
                 phase: str = "ingest") -> Loaded:
    import jax
    import numpy as np
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.datasets import foaf

    n_seeds = min(100, max(1, people // 20))
    session = TPUCypherSession(config=config)
    built, seconds = timed(lambda: foaf.build_graph(
        session, people, edges, n_seeds, np.random.RandomState(seed)))
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase, seconds=seconds, people=people, edges=edges,
         alice_seeds=n_seeds, seed=seed,
         device_bytes_in_use=stats.get("bytes_in_use"),
         device_peak_bytes=stats.get("peak_bytes_in_use"),
         device_bytes_limit=stats.get("bytes_limit"),
         string_pool=len(session.backend.pool))
    return Loaded(session, *built)


def _count_strategy(result) -> Optional[str]:
    for m in (result.metrics or {}).get("operators", ()):
        if m.get("op") == "CountPattern":
            return m.get("strategy")
    return None


def phase_count(ld: Loaded, phase: str = "count", warm: int = 5,
                strategies: Optional[tuple] = None) -> None:
    """Config 1, cold then ``warm`` times; ``strategies``: the
    CountPattern strategies the plan must have taken (None: any plan)."""
    from caps_tpu.datasets import foaf

    want = foaf.expected_paths(ld.src, ld.dst, ld.names, ["Alice"])["Alice"]
    first, cold_s = timed(lambda: ld.graph.cypher(foaf.QUERY))
    got, fetch_s = timed(lambda: first.records.to_maps()[0]["c"])
    strategy = _count_strategy(first)
    warm_s = []
    for _ in range(warm):
        c, s = timed(lambda: ld.graph.cypher(foaf.QUERY)
                     .records.to_maps()[0]["c"])
        check(c == want, f"warm count {c} != oracle {want}")
        warm_s.append(s)
    emit(phase, rows=1, count=got, oracle=want,
         strategy=strategy or "join-cascade",
         cold_s=cold_s + fetch_s, warm_median_s=statistics.median(warm_s),
         warm_s=warm_s, checked="count == numpy oracle, cold and warm")
    check(got == want, f"count {got} != oracle {want}")
    check(strategies is None or strategy in strategies,
          f"count ran as {strategy or 'join cascade'}, not {strategies}")


def _path_cases(ld: Loaded):
    from caps_tpu.datasets import foaf
    raw = (ld.src, ld.dst, ld.names, ld.ages)
    return [("age_top", foaf.AGE_TOP_QUERY,
             lambda s: foaf.expected_age_top(*raw, s)),
            ("age_split", foaf.AGE_SPLIT_QUERY,
             lambda s: foaf.expected_age_split(*raw, s))]


def phase_paths(ld: Loaded, phase: str = "paths", warm: int = 3
                ) -> Dict[tuple, list]:
    """Each query: three bindings in order (record small, re-record at
    'Alice', generic replay), then ``warm`` timed passes over all three.
    Returns {(query name, seed): rows} for the serve phase."""
    answers: Dict[tuple, list] = {}
    runs, rows_total = [], 0
    fused = ld.session.fused
    for name, query, oracle in _path_cases(ld):
        for seed in ld.seeds:
            want = oracle(seed)
            got, s = timed(lambda: ld.graph.cypher(
                query, {"seed": seed}).records.to_maps())
            runs.append({"query": name, "seed": seed, "seconds": s,
                         "rows": len(got), "fused_mode": fused.last_mode})
            check(got == want, f"{name}[{seed}]: {got} != oracle {want}")
            answers[(name, seed)] = got
            rows_total += len(got)
    warm_s = []
    for _ in range(warm):
        for name, query, _oracle in _path_cases(ld):
            for seed in ld.seeds:
                got, s = timed(lambda: ld.graph.cypher(
                    query, {"seed": seed}).records.to_maps())
                check(got == answers[(name, seed)],
                      f"warm {name}[{seed}] changed its answer")
                warm_s.append(s)
    emit(phase, rows=rows_total, first_runs=runs,
         first_runs_total_s=sum(r["seconds"] for r in runs),
         warm_median_s=statistics.median(warm_s), warm_runs=len(warm_s),
         warm_max_s=max(warm_s),
         checked="every binding == numpy oracle (r1 != r2 honoured)")
    return answers


def phase_serve(ld: Loaded, answers: Dict[tuple, list], threads: int = 4,
                per_thread: int = 8) -> None:
    from caps_tpu.serve import QueryServer

    cases = [(name, query, seed) for name, query, _o in _path_cases(ld)
             for seed in ld.seeds]
    server = QueryServer(ld.session, graph=ld.graph)
    handles: List[tuple] = []
    lock = threading.Lock()

    def client(i: int) -> None:
        for j in range(per_thread):
            name, query, seed = cases[(i * per_thread + j) % len(cases)]
            h = server.submit(query, {"seed": seed})
            with lock:
                handles.append((name, seed, h))

    t0 = time.perf_counter()
    try:
        workers = [threading.Thread(target=client, args=(i,))
                   for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(600)
            check(not w.is_alive(), "a serve client thread did not finish")
        for name, seed, h in handles:
            rows = h.rows(timeout=600)
            check(rows == answers[(name, seed)],
                  f"served {name}[{seed}]: {rows} != sequential answer")
    finally:
        server.shutdown()
    seconds = time.perf_counter() - t0
    stats = server.stats()
    emit("serve", seconds=seconds, requests=len(handles),
         threads=threads, completed=stats.get("completed"),
         batches=stats.get("batches"),
         batch_size_max=stats.get("batch_size.max"),
         checked="every handle's rows() == sequential answer; "
                 "clean shutdown")
    check(len(handles) == threads * per_thread, "requests went missing")


def phase_no_fallback(ld: Loaded, device: dict) -> None:
    be = ld.session.backend
    snap = ld.session.metrics_snapshot()
    launches = {f: snap[f"backend.kernel.{f}"] for f in be.kernel_launches}
    on = [f for f in launches if be.use_kernel(f)]
    emit("assert-no-fallback", fallback_count=ld.session.fallback_count,
         fallback_reasons=list(be.fallback_reasons),
         kernel_families_on=on, kernel_launches=launches,
         kernels_compiled=bool(snap["backend.kernels_compiled"]),
         size_syncs=snap["backend.syncs"],
         fused_recordings=snap["fused.recordings"],
         fused_generic_replays=snap["fused.generic_replays"],
         checked="no host fallback; every family the static table turns "
                 "on was launched, compiled on tpu")
    check(ld.session.fallback_count == 0, "host fallbacks happened")
    check(be.fallback_reasons == [], f"fallbacks: {be.fallback_reasons}")
    check(bool(snap["backend.kernels_compiled"])
          == (device["platform"] == "tpu"),
          "kernels ran interpreted on a tpu")
    for f in on:
        check(launches[f] > 0, f"kernel family {f!r} is on but never ran")


def phase_count_pushdown(people: int, edges: int, seed: int,
                         mesh_shape: tuple = ()) -> None:
    """Config 1 through the count push-down.  The default planner's cost
    model routes around it at this size (see ``count``), so this session
    turns the model off: the fixed heuristic always pushes down."""
    from caps_tpu.okapi.config import EngineConfig

    prefix = "sharded-" if mesh_shape else ""
    ld = phase_ingest(people, edges, seed, phase=f"{prefix}pushdown-ingest",
                      config=EngineConfig(mesh_shape=mesh_shape,
                                          use_cost_model=False))
    phase_count(ld, phase=f"{prefix}count-pushdown",
                strategies=("ring",) if mesh_shape else ("fused-spmv",))
    check(ld.session.fallback_count == 0,
          f"host fallbacks: {ld.session.backend.fallback_reasons}")


def phase_sharded(people: int, edges: int, seed: int, chips: int) -> None:
    """The ``--chips`` path: same graph, ``mesh_shape=(chips,)``."""
    from caps_tpu.okapi.config import EngineConfig

    ld = phase_ingest(people, edges, seed,
                      config=EngineConfig(mesh_shape=(chips,)),
                      phase="sharded-ingest")
    be = ld.session.backend
    placed = {}
    for rt in ld.graph.rel_tables:
        for cname, col in rt.table._cols.items():
            placed[cname] = sorted({str(s.device)
                                    for s in col.data.addressable_shards})
    emit("sharded-placement", mesh=list(be.mesh.devices.shape),
         edge_column_devices=placed,
         checked=f"every edge column sharded over {chips} distinct devices")
    for cname, devs in placed.items():
        check(len(devs) == chips,
              f"edge column {cname} sits on {len(devs)} devices: {devs}")
    phase_count(ld, phase="sharded-count", warm=3)
    phase_paths(ld, phase="sharded-paths", warm=1)
    snap = ld.session.metrics_snapshot()
    emit("sharded-collectives", dist_joins=snap["backend.dist_joins"],
         broadcast_joins=snap["backend.broadcast_joins"],
         salted_joins=snap["backend.salted_joins"],
         ici_bytes=snap["backend.ici_bytes"],
         ici_payload_bytes=snap["backend.ici_payload_bytes"],
         fallback_count=ld.session.fallback_count,
         fallback_reasons=list(be.fallback_reasons),
         checked="hand-scheduled distributed joins fired, bytes crossed "
                 "ICI, no host fallback")
    check(snap["backend.dist_joins"] + snap["backend.broadcast_joins"] >= 1,
          "no distributed join fired")
    check(snap["backend.ici_bytes"] > 0, "no ICI bytes were counted")
    check(ld.session.fallback_count == 0,
          f"host fallbacks: {be.fallback_reasons}")
    phase_count_pushdown(people, edges, seed, mesh_shape=(chips,))


# ---------------------------------------------------------------------------


def run(args, device_phase: Callable[[int], dict] = phase_device) -> dict:
    """All phases in order; returns the device dict for the last line.
    ``device_phase`` is a parameter so a CPU rehearsal (tests) can stand
    in for the device check from outside — the script has no option that
    skips it."""
    device = device_phase(args.chips)
    if args.chips > 1:
        phase_sharded(args.people, args.edges, args.seed, args.chips)
        return device
    ld = phase_ingest(args.people, args.edges, args.seed)
    phase_count(ld)
    answers = phase_paths(ld)
    phase_serve(ld, answers)
    phase_no_fallback(ld, device)
    phase_count_pushdown(args.people, args.edges, args.seed)
    return device


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--people", type=int, default=1_000_000)
    ap.add_argument("--edges", type=int, default=5_000_000)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded path, on a four-chip host")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = run(args)
    except SmokeFailure as ex:
        emit("failed", ok=False, error=str(ex))
        return 1
    emit("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

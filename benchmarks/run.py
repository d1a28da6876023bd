#!/usr/bin/env python3
"""The benchmark's command: one cell of BENCHMARK.json, one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run: read the cell's files -> make the data from ``--seed`` -> build
the system under test (a default session, the graph, a ``QueryServer``)
-> warm up THROUGH ``QueryServer.submit`` (the entry the window drives;
``graph.cypher`` is never called, see README.md) -> stamp the set-up time ->
drive the closed-loop clients for ``--seconds`` -> shut the server down
-> compute the numpy reference and compare every answer of warm-up and
window -> print one JSON line.

Nothing here names a cell, a configuration, a traffic mix or a metric:
they are files found by the names in BENCHMARK.json (README.md).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can stamp it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Callable, Dict, List, Optional, Sequence, Tuple  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: seconds into the window at which the traced interval starts, and its
#: length: a few seconds of steady state, not the whole window
TRACE_START_S = 1.0
TRACE_SECONDS = 4.0
#: reads per client in warm-up's concurrent pass
WARM_CONCURRENT_PER_CLIENT = 8

now = time.perf_counter


class BenchError(Exception):
    """The run cannot be made: exit non-zero, print no result."""


# -- the cell's files ---------------------------------------------------------


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(bench_dir: str, rel: str):
    """Import ``<bench_dir>/<rel>.py`` by path (no sys.path entry needed,
    so a copy of the benchmark elsewhere loads its own files)."""
    path = os.path.join(bench_dir, rel + ".py")
    name = "bench_" + rel.replace("/", "_").replace("-", "_")
    loaded = sys.modules.get(name)
    if loaded is not None and getattr(loaded, "__file__", None) == path:
        return loaded
    if not os.path.exists(path):
        raise BenchError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root: str, workload: str):
        spec = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.bench_dir = os.path.join(root, spec["paths"][0])
        cfg = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = _read_json(os.path.join(root, cfg["file"]))
        self.traffic = _read_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.generator = _module(
            self.bench_dir, "generators/" + self.config["generator"])
        mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    def read_metrics(self, kind: str, metrics: Sequence[dict],
                     ctx: dict) -> Dict[str, dict]:
        """Each metric through the reader its file under ``<kind>/`` names;
        a reader that returns None leaves its metric out (``run_cell``
        then refuses the run: see ``silent``)."""
        out = {}
        for m in metrics:
            how = _read_json(os.path.join(
                self.bench_dir, kind, m["name"] + ".json"))
            mod, fn = how["reader"].rsplit(".", 1)
            value = getattr(_module(self.bench_dir, mod), fn)(
                ctx, **how.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


# -- the device ---------------------------------------------------------------


def check_device(cell: Cell) -> list:
    """The chips this cell runs on; raises where JAX has no accelerator
    or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no accelerator: jax.devices() is "
                         f"{devices[0].platform}")
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chip(s), "
                         f"found {len(devices)}")
    return devices


def device_report(devices: list) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


# -- the system under test ----------------------------------------------------


class Served:
    """A default ``TPUCypherSession``, the graph, and a ``QueryServer`` in
    this process, with the settings the configuration file states."""

    def __init__(self, config: dict, generator, data):
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        try:
            import caps_tpu
        except ImportError as ex:
            raise BenchError(f"the program is not in this checkout: {ex}")
        if not os.path.abspath(caps_tpu.__file__).startswith(ROOT + os.sep):
            raise BenchError(f"caps_tpu came from {caps_tpu.__file__}, "
                             f"not from this checkout")
        from caps_tpu.backends.tpu.session import TPUCypherSession
        from caps_tpu.okapi.config import EngineConfig
        from caps_tpu.serve import QueryServer
        from caps_tpu.serve.server import ServerConfig
        self.session = TPUCypherSession(
            config=EngineConfig(**config.get("engine", {})))
        self.graph = generator.build_graph(self.session, data)
        self.server = QueryServer(
            self.session, graph=self.graph,
            config=ServerConfig(**config.get("server", {})))

    def submit(self, text: str, params: dict):
        return self.server.submit(text, params)

    def counters(self) -> Dict[str, float]:
        snap = dict(self.session.metrics_snapshot())
        snap.update({"serve." + k: v for k, v in self.server.stats().items()})
        return {k: v for k, v in snap.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    def close(self) -> None:
        self.server.shutdown()


# -- load ---------------------------------------------------------------------

Pair = Tuple[str, dict]                 # query name, parameters
#: pair index, submitted at, answered at, rows or None, error or None
Read = Tuple[int, float, float, Optional[list], Optional[str]]


def one_read(system, texts: Dict[str, str], pairs: Sequence[Pair], i: int,
             timeout_s: float) -> Read:
    query, params = pairs[i]
    t0 = now()
    try:
        rows = system.submit(texts[query], params).rows(timeout_s)
        return i, t0, now(), rows, None
    except Exception as ex:  # a read that raises or times out has failed
        return i, t0, now(), None, f"{type(ex).__name__}: {ex}"


def closed_loop(system, texts, pairs, orders: Sequence[Sequence[int]],
                timeout_s: float, until: Optional[float]) -> List[Read]:
    """One thread per entry of ``orders``; each sends its next read when
    the previous answer has arrived.  ``until`` None: each walks its order
    once.  Otherwise each cycles through its order and sends no read at or
    after ``until``; a read in flight then is still waited for."""
    reads: List[List[Read]] = [[] for _ in orders]

    def client(c: int) -> None:
        order, k = orders[c], 0
        while order and (until is not None or k < len(order)):
            if until is not None and now() >= until:
                return
            reads[c].append(one_read(system, texts, pairs,
                                     order[k % len(order)], timeout_s))
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(len(orders))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for per_client in reads for r in per_client]


def make_pairs(cell: Cell, data, seed: int):
    """The mix's (query, binding) pairs in the mix's own order, and each
    client's seeded permutation of them."""
    rng = np.random.RandomState([seed & 0xFFFFFFFF, seed >> 32, 1])
    binds = cell.generator.bindings(data, cell.traffic["bindings"], rng)
    pairs = [(q, b) for b in binds for q in cell.traffic["queries"]]
    orders = [[int(i) for i in rng.permutation(len(pairs))]
              for _ in range(int(cell.traffic["clients"]))]
    return pairs, orders


def warm_up(system, cell: Cell, texts, pairs, orders) -> List[Read]:
    """Every pair once in the mix's own order from one client: each
    answer is there to be compared, and the fused executor records,
    re-records and goes generic within the first few.  Then a short
    concurrent pass from the cell's clients, so that whatever the batcher
    forms under concurrency has run before the stamp.  A first read may
    compile for minutes: warm-up has a timeout of its own."""
    timeout_s = float(cell.traffic["warmup_timeout_s"])
    every = list(range(len(pairs)))
    reads = closed_loop(system, texts, pairs, [every], timeout_s, None)
    _note("warm-up, every pair once")
    dealt = [o[:WARM_CONCURRENT_PER_CLIENT] for o in orders]
    reads += closed_loop(system, texts, pairs, dealt, timeout_s, None)
    _note("warm-up, concurrent pass")
    return reads


def _note(what: str) -> None:
    """Where set-up's time goes, on stderr (well before its last lines)."""
    print(f"[{now() - _T0:8.2f} s] {what}", file=sys.stderr)


def traced_interval(trace_dir: str, seconds: float,
                    marker: str) -> Tuple[float, float]:
    """Trace ``seconds`` of the running window; returns the host-clock
    interval the marker span covered."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the engine's TraceMe spans are enough
    opts.enable_hlo_proto = False
    time.sleep(TRACE_START_S)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(marker):
            t0 = now()
            time.sleep(seconds)
            t1 = now()
    finally:
        jax.profiler.stop_trace()
    return t0, t1


# -- correctness --------------------------------------------------------------


def compare(cell: Cell, data, pairs, warm: Sequence[Read],
            window: Sequence[Read]) -> Tuple[dict, List[str]]:
    """Every answer of warm-up and window against the numpy reference.
    Returns the numbers compared, each with its limit (all exact: 0), and
    a few examples of what went wrong."""
    by_binding: Dict[str, dict] = {}
    for _q, params in pairs:
        key = json.dumps(params, sort_keys=True)
        if key not in by_binding:
            by_binding[key] = cell.generator.reference(
                data, cell.traffic["queries"], params)
    want = [by_binding[json.dumps(p, sort_keys=True)][q] for q, p in pairs]
    examples: List[str] = []

    def tally(reads: Sequence[Read]) -> Tuple[int, int]:
        wrong = unanswered = 0
        for i, _t0, _t1, rows, err in reads:
            if err is not None:
                unanswered += 1
                bad = err
            elif rows != want[i]:
                wrong += 1
                bad = f"got {rows} want {want[i]}"
            else:
                continue
            if len(examples) < 3:
                examples.append(f"{pairs[i][0]} {pairs[i][1]}: {bad}"[:300])
        return wrong, unanswered

    w_wrong, w_unanswered = tally(warm)
    wrong, unanswered = tally(window)
    compared = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "warmup_wrong_answers": {"value": w_wrong, "limit": 0},
        "warmup_unanswered": {"value": w_unanswered, "limit": 0},
    }
    return compared, examples


# -- one run ------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device_check: Callable[[Cell], list] = check_device,
             make_system: Callable = Served) -> dict:
    """The run as a dict (the result line).  ``device_check`` and
    ``make_system`` are parameters so that the tests can stand in for the
    chip and break the timed path from outside; the command has no option
    that does either."""
    devices = device_check(cell)
    gen = cell.generator
    _note("imports, device")
    data = gen.make_data(cell.config["sizes"], seed)
    pairs, orders = make_pairs(cell, data, seed)
    _note("data from the seed")
    texts = gen.QUERIES
    timeout_s = float(cell.traffic["timeout_s"])
    system = make_system(cell.config, gen, data)
    _note("session, ingest, server")
    try:
        warm = warm_up(system, cell, texts, pairs, orders)
        setup_seconds = now() - _T0
        before = system.counters()
        t_start = now()
        t_end = t_start + seconds
        box: dict = {}
        loop = threading.Thread(
            target=lambda: box.update(reads=closed_loop(
                system, texts, pairs, orders, timeout_s, t_end)), daemon=True)
        loop.start()
        if trace:
            trace_reduce = _module(cell.bench_dir, "trace_reduce")
            trace_dir = os.path.join(ROOT, ".bench_trace")
            traced = traced_interval(
                trace_dir, min(TRACE_SECONDS, max(0.5, seconds / 2)),
                trace_reduce.WINDOW_MARKER)
        loop.join()
        window: List[Read] = box["reads"]
        after = system.counters()
    finally:
        system.close()
    device = device_report(devices)
    del system
    _note("window closed, server shut down")

    compared, examples = compare(cell, data, pairs, warm, window)
    answered = [r for r in window if r[4] is None]
    failed = len(window) - len(answered) \
        + compared["wrong_answers"]["value"]
    ctx = {
        "setup_seconds": setup_seconds,
        "latencies_s": [t1 - t0 for _i, t0, t1, _r, _e in answered],
        # the window sends for ``seconds`` and then waits for what is in
        # flight: its work is every read it sent, its time runs to the
        # last answer
        "elapsed_s": max([r[2] for r in window], default=t_end) - t_start,
        "counters": {k: v - before.get(k, 0) for k, v in after.items()},
    }
    ctx["counters"]["window.reads"] = len(answered)
    # for whoever has to explain a run that read far off: the window's
    # slowest reads and every counter that moved in it
    for i, t0, t1, _rows, _err in sorted(
            window, key=lambda r: r[1] - r[2])[:3]:
        _note(f"slowest read {t1 - t0:.3f} s, sent at +{t0 - t_start:.2f} s:"
              f" {pairs[i][0]} {pairs[i][1]}")
    _note("window counters " + json.dumps(
        {k: v for k, v in sorted(ctx["counters"].items()) if v}))
    out = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": len(window),
        "failed": failed,
    }
    if trace:
        tr = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if tr is None:
            raise BenchError("the trace has no marker span, or no device "
                             "operation inside it")
        ctx["trace"] = tr
        # the kernels as the trace names them: what a kernel metric's
        # pattern has to match
        _note("custom calls in the trace: " + json.dumps(
            {name[:60]: s for name, s in sorted(tr["op_s"].items())
             if "tpu_custom_call" in name}))
        ctx["traced_reads"] = sum(
            1 for r in answered if traced[0] <= r[2] <= traced[1])
        listed = cell.per_layer
        out["metrics"] = cell.read_metrics("layer_metrics", listed, ctx)
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        listed = cell.end_to_end
        out["metrics"] = cell.read_metrics("end_to_end", listed, ctx)
    out["device"] = device
    out["compared"] = compared
    _note("reference, comparison, metrics")
    for ex in examples:
        print("mismatch: " + ex, file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    silent = [m["name"] for m in listed if m["name"] not in out["metrics"]]
    if silent:
        # a kernel renamed or taken off the path must not just fall out of
        # the line; a cell in which a metric has nothing to read by design
        # is left out of that metric's ``workloads`` in BENCHMARK.json
        raise BenchError(f"{cell.name} lists {silent}, and their readers "
                         f"found nothing to read in this run")
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT, **stand_ins) -> int:
    args = parse_args(argv)
    try:
        cell = Cell(root, args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       **stand_ins)
    except BenchError as ex:
        print(f"benchmark: {ex}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

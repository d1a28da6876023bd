"""A later PR adds a cell, a traffic mix and a per-layer metric with a
reader of its own as NEW files plus entries in BENCHMARK.json; nothing
that is already there is edited, ``run.py`` least of all."""
import json
import os
import re

import run
from test_harness import run_line


def add_files(root):
    bench = os.path.join(root, "benchmarks")
    before = {}
    for d, _sub, files in os.walk(bench):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    with open(os.path.join(bench, "traffic", "depth3-c2.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 2, "queries": ["fof3"],
                   "bindings": {"kind": "people", "count": 3},
                   "timeout_s": 30.0, "warmup_timeout_s": 300.0}, f)
    with open(os.path.join(bench, "readers_later.py"), "w") as f:
        f.write("def replays_per_read(ctx, key):\n"
                "    reads = ctx['counters'].get('window.reads')\n"
                "    return ctx['counters'][key] / reads if reads else None\n")
    with open(os.path.join(bench, "layer_metrics",
                           "fused.replays_per_read.json"), "w") as f:
        json.dump({"what": "generic replays per read",
                   "reader": "readers_later.replays_per_read",
                   "args": {"key": "fused.generic_replays"}}, f)
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["workloads"].append({
        "name": "fof-depth3-c2", "config": spec["configs"][0]["name"],
        "traffic": "depth3-c2", "chips": 1, "why": "added by the test"})
    spec["per_layer"].append({
        "name": "fused.replays_per_read", "unit": "replays/read",
        "better": "lower", "source": "program_counter",
        "layer": "fused executor", "moves": "read_p50_ms",
        "workloads": ["fof-depth3-c2"]})
    json.dump(spec, open(path, "w"))
    return before


def test_a_new_cell_and_metric_are_picked_up(small_root, capsys):
    before = add_files(small_root)
    line, _err = run_line(small_root, capsys, "fof-depth3-c2")
    assert line["correct"] is True and line["attempted"] > 0
    assert line["metrics"]["read_p50_ms"]["value"] > 0

    cell = run.Cell(small_root, "fof-depth3-c2")
    assert cell.traffic["clients"] == 2
    names = [m["name"] for m in cell.per_layer]
    assert "fused.replays_per_read" in names
    ctx = {"counters": {"fused.generic_replays": 30, "window.reads": 10,
                        "fused.recordings": 0}}
    got = cell.read_metrics("layer_metrics", cell.per_layer, ctx)
    assert got["fused.replays_per_read"] == {"value": 3.0,
                                             "unit": "replays/read"}
    assert got["fused.recordings_in_window"]["value"] == 0.0
    # readers with nothing to read leave their metric out
    assert "device.idle_share" not in got and "serve.batch_mean" not in got
    # the metric is not reported by a cell it does not list
    other = run.Cell(small_root, json.load(open(
        f"{small_root}/BENCHMARK.json"))["workloads"][0]["name"])
    assert "fused.replays_per_read" not in [m["name"] for m in other.per_layer]

    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} was edited"


def test_run_py_names_no_cell_config_mix_or_metric():
    from conftest import BENCH_DIR, REPO_ROOT
    spec = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    names = ([w["name"] for w in spec["workloads"]]
             + [w["traffic"] for w in spec["workloads"]]
             + [c["name"] for c in spec["configs"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    text = open(os.path.join(BENCH_DIR, "run.py")).read()
    whole = lambda n: re.search(r"(?<![\w.-])" + re.escape(n) + r"(?![\w.-])", text)  # noqa: E731
    assert [n for n in names if whole(n)] == []


def test_a_listed_metric_that_reads_nothing_stops_the_run(small_root, capsys):
    """A reader that finds nothing (a kernel renamed, a counter gone)
    must not drop its metric from the line in silence."""
    bench = os.path.join(small_root, "benchmarks")
    with open(os.path.join(bench, "readers_silent.py"), "w") as f:
        f.write("def nothing(ctx):\n    return None\n")
    with open(os.path.join(bench, "end_to_end", "gone_ms.json"), "w") as f:
        json.dump({"what": "reads nothing", "reader": "readers_silent.nothing",
                   "args": {}}, f)
    path = os.path.join(small_root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["end_to_end"].append({"name": "gone_ms", "unit": "ms",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock"})
    json.dump(spec, open(path, "w"))
    from conftest import cpu_devices
    rc = run.main(["--workload", spec["workloads"][0]["name"], "--seed", "5",
                   "--seconds", "0.5", "--trace", "0"], root=small_root,
                  device_check=cpu_devices)
    captured = capsys.readouterr()
    assert rc == 1 and captured.out.strip() == ""
    assert "gone_ms" in captured.err and "nothing to read" in captured.err

"""PR 27's per-layer metrics: nine files under ``layer_metrics/`` that
name readers ``readers.py`` already has and counters the program now
registers when the session and the server are built.

Which of them ``BENCHMARK.json`` lists is not this file's business
beyond this: one that is listed reads as ISSUE 27's table (``ENTRIES``)
says.  Any PR may append further ``per_layer`` entries after them.
"""
import json
import os

import pytest

import run
from conftest import BENCH_DIR, REPO_ROOT

PROGRAM_COUNTER = {"source": "program_counter"}
ENTRIES = [
    {"name": "serve.lock_wait_ms", "unit": "ms", "better": "lower",
     **PROGRAM_COUNTER, "layer": "admission and batcher",
     "moves": "read_p50_ms"},
    {"name": "serve.execute_ms", "unit": "ms", "better": "lower",
     **PROGRAM_COUNTER, "layer": "admission and batcher",
     "moves": "read_p50_ms"},
    {"name": "serve.materialize_ms", "unit": "ms", "better": "lower",
     **PROGRAM_COUNTER, "layer": "admission and batcher",
     "moves": "read_p50_ms"},
    {"name": "exec.service_ms_per_read", "unit": "ms/read",
     "better": "lower", **PROGRAM_COUNTER, "layer": "fused executor",
     "moves": "reads_per_s"},
    {"name": "exec.sync_wait_ms_per_read", "unit": "ms/read",
     "better": "lower", **PROGRAM_COUNTER, "layer": "fused executor",
     "moves": "read_p50_ms"},
    {"name": "exec.d2h_bytes_per_read", "unit": "bytes/read",
     "better": "lower", **PROGRAM_COUNTER, "layer": "device operators",
     "moves": "read_p50_ms"},
    {"name": "xla.traces_in_window", "unit": "count", "better": "lower",
     **PROGRAM_COUNTER, "layer": "device operators", "moves": "read_p90_ms"},
    {"name": "xla.compiles_in_window", "unit": "count", "better": "lower",
     **PROGRAM_COUNTER, "layer": "device operators", "moves": "read_p90_ms"},
    {"name": "xla.compile_s_in_window", "unit": "s", "better": "lower",
     **PROGRAM_COUNTER, "layer": "device operators", "moves": "read_p90_ms"},
]
NAMES = [m["name"] for m in ENTRIES]


def how(name):
    with open(os.path.join(BENCH_DIR, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def keys_named(name):
    args = how(name)["args"]
    named = []
    for k in ("num", "den", "key"):
        v = args.get(k, [])
        named += [v] if isinstance(v, str) else list(v)
    return named


@pytest.mark.parametrize("name", NAMES)
def test_file_names_a_reader_that_is_there(name):
    spec = how(name)
    assert set(spec) == {"what", "reader", "args"} and spec["what"]
    module, fn = spec["reader"].rsplit(".", 1)
    # no reader code comes with this PR: the first readers do
    assert module == "readers" and fn in ("counter_ratio", "counter_delta")
    assert callable(getattr(run._module(BENCH_DIR, module), fn))
    assert keys_named(name)


def test_a_listed_entry_reads_as_the_issue_says():
    spec = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    listed = [m for m in spec["per_layer"] if m["name"] in NAMES]
    assert listed, "none of PR 27's metrics is listed"
    for m in listed:
        assert m == ENTRIES[NAMES.index(m["name"])]
        assert "workloads" not in m
    layers = {m["layer"] for m in spec["per_layer"]}
    ends = {m["name"] for m in spec["end_to_end"]}
    for m in ENTRIES:
        assert m["layer"] in layers and m["moves"] in ends


@pytest.fixture
def served(small_root):
    cell = run.Cell(small_root, json.load(open(
        os.path.join(small_root, "BENCHMARK.json")))["workloads"][0]["name"])
    data = cell.generator.make_data(cell.config["sizes"], 27)
    system = run.Served(cell.config, cell.generator, data)
    try:
        yield cell, data, system
    finally:
        system.close()


def test_every_counter_is_there_before_a_read_and_a_window_reads_all(served):
    cell, data, system = served
    before = system.counters()
    for name in NAMES:
        for key in keys_named(name):
            if key != "window.reads":       # run.py's own
                assert key in before, (name, key)
    text = cell.generator.QUERIES[cell.traffic["queries"][0]]
    pairs, _orders = run.make_pairs(cell, data, 27)
    reads = 4
    for _query, params in pairs[:reads]:
        assert len(system.submit(text, params).rows(120)) == 1
    # a compile inside the window, whatever this process compiled before
    import jax
    import jax.numpy as jnp
    jax.jit(lambda x: x * 27 + 2727)(jnp.arange(2727)).block_until_ready()
    after = system.counters()
    ctx = {"counters": {k: v - before.get(k, 0) for k, v in after.items()}}
    ctx["counters"]["window.reads"] = reads
    got = cell.read_metrics("layer_metrics", ENTRIES, ctx)
    assert list(got) == NAMES               # a number for all nine
    for m in ENTRIES:
        assert got[m["name"]]["unit"] == m["unit"]
    value = {n: got[n]["value"] for n in NAMES}
    assert value["serve.execute_ms"] > 0 and value["serve.lock_wait_ms"] >= 0
    assert value["serve.materialize_ms"] > 0
    assert value["exec.service_ms_per_read"] > 0
    assert value["exec.sync_wait_ms_per_read"] > 0
    assert value["exec.d2h_bytes_per_read"] > 0
    # whoever traces and compiles in the window, it is counted
    assert value["xla.traces_in_window"] > 0
    assert value["xla.compiles_in_window"] > 0
    assert value["xla.compile_s_in_window"] > 0
    # the same reads again are a steady state: nothing compiles
    before = system.counters()
    for _query, params in pairs[:reads]:
        system.submit(text, params).rows(120)
    after = system.counters()
    assert after["xla.traces"] == before["xla.traces"]
    assert after["xla.compiles"] == before["xla.compiles"]
    assert after["serve.lock_wait_s.count"] \
        == before["serve.lock_wait_s.count"] + reads


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit (PR 26) gives the readers: no counter of
    this PR.  Only the metric over counters it has reads; the others
    return nothing, whatever ``BENCHMARK.json`` lists."""
    cell = run.Cell(REPO_ROOT, json.load(open(
        os.path.join(REPO_ROOT, "BENCHMARK.json")))["workloads"][0]["name"])
    ctx = {"counters": {"query.execute_s.sum": 51.2, "window.reads": 303,
                        "backend.syncs": 299}}
    got = cell.read_metrics("layer_metrics", ENTRIES, ctx)
    assert list(got) == ["exec.service_ms_per_read"]
    assert got["exec.service_ms_per_read"]["value"] == pytest.approx(
        1000 * 51.2 / 303)

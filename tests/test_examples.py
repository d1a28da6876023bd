"""Examples must stay runnable — the analog of the reference's
documentation module compiling its snippet sources (SURVEY.md §4.5).
Each example's main() returns its result rows so we can assert content,
not just exit status."""
import os
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")
sys.path.insert(0, EXAMPLES_DIR)


@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_social_network(backend):
    import social_network
    rows, foaf = social_network.main(backend)
    assert rows == [{"a": "Alice", "b": "Bob"}, {"a": "Alice", "b": "Carol"}]
    assert foaf == [{"foaf": "Carol"}]


def test_columnar_input():
    import columnar_input
    rows = columnar_input.main()
    assert rows == [{"customer": "Nia", "total": 298.0},
                    {"customer": "Omar", "total": 19.0}]


def test_multiple_graph():
    import multiple_graph
    people, edges = multiple_graph.main()
    assert [r["n"] for r in people] == ["Alice", "Bob"]
    assert edges == [{"x": "Alice", "y": "Bob"}]


def test_recommendation():
    import recommendation
    rows = recommendation.main()
    assert rows == [{"recommend": "monitor", "score": 2},
                    {"recommend": "headset", "score": 1}]


def test_fs_datasource():
    import fs_datasource
    rows = fs_datasource.main()
    assert rows == [{"n": "Kyoto"}]


@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_parameterized_reads(backend):
    import parameterized_reads
    out = parameterized_reads.main(backend)
    # (min_age, row count, size_syncs) per rotation of the prepared query
    assert [(m, n) for m, n, _ in out] == [
        (30, 4), (40, 2), (25, 5), (50, 1), (30, 4)]


@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_serve_concurrent(backend):
    import serve_concurrent
    ok, batch_max = serve_concurrent.main(backend)
    assert ok == serve_concurrent.N_CLIENTS * serve_concurrent.PER_CLIENT
    assert batch_max > 1  # the micro-batcher demonstrably coalesced


@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_profile_query(backend):
    import profile_query
    rows, explained, profiled, n_events = profile_query.main(backend)
    assert rows == [{"person": "Ana", "knows": "Bo"},
                    {"person": "Ana", "knows": "Cleo"},
                    {"person": "Bo", "knows": "Cleo"}]
    assert explained.records is None
    assert profiled.profile["rows"] == len(rows)
    assert "rows=" in profiled.plans["profile"]
    assert n_events > 0


# -- chip_smoke.py (repo root): the chip's proof, rehearsed on the CPU ------

REPO_ROOT = os.path.dirname(EXAMPLES_DIR)


def test_chip_smoke_phases_on_cpu(capsys):
    """Every phase of the one-chip script at a tiny size, in-process.
    The device check is stood in for HERE — the script has no option
    that skips it."""
    import json
    sys.path.insert(0, REPO_ROOT)
    import chip_smoke
    import jax

    def cpu_device(chips):
        d = jax.devices()
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}

    args = chip_smoke.parse_args(["--people", "2000", "--edges", "10000"])
    device = chip_smoke.run(args, device_phase=cpu_device)
    assert device["platform"] == "cpu"
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    by_phase = {ln["phase"]: ln for ln in lines}
    assert list(by_phase) == ["ingest", "count", "paths", "serve",
                              "assert-no-fallback", "pushdown-ingest",
                              "count-pushdown"]
    assert by_phase["ingest"]["people"] == 2000
    assert by_phase["count"]["count"] == by_phase["count"]["oracle"]
    assert {r["fused_mode"] for r in by_phase["paths"]["first_runs"]} \
        == {"record", "replay_gen"}
    assert by_phase["serve"]["completed"] == by_phase["serve"]["requests"]
    nf = by_phase["assert-no-fallback"]
    assert nf["fallback_count"] == 0 and nf["fallback_reasons"] == []
    # on the CPU the static table turns on the interpreted families
    assert nf["kernel_families_on"] == ["expand", "segment"]
    assert all(nf["kernel_launches"][f] > 0 for f in nf["kernel_families_on"])
    assert nf["kernels_compiled"] is False
    assert by_phase["count-pushdown"]["strategy"] == "fused-spmv"


def test_chip_smoke_refuses_the_cpu():
    """As the driver runs it where there is no chip: non-zero exit at the
    ``device`` phase, and no result line."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert proc.returncode != 0
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    assert [ln["phase"] for ln in lines] == ["device", "failed"]
    assert lines[0]["platform"] == "cpu"
    assert not any("ok" in ln and ln["ok"] for ln in lines)

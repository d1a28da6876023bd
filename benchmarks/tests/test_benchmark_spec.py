"""``BENCHMARK.json`` against the files it names: one case a cell and a
metric, so that an entry appended without its file (or with a name the
contract refuses) fails here on the CPU and not at the driver's check."""
import json
import os
import re

import pytest

import run
from conftest import BENCH_DIR, REPO_ROOT

SPEC = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
CELLS = {w["name"] for w in SPEC["workloads"]}
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
METRICS = [("end_to_end", m) for m in SPEC["end_to_end"]] \
    + [("layer_metrics", m) for m in SPEC["per_layer"]]


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_a_cell_names_files_that_are_there(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.fullmatch(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    cfg = CONFIGS[cell["config"]]
    assert cfg["file"].startswith(SPEC["paths"][0] + "/")
    # the harness's own loader: configuration, traffic file and generator
    # are there, or it raises
    loaded = run.Cell(REPO_ROOT, cell["name"])
    assert loaded.config["source"] == cfg["source"] and one_line(cfg["source"])
    assert sorted(loaded.config["reduced"]) == sorted(cfg["reduced"])
    assert set(loaded.traffic["queries"]) <= set(loaded.generator.QUERIES)
    assert {"loop", "clients", "bindings", "timeout_s",
            "warmup_timeout_s"} <= set(loaded.traffic)


@pytest.mark.parametrize("kind,metric", METRICS,
                         ids=[m["name"] for _k, m in METRICS])
def test_a_metric_has_its_file_and_lists_only_cells(kind, metric):
    assert NAME.fullmatch(metric["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    with open(os.path.join(BENCH_DIR, kind, metric["name"] + ".json")) as f:
        assert "." in json.load(f)["reader"]
    assert set(metric.get("workloads", [])) <= CELLS
    if kind == "layer_metrics":
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert one_line(metric["layer"]) and "bound" not in metric
    else:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_and_one_more_of_each_kind():
    def listed(m, cell):
        return cell in m.get("workloads", CELLS)

    for cell in CELLS:
        ends = [m["name"] for m in SPEC["end_to_end"] if listed(m, cell)]
        assert "setup_s" in ends and len(ends) >= 2
        assert any(listed(m, cell) for m in SPEC["per_layer"])
        # a per-layer metric is read only where the metric it moves is
        for m in SPEC["per_layer"]:
            if listed(m, cell):
                assert m["moves"] in ends, (cell, m["name"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) \
        <= max(1, len(CELLS) // 2)

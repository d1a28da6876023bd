"""A whole run on the CPU at 20,000 people with the device check stood
in for: the answers are compared, planted faults come out as not correct,
the result line has the contract's keys, and the command itself refuses
to run without a TPU."""
import json
import subprocess
import sys

import pytest

import run
from conftest import REPO_ROOT, cpu_devices

CELLS = [w["name"] for w in
         json.load(open(f"{REPO_ROOT}/BENCHMARK.json"))["workloads"]]


def run_line(root, capsys, workload, **stand_ins):
    rc = run.main(["--workload", workload, "--seed", "3000000019",
                   "--seconds", "1.5", "--trace", "0"], root=root,
                  device_check=cpu_devices, **stand_ins)
    captured = capsys.readouterr()
    assert rc == 0
    return json.loads(captured.out.splitlines()[-1]), captured.err


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(small_root, capsys, workload):
    line, err = run_line(small_root, capsys, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    spec = json.load(open(f"{small_root}/BENCHMARK.json"))
    assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["compared"].values())
    # each number compared beside its limit: the last lines of stderr
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "(limit 0)" in t for t in tail)


class Broken(run.Served):
    """The served system with the timed path broken underneath: every
    ``every``-th answer is altered where it is produced, or never comes."""

    def __init__(self, *a, fault, every=5, **kw):
        super().__init__(*a, **kw)
        self.fault, self.every, self.n = fault, every, 0

    def submit(self, text, params):
        handle = super().submit(text, params)
        self.n += 1
        if self.n % self.every:
            return handle
        return AlteredHandle(handle, self.fault)


class AlteredHandle:
    def __init__(self, handle, fault):
        self.handle, self.fault = handle, fault

    def rows(self, timeout=None):
        rows = [dict(r) for r in self.handle.rows(timeout)]
        if self.fault == "timeout":
            from caps_tpu.serve.errors import WaitTimeout
            raise WaitTimeout("request not complete")
        rows[0]["fof"] += 1
        return rows


@pytest.mark.parametrize("fault,number", [("wrong", "wrong_answers"),
                                          ("timeout", "unanswered")])
def test_planted_fault_is_not_correct(small_root, capsys, fault, number):
    line, err = run_line(
        small_root, capsys, CELLS[0],
        make_system=lambda *a: Broken(*a, fault=fault))
    assert line["correct"] is False
    assert line["failed"] > 0
    assert line["compared"][number]["value"] == line["failed"]
    assert "mismatch: " in err


def test_command_refuses_the_cpu():
    """As the driver would run it where there is no chip (this sandbox
    holds JAX to the CPU; the command itself never chooses a platform)."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

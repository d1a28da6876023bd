"""The control comes out as not correct in every cell: answers from a
graph that lacks every hundredth relationship fail the exact comparison."""
import json

import pytest

import control
from conftest import cpu_devices
from test_harness import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(small_root, capsys, workload):
    rc = control.main(["--workload", workload, "--seeds", "5,3000000023",
                       "--seconds", "0.3"], root=small_root,
                      device_check=cpu_devices)
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 2
    for line in lines:
        assert line["control_correct"] is False
        assert line["compared"]["wrong_answers"]["value"] > 0
        assert line["compared"]["warmup_wrong_answers"]["value"] > 0
        assert line["compared"]["unanswered"]["value"] == 0

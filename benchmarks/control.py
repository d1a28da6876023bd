#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place with one of the configuration's guarantees broken, driven through
the same run (warm-up, window, comparison).  It has to come out as NOT
correct; a comparison that passes it would pass a program that does the
same.

The guarantee broken is "exact answers on the graph as loaded": the
stand-in answers from a copy of the graph that lacks a share of its
relationships (the configuration file's ``control.stale_fraction``), as a
replica that missed those writes would.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds 5

prints one JSON line per seed.  The benchmark's own runs never run this;
``tests/test_control.py`` keeps it at a small size.
"""
from __future__ import annotations

import json
import sys
import threading

import run


class StaleReference:
    """Stands where ``run.Served`` stands."""

    def __init__(self, config: dict, generator, data):
        self.generator = generator
        self.data = generator.stale_copy(
            data, float(config["control"]["stale_fraction"]))
        self.query_of = {text: q for q, text in generator.QUERIES.items()}
        self.answers: dict = {}
        self.lock = threading.Lock()

    def submit(self, text: str, params: dict):
        key = json.dumps(params, sort_keys=True)
        with self.lock:
            if key not in self.answers:
                self.answers[key] = self.generator.reference(
                    self.data, list(self.query_of.values()), params)
        return _Answer(self.answers[key][self.query_of[text]])

    def counters(self) -> dict:
        return {}

    def close(self) -> None:
        pass


class _Answer:
    def __init__(self, rows):
        self._rows = rows

    def rows(self, timeout=None):
        return [dict(r) for r in self._rows]


def main(argv=None, root: str = run.ROOT, **stand_ins) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    passed = 0
    try:
        cell = run.Cell(root, args.workload)
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run.run_cell(cell, seed, args.seconds, False,
                               make_system=StaleReference, **stand_ins)
            passed += bool(out["correct"])
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control_correct": out["correct"],
                              "attempted": out["attempted"],
                              "compared": out["compared"]}), flush=True)
    except run.BenchError as ex:
        print(f"control: {ex}", file=sys.stderr)
        return 1
    return 2 if passed else 0   # a control that passes is the failure


if __name__ == "__main__":
    sys.exit(main())

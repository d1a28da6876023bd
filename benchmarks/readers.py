"""Readers of the first per-layer metrics.

A reader is ``fn(ctx, **args) -> float | None``; a metric's file under
``layer_metrics/`` names it as ``"<module>.<function>"`` (a module is a
``.py`` file beside this one, so a later metric that reads a new span
brings a reader file of its own).  ``ctx`` has:

``setup_seconds``, ``latencies_s`` (client side, every read the window
sent that was answered, those answered after its close included) and
``elapsed_s`` (the window's opening to its last answer) -- all runs
``counters``  the program's counters, window's end minus window's start:
              ``session.metrics_snapshot()`` and ``server.stats()`` (the
              latter under ``serve.``), plus ``window.reads`` (reads
              answered inside the window) -- all ``--trace`` runs
``trace``     ``trace_reduce.reduce_trace``'s dict, or None
``traced_reads``  reads answered inside the traced interval

A reader that finds nothing to read returns None, never 0; ``run.py``
then refuses the run, naming the metric (README.md).
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Union

import numpy as np

Keys = Union[str, Sequence[str]]


def setup_s(ctx: dict) -> Optional[float]:
    return ctx["setup_seconds"]


def rate(ctx: dict) -> Optional[float]:
    """Every read of the window that was answered, over the time from the
    window's opening to its last answer.  (Counting only the answers
    before the close over the fixed length would step by one read in
    ~150: two runs then read the same to the last digit or 0.7 % apart.)"""
    if not ctx["latencies_s"]:
        return None
    return len(ctx["latencies_s"]) / ctx["elapsed_s"]


def latency_percentile_ms(ctx: dict, q: float) -> Optional[float]:
    """Over every read of the window, by linear interpolation."""
    if not ctx["latencies_s"]:
        return None
    return 1e3 * float(np.percentile(ctx["latencies_s"], q))


def _total(counters: dict, keys: Keys) -> Optional[float]:
    keys = [keys] if isinstance(keys, str) else list(keys)
    if any(k not in counters for k in keys):
        return None
    return float(sum(counters[k] for k in keys))


def counter_delta(ctx: dict, key: Keys) -> Optional[float]:
    return _total(ctx["counters"], key)


def counter_ratio(ctx: dict, num: Keys, den: Keys,
                  scale: float = 1.0) -> Optional[float]:
    n, d = _total(ctx["counters"], num), _total(ctx["counters"], den)
    if n is None or not d:
        return None
    return scale * n / d


def trace_programs_per_read(ctx: dict) -> Optional[float]:
    tr, reads = ctx.get("trace"), ctx.get("traced_reads")
    if not tr or not reads or not tr["programs"]:
        return None
    return tr["programs"] / reads


def trace_busy_ms_per_read(ctx: dict) -> Optional[float]:
    tr, reads = ctx.get("trace"), ctx.get("traced_reads")
    if not tr or not reads:
        return None
    return 1e3 * tr["busy_s"] / reads


def trace_op_ms_per_read(ctx: dict, pattern: str) -> Optional[float]:
    """Summed device time of the ops whose name matches ``pattern``."""
    tr, reads = ctx.get("trace"), ctx.get("traced_reads")
    if not tr or not reads:
        return None
    rx = re.compile(pattern)
    hit = [s for name, s in tr["op_s"].items() if rx.search(name)]
    if not hit:
        return None
    return 1e3 * sum(hit) / reads


def trace_idle_share(ctx: dict) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

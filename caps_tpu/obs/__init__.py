"""caps_tpu observability: tracing, metrics, EXPLAIN/PROFILE plumbing.

The measuring instrument for the roofline gap (ROADMAP / round-5
verdict): structured spans (query → phase → relational operator) with
wall time, device time, output cardinality, and bytes moved; a metrics
registry that absorbs the engine's scattered stats; and exporters
(JSON-lines, ``chrome://tracing``).  The Cypher ``EXPLAIN`` / ``PROFILE``
query prefixes (frontend/parser.py, relational/session.py) are the
user-facing entry points; ``session.metrics_snapshot()`` is the
programmatic one.

Design constraints:

* near-zero overhead when disabled — a disabled tracer returns a shared
  no-op span; per-operator instrumentation costs one attribute check;
* never silently wrong numbers — fused-replay runs tag per-operator
  times as host dispatch and report device time as a per-replay
  aggregate span (docs/tpu.md);
* one clock — all timestamps come from :mod:`caps_tpu.obs.clock`
  (enforced by ``python -m caps_tpu.analysis``, clock-discipline).
"""
from caps_tpu.obs import clock, lockgraph
from caps_tpu.obs.compile import (CompileLedger, attributed as
                                  compile_attributed, charge as
                                  compile_charge, charged as compile_charged,
                                  global_compile_ledger)
from caps_tpu.obs.export import (chrome_trace_events, write_chrome_trace,
                                 write_jsonl)
from caps_tpu.obs.ledger import (MemoryLedger, device_memory,
                                 snapshot_footprint)
from caps_tpu.obs.log import EventLog, SlowQueryLog
from caps_tpu.obs.metrics import (MetricsRegistry, diff_snapshots,
                                  global_registry)
from caps_tpu.obs.profile import (find_executed_rows, profile_tree,
                                  render_profile, tag_timing)
from caps_tpu.obs.telemetry import (FlightRecorder, OpStatsStore,
                                    RollingCounter, RollingHistogram,
                                    ServingTelemetry, SLOConfig)
from caps_tpu.obs.tracer import (NULL_SPAN, NullSpan, Span, Tracer, activate,
                                 active_tracer, profiler_span, timed_span)

__all__ = [
    "clock", "lockgraph", "Span", "NullSpan", "NULL_SPAN", "Tracer",
    "activate",
    "active_tracer", "profiler_span", "timed_span", "MetricsRegistry", "global_registry", "diff_snapshots",
    "write_jsonl", "write_chrome_trace", "chrome_trace_events",
    "profile_tree", "render_profile", "tag_timing", "find_executed_rows",
    "SLOConfig", "ServingTelemetry", "FlightRecorder", "OpStatsStore",
    "RollingCounter", "RollingHistogram",
    "CompileLedger", "compile_attributed", "compile_charge",
    "compile_charged", "global_compile_ledger",
    "MemoryLedger", "device_memory", "snapshot_footprint",
    "EventLog", "SlowQueryLog",
]

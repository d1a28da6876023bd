"""capslint — the repo's multi-pass static-analysis framework.

The serving tier's correctness rests on invariants no general-purpose
tool checks: a global lock order across ~20 locked files, the
replayability fence around traced code, the ServeError catch-one
contract, the single sanctioned clock, and the metrics registry's
naming rules.  This package machine-checks them:

=================  =========================================================
pass               guards
=================  =========================================================
lock-order         lock-acquisition graph from ``with`` nesting (+ one
                   level of call resolution) over serve/, obs/,
                   relational/, okapi/, testing/faults.py: cycles are
                   potential deadlocks; ``__del__``/atexit acquisition
                   flagged.  Runtime complement: caps_tpu/obs/lockgraph.py
tracer-purity      no clock reads / RNG / module-state mutation inside
                   jax.jit / shard_map / pallas_call / fused-record code
                   (the PR 1/4 replayability fence)
error-taxonomy     serve/ raises inherit ServeError; exceptions never
                   mutated beyond first-writer-wins caps_* markers; no
                   swallowed broad handlers; the worker path routes
                   failures through failure.classify (PR 4)
clock-discipline   every timing read goes through caps_tpu.obs.clock —
                   AST-resolved, closing the regex lint's
                   ``from time import perf_counter`` hole (PR 2)
metric-names       dotted-prefix conventions, name->kind uniqueness,
                   histogram snapshot collisions; generates
                   docs/metrics.md (CI drift-checked)
structured-log     every structured-log emit site (obs/log.py contract)
                   carries the request_id/family correlation fields, so
                   events always join with flight dumps and slow-query
                   records (PR 9)
=================  =========================================================

Run ``python -m caps_tpu.analysis`` (or the ``capslint`` console
script).  ``--only a,b`` selects passes, ``--list`` describes them,
``--json`` emits machine-readable findings, and a finding line carrying
``# capslint: disable=<pass>`` is suppressed.  The whole package is
parsed exactly once per run, shared by every pass, and nothing is
imported from the code under analysis.
"""
from __future__ import annotations

from caps_tpu.analysis.core import (AnalysisConfig, Finding, Project,
                                    Source, analysis_pass, load_project,
                                    pass_descriptions, pass_names,
                                    run_passes)

# importing the pass modules registers them (registration order = run
# order = the order the table above documents)
from caps_tpu.analysis import locks as _locks              # noqa: F401
from caps_tpu.analysis import purity as _purity            # noqa: F401
from caps_tpu.analysis import taxonomy as _taxonomy        # noqa: F401
from caps_tpu.analysis import clocks as _clocks            # noqa: F401
from caps_tpu.analysis import metric_names as _metric_names  # noqa: F401
from caps_tpu.analysis import structlog as _structlog      # noqa: F401

from caps_tpu.analysis.metric_names import (check_metrics_doc,
                                            generate_metrics_doc,
                                            write_metrics_doc)

__all__ = [
    "AnalysisConfig", "Finding", "Project", "Source", "analysis_pass",
    "load_project", "pass_descriptions", "pass_names", "run_passes",
    "check_metrics_doc", "generate_metrics_doc", "write_metrics_doc",
]


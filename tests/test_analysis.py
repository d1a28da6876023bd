"""capslint (ISSUE 7): the multi-pass static-analysis framework.

The contracts under test:

* each pass FIRES on a fixture violation with the right path:line —
  a known lock cycle, a purity violation inside jitted code, a
  non-ServeError raise, a naked ``from``-imported timer (the hole the
  old regex lint could not see), and a duplicate metric name;
* inline ``# capslint: disable=<pass>`` suppressions work;
* the LIVE repo is clean under all five passes, and docs/metrics.md
  matches the source (the CI drift check);
* the runtime lock graph (caps_tpu/obs/lockgraph.py) records edges,
  raises on cycles in strict mode, ignores re-entrant re-acquisition,
  and is a plain ``threading`` primitive when the env opt-in is off.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading

import pytest

from caps_tpu.analysis import (AnalysisConfig, Project, check_metrics_doc,
                               generate_metrics_doc, load_project,
                               pass_names, run_passes)
from caps_tpu.analysis.__main__ import main as capslint_main
from caps_tpu.analysis.locks import static_lock_graph
from caps_tpu.obs import lockgraph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _project(tmp_path, files, config=None) -> Project:
    for rel, text in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return Project(str(tmp_path), config)


def _findings(project, only):
    return run_passes(project, only=[only])


def _lines(findings):
    return {(f.path, f.line) for f in findings}


# -- lock-order --------------------------------------------------------------

LOCK_CYCLE = """\
import threading

_a = threading.Lock()
_b = threading.Lock()


def forwards():
    with _a:
        with _b:
            pass


def backwards():
    with _b:
        with _a:
            pass
"""


def test_lock_order_cycle_fires(tmp_path):
    p = _project(tmp_path, {"caps_tpu/serve/locky.py": LOCK_CYCLE})
    found = _findings(p, "lock-order")
    assert len(found) == 1
    f = found[0]
    assert f.path == "caps_tpu/serve/locky.py"
    assert "cycle" in f.message and "locky._a" in f.message \
        and "locky._b" in f.message


def test_lock_order_one_level_call_resolution(tmp_path):
    src = """\
import threading

_a = threading.Lock()
_b = threading.Lock()


def inner():
    with _b:
        with _a:
            pass


def outer():
    with _a:
        inner()
"""
    p = _project(tmp_path, {"caps_tpu/serve/callres.py": src})
    found = _findings(p, "lock-order")
    # outer holds _a and calls inner, which takes _b then _a: the
    # resolved _a -> _b edge closes a cycle with inner's _b -> _a
    assert len(found) == 1 and "cycle" in found[0].message


def test_lock_order_foreign_attr_may_alias(tmp_path):
    """A foreign-attribute lock defined on SEVERAL classes (the
    duck-typed execution seam: a ShardGroup standing in for a
    DeviceReplica behind one ``member.lock`` call site) resolves to
    EVERY candidate instead of being dropped — each alias keeps its
    nesting edges, and no edge is fabricated BETWEEN the aliases."""
    src = """\
import threading

_inner = threading.Lock()


class Replica:
    def __init__(self):
        self.lock = threading.Lock()


class Group:
    def __init__(self):
        self.lock = threading.Lock()


def dispatch(member):
    with member.lock:
        with _inner:
            pass
"""
    from caps_tpu.analysis.locks import static_lock_graph
    p = _project(tmp_path, {"caps_tpu/serve/alias.py": src})
    assert _findings(p, "lock-order") == []  # acyclic: clean
    edges, _index, _info = static_lock_graph(p)
    assert ("alias.Replica.lock", "alias._inner") in edges
    assert ("alias.Group.lock", "alias._inner") in edges
    # aliases of ONE runtime lock must not order against each other
    assert ("alias.Replica.lock", "alias.Group.lock") not in edges
    assert ("alias.Group.lock", "alias.Replica.lock") not in edges


def test_lock_order_del_and_atexit_fire(tmp_path):
    src = """\
import atexit
import threading

_a = threading.Lock()


class Holder:
    def __del__(self):
        with _a:
            pass


def _cleanup():
    with _a:
        pass


atexit.register(_cleanup)
"""
    p = _project(tmp_path, {"caps_tpu/obs/fin.py": src})
    msgs = [f.message for f in _findings(p, "lock-order")]
    assert any("__del__" in m for m in msgs)
    assert any("atexit" in m for m in msgs)


def test_lock_order_same_basename_modules_stay_distinct(tmp_path):
    # two __init__.py (same basename) each hold their own module-level
    # _lock in a consistent order: no merged node, no phantom cycle —
    # and the node ids disambiguate via the dotted path
    a = """\
import threading

_lock = threading.Lock()
_inner = threading.Lock()


def use():
    with _lock:
        with _inner:
            pass
"""
    b = a.replace("with _lock:\n        with _inner:",
                  "with _inner:\n        with _lock:")
    p = _project(tmp_path, {"caps_tpu/serve/__init__.py": a,
                            "caps_tpu/obs/__init__.py": b})
    assert _findings(p, "lock-order") == []
    _edges, index, _info = static_lock_graph(p)
    assert "serve.__init__._lock" in index.ids
    assert "obs.__init__._lock" in index.ids


def test_lock_order_acyclic_is_clean(tmp_path):
    src = LOCK_CYCLE.replace("with _b:\n        with _a:",
                             "with _a:\n        with _b:")
    p = _project(tmp_path, {"caps_tpu/serve/locky.py": src})
    assert _findings(p, "lock-order") == []


# -- tracer-purity -----------------------------------------------------------

PURITY_BAD = """\
import time
import random
import jax

_SEEN = []


@jax.jit
def kernel(x):
    t = time.perf_counter()
    r = random.random()
    _SEEN.append(x)
    return x + t + r


def helper(x):
    return time.time()


def outer(x):
    return jax.jit(inner)(x)


def inner(x):
    return helper(x)
"""


def test_purity_fires_inside_jitted_code(tmp_path):
    p = _project(tmp_path, {"caps_tpu/ops/hot.py": PURITY_BAD})
    found = _findings(p, "tracer-purity")
    lines = _lines(found)
    assert ("caps_tpu/ops/hot.py", 10) in lines   # time.perf_counter
    assert ("caps_tpu/ops/hot.py", 11) in lines   # random.random
    assert ("caps_tpu/ops/hot.py", 12) in lines   # _SEEN.append
    # closure: helper() reached via jax.jit(inner) -> inner -> helper
    assert ("caps_tpu/ops/hot.py", 17) in lines
    # nothing outside traced code is flagged
    assert all(path == "caps_tpu/ops/hot.py" for path, _ in lines)


def test_purity_global_write_fires(tmp_path):
    src = """\
import jax

_calls = 0


@jax.jit
def kernel(x):
    global _calls
    _calls += 1
    return x
"""
    p = _project(tmp_path, {"caps_tpu/ops/gm.py": src})
    found = _findings(p, "tracer-purity")
    assert ("caps_tpu/ops/gm.py", 9) in _lines(found)
    assert any("writes module-level '_calls'" in f.message
               for f in found)


def test_purity_ignores_untraced_code(tmp_path):
    src = """\
import time


def host_side():
    return time.perf_counter()
"""
    p = _project(tmp_path, {"caps_tpu/ops/cold.py": src})
    assert _findings(p, "tracer-purity") == []


def test_purity_wcoj_kernel_clock_read_fires(tmp_path):
    """The WCOJ kernel layer's jit roots are auto-discovered by the
    purity closure: a clock read inside a wcoj-shaped probe (the exact
    decorator/searchsorted structure of ops/wcoj.py) is flagged at its
    line — the fixture proof that the new kernel functions sit in the
    tracer-purity root set."""
    src = """\
import time

import jax
import jax.numpy as jnp


@jax.jit
def probe_adj(keys_sorted, u, ok, n):
    drift = time.perf_counter()
    base = u.astype(jnp.int64) * n
    lo = jnp.searchsorted(keys_sorted, base, side="left")
    hi = jnp.searchsorted(keys_sorted, base + n, side="left")
    return jnp.where(ok, hi - lo, 0) + drift, lo


def extend(keys_sorted, perm, u, ok, n, out_cap):
    counts, lo = probe_adj(keys_sorted, u, ok, n)
    return counts
"""
    p = _project(tmp_path, {"caps_tpu/ops/wcoj_fix.py": src})
    found = _findings(p, "tracer-purity")
    assert ("caps_tpu/ops/wcoj_fix.py", 9) in _lines(found)
    # the un-jitted composition wrapper is NOT itself a root
    assert all(line != 17 for _p, line in _lines(found))


def test_purity_live_wcoj_kernels_are_roots():
    """On the LIVE tree the ops/wcoj.py probes must be reached by the
    purity closure (jit-decorated roots) — and clean (the repo-clean
    test covers cleanliness; this asserts REACHABILITY, so a future
    refactor dropping the jit decorators cannot silently un-check the
    kernel layer)."""
    from caps_tpu.analysis.purity import traced_functions
    project = load_project(REPO)
    reached = {(path, fn) for path, fn in traced_functions(project)}
    wcoj_fns = {fn for path, fn in reached
                if path.endswith("caps_tpu/ops/wcoj.py")}
    assert {"probe_adj", "probe_pair", "multiplicity",
            "probe_id", "edge_keys"} <= wcoj_fns, wcoj_fns


def test_purity_fused_record_path_compute(tmp_path):
    src = """\
from caps_tpu.obs import clock


class ScanOp:
    def _compute(self):
        return clock.now()
"""
    p = _project(tmp_path, {"caps_tpu/relational/oppy.py": src})
    found = _findings(p, "tracer-purity")
    assert _lines(found) == {("caps_tpu/relational/oppy.py", 6)}
    assert "fused record path" in found[0].message


# -- error-taxonomy ----------------------------------------------------------

SERVE_ERRORS = """\
class ServeError(RuntimeError):
    pass


class Overloaded(ServeError):
    pass
"""

_TAXO_CONFIG = dataclasses.replace(
    AnalysisConfig(),
    expected_serve_modules=frozenset({"errors.py", "foo.py"}),
    worker_roots=())

SERVE_BAD_RAISE = """\
from caps_tpu.serve.errors import Overloaded


def shed():
    raise Overloaded("ok")


def wrong():
    raise TimeoutError("not a ServeError")
"""


def test_taxonomy_non_serve_error_raise_fires(tmp_path):
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/foo.py": SERVE_BAD_RAISE,
    }, _TAXO_CONFIG)
    found = _findings(p, "error-taxonomy")
    assert _lines(found) == {("caps_tpu/serve/foo.py", 9)}
    assert "TimeoutError" in found[0].message
    assert "does not inherit ServeError" in found[0].message


def test_taxonomy_resolves_serve_errors_via_sibling_modules(tmp_path):
    # a ServeError subclass imported from a SIBLING serve module (or
    # relatively) is valid provenance — the pass must not misreport it
    src = """\
from caps_tpu.serve.other import Overloaded
from .errors import ServeError


def shed():
    raise Overloaded("ok")


def base():
    raise ServeError("ok")
"""
    cfg = dataclasses.replace(
        _TAXO_CONFIG,
        expected_serve_modules=frozenset({"errors.py", "foo.py",
                                          "other.py"}))
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/other.py": "",
        "caps_tpu/serve/foo.py": src,
    }, cfg)
    assert _findings(p, "error-taxonomy") == []


def test_taxonomy_missing_expected_module_fires(tmp_path):
    p = _project(tmp_path, {"caps_tpu/serve/errors.py": SERVE_ERRORS},
                 _TAXO_CONFIG)
    found = _findings(p, "error-taxonomy")
    assert any("foo.py" in f.path and "MISSING" in f.message
               for f in found)


def test_taxonomy_exception_mutation_fires(tmp_path):
    src = """\
def handler():
    try:
        pass
    except Exception as ex:
        ex.my_note = "boom"
        raise
"""
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/foo.py": src,
    }, _TAXO_CONFIG)
    found = _findings(p, "error-taxonomy")
    assert ("caps_tpu/serve/foo.py", 5) in _lines(found)
    assert any("mutates caught exception" in f.message for f in found)


def test_taxonomy_unguarded_marker_stamp_fires(tmp_path):
    src = """\
def handler():
    try:
        pass
    except Exception as ex:
        ex.caps_failed_op = "Scan"
        raise
"""
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/foo.py": src,
    }, _TAXO_CONFIG)
    found = _findings(p, "error-taxonomy")
    assert any("first-writer-wins" in f.message for f in found)
    # the guarded idiom is clean
    guarded = src.replace(
        '        ex.caps_failed_op = "Scan"',
        '        if getattr(ex, "caps_failed_op", None) is None:\n'
        '            ex.caps_failed_op = "Scan"')
    p2 = _project(tmp_path / "g", {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/foo.py": guarded,
    }, _TAXO_CONFIG)
    assert _findings(p2, "error-taxonomy") == []


def test_taxonomy_swallowed_handler_fires(tmp_path):
    src = """\
def swallow():
    try:
        pass
    except Exception as ex:
        return None
"""
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/foo.py": src,
    }, _TAXO_CONFIG)
    found = _findings(p, "error-taxonomy")
    assert ("caps_tpu/serve/foo.py", 4) in _lines(found)
    assert "never uses it" in found[0].message


def test_taxonomy_worker_must_reach_classify(tmp_path):
    src = """\
class Server:
    def _worker_loop(self):
        self._step()

    def _step(self):
        pass
"""
    cfg = dataclasses.replace(
        _TAXO_CONFIG,
        worker_roots=(("caps_tpu/serve/srv.py", "Server._worker_loop"),),
        expected_serve_modules=frozenset({"errors.py", "srv.py"}))
    p = _project(tmp_path, {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/srv.py": src,
    }, cfg)
    found = _findings(p, "error-taxonomy")
    assert any("never reaches" in f.message for f in found)
    fixed = src.replace("def _step(self):\n        pass",
                        "def _step(self):\n        classify(None)")
    p2 = _project(tmp_path / "ok", {
        "caps_tpu/serve/errors.py": SERVE_ERRORS,
        "caps_tpu/serve/srv.py": fixed,
    }, cfg)
    assert _findings(p2, "error-taxonomy") == []


# -- clock-discipline --------------------------------------------------------

#: synthetic trees don't carry the real repo's pinned clock modules —
#: the vacuity-guard test covers that contract explicitly
_CLOCK_CONFIG = dataclasses.replace(
    AnalysisConfig(), expected_clock_modules=frozenset())


def test_clock_from_import_hole_fires(tmp_path):
    src = """\
from time import perf_counter


def t():
    return perf_counter()
"""
    p = _project(tmp_path, {"caps_tpu/serve/t.py": src}, _CLOCK_CONFIG)
    found = _findings(p, "clock-discipline")
    # the import line itself is the finding — the exact form the old
    # regex (matching `time.perf_counter(`) could never see
    assert _lines(found) == {("caps_tpu/serve/t.py", 1)}
    assert "from time import perf_counter" in found[0].message


def test_clock_aliased_module_fires(tmp_path):
    src = """\
import time as _t

now = _t.perf_counter
"""
    p = _project(tmp_path, {"caps_tpu/relational/t.py": src}, _CLOCK_CONFIG)
    found = _findings(p, "clock-discipline")
    assert _lines(found) == {("caps_tpu/relational/t.py", 3)}


def test_clock_exempts_clock_module(tmp_path):
    src = "import time as _time\nnow = _time.perf_counter\n"
    p = _project(tmp_path, {"caps_tpu/obs/clock.py": src}, _CLOCK_CONFIG)
    assert _findings(p, "clock-discipline") == []


def test_clock_expected_module_vacuity_guard(tmp_path):
    """A pinned clock module missing from the walk is a FINDING — the
    pass must not silently stop covering code whose correctness depends
    on the sanctioned clock (the result cache's recency decay)."""
    p = _project(tmp_path, {"caps_tpu/serve/t.py": "x = 1\n"})
    found = _findings(p, "clock-discipline")
    assert _lines(found) == {
        ("caps_tpu/relational/result_cache.py", 1)}
    assert "vacuous" in found[0].message
    # present → clean (and the module itself is checked as usual)
    p2 = _project(tmp_path / "ok", {
        "caps_tpu/serve/t.py": "x = 1\n",
        "caps_tpu/relational/result_cache.py":
            "from caps_tpu.obs import clock\nnow_t = clock.now\n"})
    assert _findings(p2, "clock-discipline") == []


# -- metric-names ------------------------------------------------------------

def test_metric_duplicate_kind_fires(tmp_path):
    src = """\
def wire(reg):
    reg.counter("serve.widgets").inc()
    reg.histogram("serve.widgets").observe(1.0)
"""
    p = _project(tmp_path, {"caps_tpu/serve/m.py": src})
    found = _findings(p, "metric-names")
    assert len(found) == 1
    assert "2 different kinds" in found[0].message
    assert "serve.widgets" in found[0].message


def test_metric_prefix_and_shape_fire(tmp_path):
    src = """\
def wire(reg):
    reg.counter("bogusprefix.x").inc()
    reg.counter("UPPER").inc()
"""
    p = _project(tmp_path, {"caps_tpu/serve/m.py": src})
    msgs = [f.message for f in _findings(p, "metric-names")]
    assert any("unsanctioned prefix" in m for m in msgs)
    assert any("dotted lowercase convention" in m for m in msgs)


def test_metric_histogram_snapshot_collision_fires(tmp_path):
    src = """\
def wire(reg):
    reg.histogram("serve.latency").observe(0.1)
    reg.counter("serve.latency.count").inc()
"""
    p = _project(tmp_path, {"caps_tpu/serve/m.py": src})
    msgs = [f.message for f in _findings(p, "metric-names")]
    assert any("snapshot expansion" in m for m in msgs)


# -- suppressions / framework ------------------------------------------------

def test_inline_suppression(tmp_path):
    src = ("from time import perf_counter  "
           "# capslint: disable=clock-discipline\n")
    p = _project(tmp_path, {"caps_tpu/serve/t.py": src}, _CLOCK_CONFIG)
    assert _findings(p, "clock-discipline") == []
    # disable=all works too, and an unrelated pass name does NOT suppress
    src2 = "from time import perf_counter  # capslint: disable=lock-order\n"
    p2 = _project(tmp_path / "b", {"caps_tpu/serve/t.py": src2},
                  _CLOCK_CONFIG)
    assert len(_findings(p2, "clock-discipline")) == 1


def test_unknown_pass_rejected(tmp_path):
    p = _project(tmp_path, {"caps_tpu/x.py": "pass\n"})
    with pytest.raises(KeyError):
        run_passes(p, only=["no-such-pass"])


@pytest.mark.parametrize("broken", [False, True])
def test_cli_json_and_exit_codes(tmp_path, capsys, broken):
    (tmp_path / "caps_tpu").mkdir()
    (tmp_path / "caps_tpu" / "bad.py").write_text(
        "from time import perf_counter\n")
    if broken:  # a file that does not parse is its own finding
        (tmp_path / "caps_tpu" / "broken.py").write_text("def oops(:\n")
    # satisfy the default config's pinned-module vacuity guard so the
    # single finding below is exactly the naked import
    (tmp_path / "caps_tpu" / "relational").mkdir()
    (tmp_path / "caps_tpu" / "relational" / "result_cache.py").write_text(
        "from caps_tpu.obs import clock\n")
    rc = capslint_main(["--root", str(tmp_path), "--json",
                        "--only", "clock-discipline"])
    out = json.loads(capsys.readouterr().out)
    by_pass = {f["pass"]: f["path"] for f in out}
    assert rc == 1 and len(out) == len(by_pass) == 1 + broken
    assert by_pass["clock-discipline"] == "caps_tpu/bad.py"
    if broken:  # reported under "parse", not as a naked timer
        assert by_pass["parse"] == "caps_tpu/broken.py"
    rc = capslint_main(["--list"])
    assert rc == 0
    listed = capsys.readouterr().out
    for name in pass_names():
        assert name in listed


# -- structured-log ----------------------------------------------------------

LOG_MODULE = """\
class EventLog:
    def emit(self, event, *, request_id, family, **fields):
        return {"event": event, "request_id": request_id,
                "family": family, **fields}
"""


def test_structured_log_missing_field_fires(tmp_path):
    caller = """\
def trip(log, req):
    log.emit("breaker.trip", request_id=req.request_id)
"""
    p = _project(tmp_path, {"caps_tpu/obs/log.py": LOG_MODULE,
                            "caps_tpu/serve/caller.py": caller})
    found = _findings(p, "structured-log")
    assert len(found) == 1
    f = found[0]
    assert f.path == "caps_tpu/serve/caller.py" and f.line == 2
    assert "family" in f.message and "request_id" not in f.message.split(
        "field(s) ")[1].split(" —")[0]


def test_structured_log_explicit_none_and_splat_pass(tmp_path):
    caller = """\
def ok(log, extra):
    log.emit("compaction.failure", request_id=None, family=None)
    log.emit("odd", **extra)  # splat: present-ness unverifiable
"""
    p = _project(tmp_path, {"caps_tpu/obs/log.py": LOG_MODULE,
                            "caps_tpu/serve/caller.py": caller})
    assert _findings(p, "structured-log") == []


def test_structured_log_missing_module_is_a_finding(tmp_path):
    p = _project(tmp_path, {"caps_tpu/serve/caller.py": "x = 1\n"})
    found = _findings(p, "structured-log")
    assert len(found) == 1
    assert found[0].path == "caps_tpu/obs/log.py"
    assert "missing" in found[0].message


def test_structured_log_module_without_anchor_is_a_finding(tmp_path):
    p = _project(tmp_path, {"caps_tpu/obs/log.py": "def emit(x):\n"
                                                   "    return x\n"})
    found = _findings(p, "structured-log")
    assert len(found) == 1 and "no anchor" in found[0].message


def test_structured_log_bare_emit_call_checked(tmp_path):
    log_mod = LOG_MODULE + """\


def emit(event, *, request_id, family):
    return (event, request_id, family)
"""
    caller = """\
from caps_tpu.obs.log import emit


def fire():
    emit("loose")
"""
    p = _project(tmp_path, {"caps_tpu/obs/log.py": log_mod,
                            "caps_tpu/serve/caller.py": caller})
    found = _findings(p, "structured-log")
    assert len(found) == 1 and found[0].line == 5


# -- the live repo -----------------------------------------------------------

def test_live_repo_is_clean():
    project = load_project(REPO)
    findings = run_passes(project)
    assert findings == [], "\n".join(f.format() for f in findings)
    assert set(pass_names()) == {"lock-order", "tracer-purity",
                                 "error-taxonomy", "clock-discipline",
                                 "metric-names", "structured-log"}


def test_live_repo_static_lock_graph_has_serve_edges():
    edges, index, _info = static_lock_graph(load_project(REPO))
    assert "devices.DeviceReplica.lock" in index.ids
    assert "plan_cache.PlanCache._lock" in index.ids
    assert "telemetry.ServingTelemetry._lock" in index.ids
    # the serve tier's real nesting is visible statically: admission's
    # condition is held while the shed is noted into the telemetry
    # window / the windowed service time is read for retry_after (the
    # device stream lock no longer nests the admission condition — the
    # service-time fold moved outside it)
    assert ("admission.AdmissionController._cond",
            "telemetry.ServingTelemetry._lock") in edges
    assert ("devices.DeviceReplica.lock",
            "devices.DeviceReplica._graphs_lock") in edges


def test_metrics_doc_has_no_drift():
    project = load_project(REPO)
    assert check_metrics_doc(project) is None
    doc = generate_metrics_doc(project)
    assert "| `serve.completed` | counter |" in doc


# -- runtime lock graph ------------------------------------------------------

def test_lockgraph_disabled_returns_plain_locks(monkeypatch):
    monkeypatch.delenv("CAPS_TPU_LOCK_GRAPH", raising=False)
    assert isinstance(lockgraph.make_lock("x.y"), type(threading.Lock()))


def test_lockgraph_records_edges_and_raises_on_cycle(monkeypatch):
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "1")
    lockgraph.reset()
    a = lockgraph.make_lock("t.a")
    b = lockgraph.make_lock("t.b")
    with a:
        with b:
            pass
    snap = lockgraph.lock_graph_snapshot()
    assert ("t.a", "t.b") in snap["edges"]
    assert lockgraph.find_cycle() is None
    with pytest.raises(lockgraph.LockOrderViolation) as exc_info:
        with b:
            with a:
                pass
    assert "t.a" in str(exc_info.value) and "t.b" in str(exc_info.value)
    # the offending edge is recorded, so the snapshot now shows the cycle
    assert lockgraph.find_cycle() is not None
    lockgraph.reset()


def test_lockgraph_record_mode_never_raises(monkeypatch):
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "record")
    lockgraph.reset()
    a = lockgraph.make_lock("r.a")
    b = lockgraph.make_lock("r.b")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    cycle = lockgraph.find_cycle()
    assert cycle is not None and cycle[0] == cycle[-1]
    lockgraph.reset()


def test_lockgraph_reentrant_rlock_records_no_self_edge(monkeypatch):
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "1")
    lockgraph.reset()
    r = lockgraph.make_rlock("t.r")
    with r:
        with r:
            pass
    snap = lockgraph.lock_graph_snapshot()
    assert snap["edges"] == [] and snap["nodes"] == ["t.r"]
    lockgraph.reset()


def test_lockgraph_condition_is_reentrant_like_stdlib(monkeypatch):
    # Condition() defaults to an RLock backing lock; the tracked
    # replacement must keep that, or code that legally nests
    # `with cond:` would deadlock ONLY under instrumentation
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "1")
    lockgraph.reset()
    cond = lockgraph.make_condition("t.recond")
    with cond:
        with cond:                   # re-entrant: must not deadlock
            cond.notify_all()
        # wait() from a re-entrant depth must release every level for
        # another thread, then restore them (the RLock save/restore
        # protocol through the proxy)
        woke = []

        def waker():
            with cond:
                cond.notify_all()
                woke.append(True)

        t = threading.Thread(target=waker)
        t.start()
        cond.wait(timeout=2)
        t.join(5)
        assert woke == [True]
    snap = lockgraph.lock_graph_snapshot()
    assert snap["edges"] == []       # reentrancy records no self-edges
    lockgraph.reset()


def test_lockgraph_condition_wait_releases(monkeypatch):
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "1")
    lockgraph.reset()
    cond = lockgraph.make_condition("t.cond")
    other = lockgraph.make_lock("t.other")
    done = threading.Event()

    def waiter():
        with cond:
            cond.wait(timeout=0.2)
            done.set()

    t = threading.Thread(target=waiter)
    t.start()
    # the waiter released the condition's lock inside wait(): another
    # thread can take it and notify
    with cond:
        cond.notify_all()
    t.join(5)
    assert done.is_set()
    with cond:
        with other:
            pass
    assert ("t.cond", "t.other") in lockgraph.lock_graph_snapshot()["edges"]
    lockgraph.reset()

"""Observability subsystem tests (caps_tpu/obs/ — ISSUE 3).

Covers: EXPLAIN plans without executing (poisoned scan hook), PROFILE
row counts match actual result cardinalities on the local and TPU
backends (plan-cache hits and fused replay included), PROFILE through a
plan-cache hit reports plan-phase time 0 and never poisons the cache
key, disabled-tracer overhead is bounded, the metrics registry /
snapshot API, the span exporters, and the collective instrumentation.
"""
from __future__ import annotations

import json
import os

import pytest

from caps_tpu import obs
from caps_tpu.obs import clock
from caps_tpu.obs.metrics import MetricsRegistry, diff_snapshots
from caps_tpu.obs.tracer import NULL_SPAN, Tracer
from caps_tpu.testing.factory import create_graph

CREATE = """
    CREATE (a:Person {name: 'Ada', age: 30}),
           (b:Person {name: 'Bo', age: 40}),
           (c:Person {name: 'Cy', age: 50}),
           (a)-[:KNOWS]->(b), (b)-[:KNOWS]->(c), (a)-[:KNOWS]->(c)
"""
Q = ("MATCH (a:Person)-[:KNOWS]->(b) WHERE a.age > $min "
     "RETURN a.name AS a, b.name AS b ORDER BY a, b")


# -- EXPLAIN ----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_explain_executes_nothing(make_session, backend, monkeypatch):
    session = make_session(backend)
    graph = create_graph(session, CREATE)

    # poison every execution entry point: any operator compute during
    # EXPLAIN means the plan executed
    from caps_tpu.relational import ops as R

    def poisoned(self):
        raise AssertionError("EXPLAIN must not execute operators")

    monkeypatch.setattr(R.ScanOp, "_compute", poisoned)
    monkeypatch.setattr(R.StartOp, "_compute", poisoned)

    res = graph.cypher("EXPLAIN " + Q, {"min": 0})
    assert res.records is None
    assert res.metrics["mode"] == "explain"
    for phase in ("ir", "logical", "relational"):
        assert phase in res.plans and res.plans[phase]
    assert "Scan" in res.plans["relational"]
    assert "=== RELATIONAL ===" in res.explain()


def test_explain_catalog_statements_do_not_mutate(make_session):
    session = make_session("local")
    graph = create_graph(session, CREATE)
    version0 = session.catalog.version
    res = graph.cypher(
        "EXPLAIN CATALOG CREATE GRAPH session.obs_explain { "
        "MATCH (n:Person) CONSTRUCT CLONE n RETURN GRAPH }")
    assert res.records is None
    # nothing stored, nothing evicted: the catalog fingerprint is unchanged
    assert session.catalog.version == version0
    with pytest.raises(Exception):
        session.cypher("FROM GRAPH session.obs_explain MATCH (n) "
                       "RETURN count(*) AS c")


# -- PROFILE ----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["local", "tpu", "sharded"])
def test_profile_rows_match_cardinality(make_session, backend):
    session = make_session(backend)
    graph = create_graph(session, CREATE)
    res = graph.cypher("PROFILE " + Q, {"min": 35})
    rows = res.records.to_maps()
    assert rows == [{"a": "Bo", "b": "Cy"}]
    assert res.metrics["mode"] == "profile"
    assert res.profile is not None
    assert res.profile["rows"] == len(rows)
    # every executed node carries measurements
    def walk(node):
        yield node
        for c in node["children"]:
            yield from walk(c)
    executed = [n for n in walk(res.profile) if n["executed"]]
    assert executed, res.profile
    for n in executed:
        assert n["seconds"] >= 0.0 and n["rows"] >= 0
    # rendered tree rides the plans dict / explain()
    assert "rows=" in res.plans["profile"]
    assert "=== PROFILE ===" in res.explain()


def test_profile_fused_replay_rows_exact(make_session):
    """TPU path: PROFILE through fused replay (exact and generic) still
    reports the actual result cardinality, and labels the run mode."""
    session = make_session("tpu")
    graph = create_graph(session, CREATE)
    for min_age in (35, 25, 35):  # converge recordings / generic stream
        graph.cypher(Q, {"min": min_age})
    res = graph.cypher("PROFILE " + Q, {"min": 25})
    rows = res.records.to_maps()
    assert len(rows) == 3
    assert res.profile["rows"] == len(rows)
    assert res.metrics["fused_mode"] in ("record", "replay", "replay_gen",
                                         "eager")
    assert res.profile.get("timing") in ("device", "dispatch", "host")


def test_profile_aggregate_replay_span(make_session):
    """With per-op sync off, replayed PROFILE runs report device time as
    ONE per-replay aggregate and tag per-op numbers as dispatch-only —
    never silently wrong."""
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.okapi.config import EngineConfig
    session = TPUCypherSession(config=EngineConfig(
        profile_sync_each_op=False))
    graph = create_graph(session, CREATE)
    for _ in range(2):
        graph.cypher(Q, {"min": 25})
    res = graph.cypher("PROFILE " + Q, {"min": 25})
    assert res.metrics["fused_mode"] in ("replay", "replay_gen")
    assert res.profile["timing"] == "dispatch"
    assert res.metrics["replay_device_s"] >= 0.0
    assert res.profile["rows"] == len(res.records.to_maps())


@pytest.mark.parametrize("backend", ["local", "tpu"])
def test_profile_plan_cache_hit_not_poisoned(make_session, backend):
    session = make_session(backend)
    graph = create_graph(session, CREATE)
    r1 = graph.cypher(Q, {"min": 35})
    assert r1.metrics["plan_cache"] == "miss"
    entries = session.plan_cache.stats()["entries"]

    # PROFILE hits the SAME entry (prefix stripped before the key)...
    res = graph.cypher("PROFILE " + Q, {"min": 45})
    assert res.metrics["plan_cache"] == "hit"
    # ...reports plan-phase time 0 (nothing was re-planned)...
    assert res.metrics["parse_s"] == 0.0
    assert res.metrics["plan_s"] == 0.0
    assert res.metrics["relational_s"] == 0.0
    assert res.profile["rows"] == len(res.records.to_maps())
    # ...and stores no extra entry under a PROFILE-flavored key
    assert session.plan_cache.stats()["entries"] == entries

    # later plain runs are unaffected: still a hit, no profile leakage
    r3 = graph.cypher(Q, {"min": 35})
    assert r3.metrics["plan_cache"] == "hit"
    assert "profile" not in r3.plans and r3.profile is None


def test_profile_and_plain_queries_agree(make_session):
    session = make_session("local")
    graph = create_graph(session, CREATE)
    plain = graph.cypher(Q, {"min": 0}).records.to_maps()
    profiled = graph.cypher("PROFILE " + Q, {"min": 0}).records.to_maps()
    assert plain == profiled


# -- query_mode / frontend ---------------------------------------------------

def test_query_mode_stripping():
    from caps_tpu.frontend.parser import parse_query, query_mode
    assert query_mode("MATCH (n) RETURN n") == (None, "MATCH (n) RETURN n")
    mode, body = query_mode("  explain MATCH (n) RETURN n")
    assert mode == "explain" and body == "MATCH (n) RETURN n"
    mode, body = query_mode("/* c */ PROFILE\nMATCH (n) RETURN n")
    assert mode == "profile" and body == "MATCH (n) RETURN n"
    # prefixed text parses (prepare() validates the full string)
    parse_query("PROFILE MATCH (n) RETURN n")
    parse_query("EXPLAIN MATCH (n) RETURN n")
    # unlexable text passes through for the parser to report
    assert query_mode("MATCH 'unterminated")[0] is None


def test_prepared_profile(make_session):
    session = make_session("local")
    graph = create_graph(session, CREATE)
    prep = graph.prepare("PROFILE " + Q)
    res = prep.run({"min": 35})
    assert res.metrics["mode"] == "profile"
    assert res.profile["rows"] == len(res.records.to_maps())


# -- overhead ---------------------------------------------------------------

def test_disabled_tracer_overhead_bounded(make_session):
    """The disabled path must be a shared no-op span (one enabled check,
    no allocation) and must record nothing across a repeated query."""
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN
    assert tr.span("y", kind="operator") is NULL_SPAN
    # the disabled call itself is cheap: 100k spans well under a second
    t0 = clock.now()
    for _ in range(100_000):
        with tr.span("hot"):
            pass
    assert clock.now() - t0 < 1.0
    assert tr.spans == [] and tr.dropped == 0

    session = make_session("local")
    graph = create_graph(session, CREATE)
    for _ in range(5):
        graph.cypher(Q, {"min": 25})
    assert session.tracer.enabled is False
    assert session.tracer.spans == []


# -- metrics registry / snapshots -------------------------------------------

def test_metrics_registry_instruments():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    reg.gauge("live", fn=lambda: 42)
    reg.observe("h", 0.5)
    reg.observe("h", 1.5)
    snap = reg.snapshot()
    assert snap["c"] == 3 and snap["g"] == 7 and snap["live"] == 42
    assert snap["h.count"] == 2 and snap["h.sum"] == 2.0
    assert snap["h.min"] == 0.5 and snap["h.max"] == 1.5

    d = diff_snapshots({"c": 1, "x": 5}, {"c": 3, "y": 2, "s": "str"})
    assert d["c"] == 2 and d["y"] == 2 and d["s"] == "str"


def test_session_metrics_snapshot_absorbs_scattered_stats(make_session):
    session = make_session("tpu")
    graph = create_graph(session, CREATE)
    snap0 = session.metrics_snapshot()
    graph.cypher(Q, {"min": 25})
    graph.cypher(Q, {"min": 35})
    delta = diff_snapshots(snap0, session.metrics_snapshot())
    assert delta["plan_cache.misses"] == 1
    assert delta["plan_cache.hits"] == 1
    assert delta["query.execute_s.count"] == 2
    # the device/fused counters the registry absorbs
    for key in ("backend.ici_payload_bytes", "backend.syncs",
                "fused.recordings", "fused.replays"):
        assert key in delta, sorted(delta)


def test_plan_cache_invalidations_in_snapshot(make_session):
    session = make_session("local")
    graph = create_graph(session, CREATE)
    session.catalog.store("obs_snap", graph)
    # this plan DEPENDS on the catalog name; a graph-object plan would
    # survive catalog churn (scoped eviction)
    session.cypher("FROM GRAPH session.obs_snap MATCH (n:Person) "
                   "RETURN count(*) AS c")
    snap0 = session.metrics_snapshot()
    # mutating the referenced name evicts exactly its dependents
    session.catalog.store("obs_snap", create_graph(session, CREATE))
    delta = diff_snapshots(snap0, session.metrics_snapshot())
    assert delta["plan_cache.invalidations"] >= 1


# -- exporters ---------------------------------------------------------------

def test_exporters(make_session, tmp_path):
    session = make_session("local")
    graph = create_graph(session, CREATE)
    graph.cypher("PROFILE " + Q, {"min": 25})
    assert session.tracer.spans, "PROFILE must collect spans"

    chrome = session.export_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(chrome))
    events = doc["traceEvents"]
    assert events
    names = {e["name"] for e in events}
    assert "query" in names and any(n.startswith("op.") for n in names)
    for e in events:
        assert e["ph"] in ("X", "i")
        assert e["ts"] >= 0

    jsonl = session.export_trace(str(tmp_path / "trace.jsonl"), fmt="jsonl")
    lines = [json.loads(l) for l in open(jsonl) if l.strip()]
    assert len(lines) == len(events)
    roots = [l for l in lines if l["parent_id"] == -1]
    assert roots and roots[0]["name"] == "query"
    # parent links resolve
    ids = {l["span_id"] for l in lines}
    assert all(l["parent_id"] in ids or l["parent_id"] == -1
               for l in lines)

    with pytest.raises(ValueError):
        session.export_trace(str(tmp_path / "x"), fmt="bogus")


def test_span_nesting_and_events():
    tr = Tracer(enabled=True)
    with tr.span("outer", kind="query") as outer:
        with tr.span("inner", kind="phase") as inner:
            tr.event("tick", bytes=10)
        outer.annotate(rows=5)
    assert len(tr.spans) == 1
    root = tr.spans[0]
    assert root.name == "outer" and root.rows == 5
    assert [c.name for c in root.children] == ["inner"]
    assert [c.name for c in root.children[0].children] == ["tick"]
    assert root.children[0].children[0].bytes == 10
    assert root.wall_s >= root.children[0].wall_s >= 0.0


# -- collectives instrumentation ---------------------------------------------

def test_collective_note_records_trace_time_counters():
    import numpy as np
    from caps_tpu.parallel.collectives import note_collective
    reg = obs.global_registry()
    snap0 = reg.snapshot()
    note_collective("unit_test_op", np.zeros((4, 4), np.int32))
    delta = diff_snapshots(snap0, reg.snapshot())
    assert delta["collectives.unit_test_op.calls"] == 1
    assert delta["collectives.unit_test_op.traced_bytes"] == 64


def test_sharded_query_counts_collectives(make_session):
    """A sharded var-expand compiles ring/exchange programs whose
    collective launches land in the process-global registry."""
    session = make_session("sharded")
    graph = create_graph(session, CREATE)
    rows = graph.cypher(
        "MATCH (a:Person)-[:KNOWS*1..2]->(f) RETURN count(*) AS c"
    ).records.to_maps()
    assert rows[0]["c"] > 0
    # trace-time counters tick once per XLA compile, so an earlier test
    # in this process may have paid the compile already — assert the
    # cumulative registry state, not a per-query delta
    snap = obs.global_registry().snapshot()
    traced = sum(v for k, v in snap.items()
                 if k.startswith("collectives.") and k.endswith(".calls")
                 and k != "collectives.unit_test_op.calls"
                 and isinstance(v, (int, float)))
    assert traced >= 1, sorted(k for k in snap if "collect" in k)


# -- spans on the profiler's clock, sync accounting, xla events --------------

def test_profiler_span_opens_annotation_and_tracer_span_together():
    from caps_tpu.obs.tracer import profiler_span, timed_span
    off = Tracer(enabled=False)
    with profiler_span("caps_tpu.table.filter", off) as sp:
        assert sp is NULL_SPAN          # the disabled contract holds
    on = Tracer(enabled=True)
    with profiler_span("caps_tpu.serve.execute", on, request_ids="7 8",
                       batch=2) as outer:
        with profiler_span("caps_tpu.Join", on, kind="operator",
                           tracer_name="op.Join") as inner:
            inner.annotate(rows=3)
    assert outer.name == "caps_tpu.serve.execute"      # same name as the
    assert outer.attrs == {"request_ids": "7 8", "batch": 2}  # annotation
    assert [c.name for c in outer.children] == ["op.Join"]
    assert outer.children[0].kind == "operator"
    assert outer.children[0].rows == 3
    # no tracer given, none active on this thread: nothing recorded
    with profiler_span("caps_tpu.sync") as sp:
        assert sp is NULL_SPAN
    with timed_span("caps_tpu.sync") as t:
        pass
    assert t.seconds >= 0.0


class _TickClock:
    """Every read of ``now`` is one second later than the last."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        self.t += 1.0
        return self.t


FOF2 = ("MATCH (a:Person)-[:KNOWS]->()-[:KNOWS]->(c) WHERE a.name = $name "
        "RETURN count(DISTINCT c) AS n")


def test_sync_accounting_one_syncs_worth_per_generic_replay(make_session,
                                                            monkeypatch):
    """``backend.syncs`` counts what it counted before the consolidation;
    ``backend.sync_wait_s`` and ``backend.d2h_bytes`` grow with it, by
    exactly one read a generic replay."""
    session = make_session("tpu")
    graph = create_graph(session, CREATE)
    graph.cypher(FOF2, {"name": "Ada"})                   # records
    assert session.fused.last_mode == "record"
    snap0 = session.metrics_snapshot()
    assert snap0["backend.syncs"] >= 1
    assert snap0["backend.sync_wait_s"] > 0.0
    assert snap0["backend.d2h_bytes"] >= snap0["backend.syncs"]
    monkeypatch.setattr(clock, "now", _TickClock().now)
    for i, name in enumerate(["Bo", "Cy", "Bo"], 1):
        result = graph.cypher(FOF2, {"name": name})
        d = diff_snapshots(snap0, session.metrics_snapshot())
        if session.fused.last_mode == "replay":
            break   # an exact replay: no sync at all, checked below
        assert session.fused.last_mode == "replay_gen"
        assert d["backend.syncs"] == i == d["fused.generic_replays"]
        # one timed region a replay: two clock reads one second apart
        assert d["backend.sync_wait_s"] == pytest.approx(float(i))
        # the violation flag alone (the count's one row is not ``live``)
        assert d["backend.d2h_bytes"] == i
    before = session.metrics_snapshot()
    graph.cypher(FOF2, {"name": "Ada"})                   # exact replay
    assert session.fused.last_mode == "replay"
    d = diff_snapshots(before, session.metrics_snapshot())
    assert d["backend.syncs"] == 0 and d["backend.d2h_bytes"] == 0
    assert d["backend.sync_wait_s"] == 0.0
    # materialization is a device->host read too: timed and sized, but
    # never a counted sync
    rows = result.records.to_maps()
    d = diff_snapshots(before, session.metrics_snapshot())
    assert len(rows) == 1 and d["backend.syncs"] == 0
    assert d["backend.d2h_bytes"] > 0 and d["backend.sync_wait_s"] >= 1.0


def test_wait_accounting_loses_nothing_across_threads(make_session):
    """A worker materializing a result accounts its reads beside the
    worker that dispatches: neither's update may be lost."""
    import threading
    backend = make_session("tpu").backend
    base = (backend.syncs, backend.sync_wait_s, backend.d2h_bytes)
    n = 20_000

    def reads(counted):
        for _ in range(n):
            backend.account_wait(0.5, 8, syncs=counted)

    threads = [threading.Thread(target=reads, args=(c,)) for c in (1, 0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert backend.syncs == base[0] + n
    assert backend.sync_wait_s == pytest.approx(base[1] + n)  # 2n halves
    assert backend.d2h_bytes == base[2] + 16 * n


def test_xla_events_count_first_shape_compiles_only(make_session):
    import jax
    import jax.numpy as jnp
    session = make_session("tpu")       # installs the listener
    snap = session.metrics_snapshot()
    assert {"xla.traces", "xla.compiles", "xla.compile_s"} <= set(snap)
    # inputs first: making them may compile too, or not (other tests)
    x11, x13 = jnp.arange(11), jnp.arange(13)
    jax.block_until_ready((x11, x13))
    snap = session.metrics_snapshot()
    fn = jax.jit(lambda x: x * 3 + 1)
    fn(x11).block_until_ready()                         # first shape
    first = diff_snapshots(snap, session.metrics_snapshot())
    assert first["xla.compiles"] >= 1 and first["xla.traces"] >= 1
    assert first["xla.compile_s"] > 0.0
    snap = session.metrics_snapshot()
    fn(x11).block_until_ready()                         # cached
    again = diff_snapshots(snap, session.metrics_snapshot())
    assert again["xla.compiles"] == 0 and again["xla.traces"] == 0
    # a second session does not install a second listener
    make_session("tpu")
    snap = session.metrics_snapshot()
    jax.jit(lambda x: x * 5 - 2)(x13).block_until_ready()
    once = diff_snapshots(snap, session.metrics_snapshot())
    assert once["xla.compiles"] == first["xla.compiles"]


def _contains(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_trace_nests_spans_under_the_executing_thread(
        make_session, tmp_path):
    """Three served reads (a recording, a generic replay, an exact
    replay) under ``jax.profiler``: on the thread that holds the
    execution lock ``caps_tpu.serve.execute`` > ``caps_tpu.fused.*`` >
    ``caps_tpu.<Operator>`` > ``caps_tpu.table.*`` > ``caps_tpu.sync``;
    what a worker does without the lock carries no ``caps_tpu.`` name."""
    import jax
    from jax.profiler import ProfileData
    from caps_tpu.serve import QueryServer
    session = make_session("tpu")
    graph = create_graph(session, CREATE)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with QueryServer(session, graph=graph) as server:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            modes = []
            for name in ("Ada", "Bo", "Ada"):
                assert len(server.submit(FOF2, {"name": name})
                           .rows(timeout=60)) == 1
                modes.append(session.fused.last_mode)
        finally:
            jax.profiler.stop_trace()
    assert modes == ["record", "replay_gen", "replay"]
    import glob
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    threads = []        # per host thread: [(name, start, end)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events
                   if e.name.startswith(("caps_tpu.", "serve.", "table."))]
            if evs:
                threads.append(evs)
    everything = [e for evs in threads for e in evs]
    names = {e[0] for e in everything}
    assert {"serve.request", "serve.lock_wait", "serve.materialize",
            "table.to_host", "caps_tpu.serve.execute", "caps_tpu.plan.lookup",
            "caps_tpu.fused.record", "caps_tpu.fused.replay_gen",
            "caps_tpu.fused.replay", "caps_tpu.fused.epilogue",
            "caps_tpu.Join", "caps_tpu.table.filter",
            "caps_tpu.table.join.probe", "caps_tpu.table.join.expand",
            "caps_tpu.table.join.gather", "caps_tpu.table.group",
            "caps_tpu.sync"} <= names, sorted(names)
    # arguments never became part of a name (trace_reduce sums by name)
    assert not [n for n in names if "#" in n or "=" in n]
    executes = [e for e in everything if e[0] == "caps_tpu.serve.execute"]
    assert len(executes) == 3
    for evs in threads:
        held = [e for e in evs if e[0] == "caps_tpu.serve.execute"]
        for e in evs:
            if e[0].startswith("caps_tpu.") and e not in held:
                # on-stream names only on the thread holding the lock,
                # while it holds it
                assert any(_contains(h, e) for h in held), e
            if e[0] in ("serve.lock_wait", "serve.materialize"):
                assert not any(_contains(h, e) for h in held), e
    def inside(name_prefix, outers):
        return [e for evs in threads for e in evs
                if e[0].startswith(name_prefix)
                and any(_contains(o, e) and o is not e for o in outers)]
    fused = inside("caps_tpu.fused.", executes)
    assert {e[0] for e in fused} == {
        "caps_tpu.fused.record", "caps_tpu.fused.replay_gen",
        "caps_tpu.fused.replay", "caps_tpu.fused.epilogue"}
    operators = [e for e in inside("caps_tpu.", fused)
                 if e[0].split(".")[1][:1].isupper()]
    assert {"caps_tpu.Join", "caps_tpu.Aggregate"} <= {e[0]
                                                       for e in operators}
    tables = inside("caps_tpu.table.", operators)
    assert tables
    syncs = [e for e in everything if e[0] == "caps_tpu.sync"]
    # the recording syncs every size inside the table method that needs
    # it; a generic replay syncs once, in the epilogue; an exact replay
    # not at all
    record = [e for e in fused if e[0] == "caps_tpu.fused.record"][0]
    in_record = [s for s in syncs if _contains(record, s)]
    assert in_record and all(any(_contains(t, s) for t in tables)
                             for s in in_record)
    epilogues = [e for e in fused if e[0] == "caps_tpu.fused.epilogue"]
    assert len(epilogues) == 1
    assert [s for s in syncs if _contains(epilogues[0], s)]
    assert len(syncs) == len(in_record) + 1
    materialize = [e for e in everything if e[0] == "serve.materialize"]
    assert len(materialize) == 3
    # its column reads are named off-stream (a cold plan's statistics
    # sketch reads columns under the lock, under the same name)
    assert all([t for t in everything if t[0] == "table.to_host"
                and _contains(m, t)] for m in materialize)

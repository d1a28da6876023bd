"""Live graph updates: snapshot isolation, failure-atomic writes,
compaction, and mixed read/write serving (ISSUE 8).

Covers the write path end to end:

* Cypher ``CREATE``/``SET``/``DELETE`` semantics on versioned graphs,
  on both the local oracle and the device backend;
* the programmatic ``graph.apply(updates)`` API;
* snapshot isolation: in-flight readers finish on the snapshot they
  started with, torn reads are impossible by construction;
* failure atomicity: an injected abort mid-commit rolls back the delta
  tables AND the string pool, and a retried write succeeds exactly once;
* compaction: digest parity between "apply then read" and "read the
  post-compaction snapshot", failure containment under
  ``flaky_compaction``, and the serve-tier background compactor;
* scoped plan-cache eviction: a write to one graph never evicts an
  unrelated graph's cached plans;
* the LDBC-interactive IU-style insert subset through the server;
* the acceptance soak: 8 clients at >= 20% writes under injected write
  aborts — availability 1.0, every reader digest-equal to a serial
  execution on its admission-time snapshot, at least one compaction
  completing under load.
"""
from __future__ import annotations

import threading

import pytest

from caps_tpu.relational.session import result_digest
from caps_tpu.relational.updates import (CreateNode, CreateRel, DeleteNode,
                                         DeleteRel, SetNodeProps,
                                         UpdateError, VersionedGraph,
                                         versioned)
from caps_tpu.testing.factory import create_graph

BACKENDS = ["local", "tpu"]

SOCIAL = ("CREATE (a:Person {name:'Alice', age:30})-[:KNOWS {since:2018}]->"
          "(b:Person {name:'Bob', age:25}), "
          "(b)-[:KNOWS {since:2020}]->(c:Person {name:'Carol', age:41})")


def _vg(session, create: str = SOCIAL) -> VersionedGraph:
    return versioned(session, create_graph(session, create))


def _rows(result):
    return result.records.to_maps() if result.records is not None else []


def _names(graph):
    return [r["n"] for r in _rows(graph.cypher(
        "MATCH (p:Person) RETURN p.name AS n ORDER BY n"))]


# -- Cypher write semantics --------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_create_nodes_and_rels(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    r = vg.cypher("CREATE (:Person {name:'Dave', age:$a})", {"a": 52})
    assert r.metrics["updates"]["created_nodes"] == 1
    assert r.metrics["snapshot_version"] == 1
    assert _names(vg) == ["Alice", "Bob", "Carol", "Dave"]
    # MATCH ... CREATE: one relationship per matched pair
    vg.cypher("MATCH (a:Person {name:'Alice'}), (d:Person {name:'Dave'}) "
              "CREATE (a)-[:KNOWS {since:$y}]->(d)", {"y": 2024})
    got = _rows(vg.cypher(
        "MATCH (:Person {name:'Alice'})-[r:KNOWS]->(t) "
        "RETURN t.name AS t, r.since AS y ORDER BY y"))
    assert got == [{"t": "Bob", "y": 2018}, {"t": "Dave", "y": 2024}]
    # whole-pattern CREATE with a fresh intermediate node
    vg.cypher("CREATE (:City {name:'Zurich'})<-[:LIVES_IN]-"
              "(:Person {name:'Erin', age:29})")
    assert _rows(vg.cypher(
        "MATCH (p:Person)-[:LIVES_IN]->(c:City) "
        "RETURN p.name AS p, c.name AS c")) == \
        [{"p": "Erin", "c": "Zurich"}]


@pytest.mark.parametrize("backend", BACKENDS)
def test_create_per_matched_row(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    # CREATE executes once per matched row (Cypher semantics)
    r = vg.cypher("MATCH (p:Person) CREATE (:Shadow {of: p.name})")
    assert r.metrics["updates"]["created_nodes"] == 3
    assert _rows(vg.cypher("MATCH (s:Shadow) RETURN count(*) AS c")) == \
        [{"c": 3}]


@pytest.mark.parametrize("backend", BACKENDS)
def test_set_properties(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    # computed SET value evaluates through the read pipeline
    vg.cypher("MATCH (p:Person {name:'Bob'}) "
              "SET p.age = p.age + 1, p.nick = 'bobby'")
    assert _rows(vg.cypher("MATCH (p:Person {name:'Bob'}) "
                           "RETURN p.age AS a, p.nick AS k")) == \
        [{"a": 26, "k": "bobby"}]
    # += merges, null removes
    vg.cypher("MATCH (p:Person {name:'Bob'}) SET p += $m",
              {"m": {"nick": None, "city": "Bern"}})
    assert _rows(vg.cypher("MATCH (p:Person {name:'Bob'}) "
                           "RETURN p.nick AS k, p.city AS c")) == \
        [{"k": None, "c": "Bern"}]
    # = replaces the whole property map
    vg.cypher("MATCH (p:Person {name:'Bob'}) SET p = $m",
              {"m": {"name": "Bob", "age": 30}})
    assert _rows(vg.cypher("MATCH (p:Person {name:'Bob'}) "
                           "RETURN p.age AS a, p.city AS c")) == \
        [{"a": 30, "c": None}]


@pytest.mark.parametrize("backend", BACKENDS)
def test_delete_semantics(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    # deleting a connected node without DETACH is a constraint error,
    # and the failed write changes NOTHING (atomicity)
    v_before = vg.current().snapshot_version
    with pytest.raises(UpdateError):
        vg.cypher("MATCH (p:Person {name:'Bob'}) DELETE p")
    assert vg.current().snapshot_version == v_before
    assert _names(vg) == ["Alice", "Bob", "Carol"]
    # DETACH DELETE removes the node and its incident relationships
    r = vg.cypher("MATCH (p:Person {name:'Bob'}) DETACH DELETE p")
    assert r.metrics["updates"]["deleted_nodes"] == 1
    assert r.metrics["updates"]["deleted_rels"] == 2
    assert _names(vg) == ["Alice", "Carol"]
    assert _rows(vg.cypher("MATCH ()-[r:KNOWS]->() "
                           "RETURN count(*) AS c")) == [{"c": 0}]
    # relationship delete leaves endpoints
    vg.cypher("MATCH (a:Person {name:'Alice'}), (c:Person {name:'Carol'}) "
              "CREATE (a)-[:KNOWS {since:2025}]->(c)")
    vg.cypher("MATCH (:Person {name:'Alice'})-[r:KNOWS]->() DELETE r")
    assert _names(vg) == ["Alice", "Carol"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_update_rejections(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    plain = create_graph(s, "CREATE (:Person {name:'X'})")
    with pytest.raises(UpdateError):
        plain.cypher("CREATE (:Person {name:'Y'})")
    with pytest.raises(UpdateError):
        vg.current().cypher("CREATE (:Person {name:'Y'})")
    with pytest.raises(UpdateError):
        vg.cypher("CREATE (n:Person) RETURN n")
    with pytest.raises(UpdateError):
        vg.cypher("MATCH (n:Person) SET n:Admin")
    # failed statements committed nothing
    assert vg.current().snapshot_version == 0


def test_explain_update_commits_nothing(make_session):
    s = make_session("local")
    vg = _vg(s)
    res = s.cypher_on_graph(vg, "EXPLAIN MATCH (p:Person {name:'Alice'}) "
                                "CREATE (p)-[:LIKES]->(:Thing)")
    assert "CreateNode" in res.plans["updates"]
    assert "CreateRel" in res.plans["updates"]
    assert "relational" in res.plans
    assert vg.current().snapshot_version == 0


# -- programmatic apply ------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_programmatic_apply(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    a = CreateNode(labels=("Person",), properties={"name": "Zed", "age": 7})
    info = vg.apply([a, CreateRel("KNOWS", a, 0, {"since": 2030})])
    assert info.created_nodes == 1 and info.created_rels == 1
    assert _rows(vg.cypher(
        "MATCH (z:Person {name:'Zed'})-[r:KNOWS]->(t) "
        "RETURN t.name AS t, r.since AS y")) == \
        [{"t": "Alice", "y": 2030}]
    vg.apply([SetNodeProps(a, {"age": 8})])
    assert _rows(vg.cypher("MATCH (z:Person {name:'Zed'}) "
                           "RETURN z.age AS a")) == [{"a": 8}]
    # validation failures are atomic no-ops
    v = vg.current().snapshot_version
    with pytest.raises(UpdateError):
        vg.apply([DeleteRel(999_999)])
    with pytest.raises(UpdateError):
        vg.apply([CreateRel("KNOWS", 0, 999_999)])
    assert vg.current().snapshot_version == v
    vg.apply([DeleteNode(a, detach=True)])
    assert _rows(vg.cypher("MATCH (z:Person {name:'Zed'}) "
                           "RETURN count(*) AS c")) == [{"c": 0}]


# -- snapshot isolation ------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_snapshot_isolation_unit(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    snap = vg.current()
    before = result_digest(snap.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a"))
    vg.cypher("CREATE (:Person {name:'New', age:1})")
    vg.cypher("MATCH (p:Person {name:'Alice'}) SET p.age = 99")
    vg.cypher("MATCH (p:Person {name:'Carol'}) DETACH DELETE p")
    # the pinned snapshot still reads its version of the world
    assert result_digest(snap.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a")) == before
    # while the handle sees everything
    assert _names(vg) == ["Alice", "Bob", "New"]
    assert _rows(vg.cypher("MATCH (p:Person {name:'Alice'}) "
                           "RETURN p.age AS a")) == [{"a": 99}]


# -- failure atomicity -------------------------------------------------------

def test_abort_write_rolls_back_completely(make_session):
    from caps_tpu.testing.faults import abort_write
    s = make_session("tpu")
    vg = _vg(s)
    pool_before = len(s.backend.pool)
    v_before = vg.current().snapshot_version
    digest_before = result_digest(vg.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a"))
    with abort_write(s, after_n_columns=1, n_times=1) as budget:
        with pytest.raises(Exception):
            vg.cypher("CREATE (:Person {name:'Torn', age:1})")
    assert budget.injected == 1
    # nothing committed, nothing leaked: version, data, AND the string
    # pool (the fused replayability fence) are exactly as before
    assert vg.current().snapshot_version == v_before
    assert len(s.backend.pool) == pool_before
    assert result_digest(vg.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a")) == \
        digest_before
    assert s.metrics_snapshot()["updates.rolled_back"] >= 1
    # the SAME write retried (the serving tier's TRANSIENT path) lands
    # exactly once
    vg.cypher("CREATE (:Person {name:'Torn', age:1})")
    assert _rows(vg.cypher("MATCH (p:Person {name:'Torn'}) "
                           "RETURN count(*) AS c")) == [{"c": 1}]


def test_abort_between_delta_columns(make_session):
    """An abort AFTER some delta columns already placed (mid-table)
    still rolls back to a clean snapshot."""
    from caps_tpu.testing.faults import abort_write
    s = make_session("tpu")
    vg = _vg(s)
    with abort_write(s, after_n_columns=2, n_times=1):
        with pytest.raises(Exception):
            vg.cypher("CREATE (:Person {name:'A1', age:1}), "
                      "(:Person {name:'A2', age:2})")
    assert _names(vg) == ["Alice", "Bob", "Carol"]
    vg.cypher("CREATE (:Person {name:'A1', age:1})")
    assert "A1" in _names(vg)


# -- compaction --------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_compaction_digest_parity(make_session, backend):
    s = make_session(backend)
    vg = _vg(s)
    vg.cypher("CREATE (:Person {name:'Dave', age:52})")
    vg.cypher("MATCH (p:Person {name:'Alice'}) SET p.age = 31")
    vg.cypher("MATCH (p:Person {name:'Carol'}) DETACH DELETE p")
    vg.cypher("MATCH (a:Person {name:'Alice'}), (d:Person {name:'Dave'}) "
              "CREATE (a)-[:KNOWS {since:2025}]->(d)")
    q = ("MATCH (a:Person)-[r:KNOWS]->(b:Person) "
         "RETURN a.name AS a, r.since AS y, b.name AS b, b.age AS age")
    before_nodes = result_digest(vg.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a"))
    before_edges = result_digest(vg.cypher(q))
    assert vg.delta_rows() > 0
    assert vg.compact() is True
    assert vg.delta_rows() == 0
    # "apply then read" is digest-equal to "read the post-compaction
    # snapshot"
    assert result_digest(vg.cypher(
        "MATCH (p:Person) RETURN p.name AS n, p.age AS a")) == before_nodes
    assert result_digest(vg.cypher(q)) == before_edges
    # ids survive compaction: more writes keep composing
    vg.cypher("MATCH (p:Person {name:'Dave'}) SET p.age = 53")
    assert _rows(vg.cypher("MATCH (p:Person {name:'Dave'}) "
                           "RETURN p.age AS a")) == [{"a": 53}]


def test_flaky_compaction_contained(make_session):
    from caps_tpu.testing.faults import flaky_compaction
    s = make_session("tpu")
    vg = _vg(s)
    vg.cypher("CREATE (:Person {name:'Dave', age:52})")
    digest = result_digest(vg.cypher("MATCH (p:Person) RETURN p.name AS n"))
    with flaky_compaction(s, error_rate=1.0, n_times=1) as budget:
        with pytest.raises(Exception):
            vg.compact()
    assert budget.injected == 1
    # the failed fold changed nothing; serving (reads AND writes)
    # continues; the next fold succeeds
    assert result_digest(vg.cypher(
        "MATCH (p:Person) RETURN p.name AS n")) == digest
    vg.cypher("CREATE (:Person {name:'Erin', age:29})")
    assert vg.compact() is True
    assert vg.delta_rows() == 0
    assert "Erin" in _names(vg)


def test_background_compactor_in_server(make_session):
    from caps_tpu.obs import clock
    from caps_tpu.serve import QueryServer, ServerConfig
    s = make_session("tpu")
    vg = _vg(s)
    server = QueryServer(s, graph=vg, config=ServerConfig(
        workers=2, compaction_threshold_rows=2,
        compaction_interval_s=0.005))
    try:
        for i in range(4):
            server.submit(f"CREATE (:Item {{k:{i}}})").result(timeout=30)
        deadline = clock.now() + 10.0
        while clock.now() < deadline:
            if s.metrics_snapshot().get("compaction.runs", 0) >= 1:
                break
            clock.sleep(0.01)
        stats = server.stats()
        assert s.metrics_snapshot()["compaction.runs"] >= 1
        assert stats["compaction"] is not None
        assert stats["compaction"]["state"] in ("idle", "running")
        rows = server.submit("MATCH (i:Item) RETURN count(*) AS c"
                             ).rows(timeout=30)
        assert rows == [{"c": 4}]
    finally:
        server.shutdown()


# -- scoped plan-cache eviction ----------------------------------------------

def test_unrelated_graph_plans_survive_a_write(make_session):
    """Satellite regression: a write to one graph evicts only THAT
    graph's superseded snapshot plans — an unrelated graph's cached
    plans keep hitting."""
    s = make_session("local")
    vg1 = _vg(s)
    vg2 = _vg(s, "CREATE (:Widget {sku:1}), (:Widget {sku:2})")
    other = create_graph(s, "CREATE (:Gadget {sn:7})")
    q2 = "MATCH (w:Widget) RETURN count(*) AS c"
    q3 = "MATCH (g:Gadget) RETURN count(*) AS c"
    assert _rows(vg2.cypher(q2)) == [{"c": 2}]
    assert _rows(other.cypher(q3)) == [{"c": 1}]
    assert vg2.cypher(q2).metrics["plan_cache"] == "hit"
    assert other.cypher(q3).metrics["plan_cache"] == "hit"
    hits_before = s.plan_cache.stats()["hits"]
    # write to vg1: neither vg2's snapshot plans nor the plain graph's
    # plans are touched
    vg1.cypher("CREATE (:Person {name:'New'})")
    assert vg2.cypher(q2).metrics["plan_cache"] == "hit"
    assert other.cypher(q3).metrics["plan_cache"] == "hit"
    assert s.plan_cache.stats()["hits"] == hits_before + 2
    # while vg1's own superseded snapshot plans were evicted (scoped)
    res = vg1.cypher("MATCH (p:Person) RETURN count(*) AS c")
    assert res.metrics["plan_cache"] == "miss"


def test_snapshot_reads_use_plan_cache_and_fuse(make_session):
    """Snapshots are real plan-cache/fused citizens: repeated reads of
    the SAME snapshot hit the cache; a commit moves readers to the new
    snapshot (a miss, by design), and old plans are evicted."""
    s = make_session("tpu")
    vg = _vg(s)
    q = "MATCH (p:Person) WHERE p.age > $min RETURN p.name AS n ORDER BY n"
    assert vg.cypher(q, {"min": 20}).metrics["plan_cache"] == "miss"
    assert vg.cypher(q, {"min": 28}).metrics["plan_cache"] == "hit"
    entries = s.plan_cache.stats()["entries"]
    assert entries >= 1
    vg.cypher("CREATE (:Person {name:'New', age:50})")
    res = vg.cypher(q, {"min": 20})
    assert res.metrics["plan_cache"] == "miss"
    assert [r["n"] for r in _rows(res)] == ["Alice", "Bob", "Carol", "New"]


# -- LDBC interactive update subset (IU-style inserts through the server) ----

def test_iu_insert_subset_through_server(make_session):
    """IU-1-style (insert person), IU-8-style (add friendship), and an
    IU-6-ish post insert, run through the server as parameterized write
    statements, with digest parity between 'apply then read' and 'read
    the post-compaction snapshot'."""
    from caps_tpu.serve import QueryServer, ServerConfig
    s = make_session("tpu")
    vg = versioned(s, create_graph(
        s, "CREATE (:Person {id:1, firstName:'Ada'}), "
           "(:Person {id:2, firstName:'Bo'})"))
    server = QueryServer(s, graph=vg, config=ServerConfig(workers=2))
    try:
        # IU-1: insert person
        server.run("CREATE (:Person {id:$id, firstName:$fn, "
                   "browserUsed:$b})",
                   {"id": 3, "fn": "Cy", "b": "Firefox"})
        # IU-8: add friendship between two existing persons
        server.run("MATCH (a:Person {id:$a}), (b:Person {id:$b}) "
                   "CREATE (a)-[:KNOWS {creationDate:$d}]->(b)",
                   {"a": 1, "b": 3, "d": 20260804})
        # IU-6-ish: insert a post by an existing person
        server.run("MATCH (p:Person {id:$p}) "
                   "CREATE (p)<-[:HAS_CREATOR]-"
                   "(:Post {id:$post, content:$c})",
                   {"p": 3, "post": 100, "c": "hello"})
        reads = [
            ("MATCH (p:Person) RETURN p.id AS id, p.firstName AS fn", {}),
            ("MATCH (a:Person)-[k:KNOWS]->(b:Person) "
             "RETURN a.id AS a, b.id AS b, k.creationDate AS d", {}),
            ("MATCH (m:Post)-[:HAS_CREATOR]->(p:Person) "
             "RETURN m.id AS m, m.content AS c, p.id AS p", {}),
        ]
        applied = [result_digest(server.run(q, params))
                   for q, params in reads]
        assert vg.compact() is True
        compacted = [result_digest(server.run(q, params))
                     for q, params in reads]
        assert applied == compacted
    finally:
        server.shutdown()


# -- the acceptance soak -----------------------------------------------------

def _mixed_soak(make_session, *, writers, readers, writes_each,
                reads_each, compaction_threshold, result_cache=None):
    """8-client mixed read/write soak under ~20%+ write aborts.

    Asserts the ISSUE acceptance: availability 1.0 (every request
    resolves), ZERO torn reads (every reader's rows equal the serial
    state at its admission-time snapshot version), and at least one
    background compaction completing under load.  With ``result_cache``
    the same reads go through the snapshot-keyed result cache: a hit
    must never serve a superseded version, and the cache stays inside
    its budget at every read."""
    from caps_tpu.serve import QueryServer, RetryPolicy, ServeError, \
        ServerConfig
    from caps_tpu.testing.faults import abort_write
    s = make_session("tpu")
    vg = versioned(s, create_graph(s, "CREATE (:Seed {k:-1, v:-1})"))
    server = QueryServer(s, graph=vg, config=ServerConfig(
        workers=2, max_queue=4096,
        # 8 attempts: the every-5 injector is PERMANENT and a commit
        # places 3 columns, so adversarial thread phasing can land the
        # same write on the abort boundary several attempts running —
        # the retry budget must outlast the worst phase, not the mean
        retry=RetryPolicy(max_attempts=8, backoff_base_s=0.002,
                          backoff_max_s=0.05),
        compaction_threshold_rows=compaction_threshold,
        compaction_interval_s=0.005, result_cache=result_cache))
    write_log = {}       # version -> (k, v)
    write_log_lock = threading.Lock()
    observations = []    # (snapshot_version, frozenset of (k, v))
    obs_lock = threading.Lock()
    failures = []

    def writer(i):
        for j in range(writes_each):
            k = i * 1000 + j
            try:
                res = server.submit("CREATE (:Item {k:$k, v:$v})",
                                    {"k": k, "v": k * 7}).result(timeout=60)
                with write_log_lock:
                    write_log[res.metrics["snapshot_version"]] = (k, k * 7)
            except Exception as ex:
                failures.append(("write", k, ex))

    def reader(i):
        for _ in range(reads_each):
            try:
                h = server.submit(
                    "MATCH (n:Item) RETURN n.k AS k, n.v AS v")
                rows = h.rows(timeout=60)
                with obs_lock:
                    observations.append(
                        (h.info["snapshot_version"],
                         frozenset((r["k"], r["v"]) for r in rows)))
                if result_cache is not None:
                    assert server.result_cache.bytes \
                        <= result_cache.budget_bytes
            except ServeError as ex:  # pragma: no cover — availability
                failures.append(("read-shed", i, ex))
            except Exception as ex:  # pragma: no cover
                failures.append(("read", i, ex))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(writers)]
    threads += [threading.Thread(target=reader, args=(i,))
                for i in range(readers)]
    try:
        with abort_write(s, after_n_columns=1, n_times=None,
                         every_n=5) as budget:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        server.shutdown()
    # availability 1.0: every one of the 8 clients' requests resolved
    assert not failures, failures[:5]
    assert len(write_log) == writers * writes_each
    assert budget.injected > 0, "the abort injector never fired"
    # zero torn reads: each reader's rows are EXACTLY the serial state
    # at its admission-time snapshot version — the set of writes whose
    # commit version <= the pinned version (compaction versions add no
    # writes, so the same fold applies)
    assert observations
    for version, seen in observations:
        expected = frozenset(kv for v, kv in write_log.items()
                             if v <= version)
        assert seen == expected, (
            f"torn read at snapshot v{version}: "
            f"unexpected={sorted(seen - expected)[:5]} "
            f"missing={sorted(expected - seen)[:5]}")
    # the final state digest matches a serial re-execution of the same
    # committed writes, in commit order, on a fresh engine
    s2 = make_session("tpu")
    vg2 = versioned(s2, create_graph(s2, "CREATE (:Seed {k:-1, v:-1})"))
    for _v, (k, v) in sorted(write_log.items()):
        vg2.cypher("CREATE (:Item {k:$k, v:$v})", {"k": k, "v": v})
    q = "MATCH (n:Item) RETURN n.k AS k, n.v AS v"
    assert result_digest(vg.cypher(q)) == result_digest(vg2.cypher(q))
    # at least one compaction completed UNDER LOAD
    assert s.metrics_snapshot()["compaction.runs"] >= 1
    if result_cache is not None:  # the reads really went through it
        assert s.metrics_snapshot()["rescache.insertions"] >= 1
    assert s.metrics_snapshot()["updates.rolled_back"] >= 1


@pytest.mark.parametrize("cached", [False, True])
def test_soak_mixed_read_write_with_aborts(make_session, cached):
    """Tier-1 soak: 8 clients, 3 writers (~27% writes) under injected
    write aborts; once more with the result cache on."""
    from caps_tpu.relational.result_cache import ResultCacheConfig
    _mixed_soak(make_session, writers=3, readers=5, writes_each=6,
                reads_each=8, compaction_threshold=6,
                result_cache=ResultCacheConfig(budget_bytes=4 << 20)
                if cached else None)


@pytest.mark.slow
def test_soak_mixed_read_write_long(make_session):
    _mixed_soak(make_session, writers=3, readers=5, writes_each=25,
                reads_each=40, compaction_threshold=12)


# -- multi-device snapshot serving ------------------------------------------

def test_snapshot_reads_replicate_across_devices(make_session):
    """Pinned snapshots replicate onto device replicas: the base
    re-ingests once per device, the delta overlay rebuilds per replica,
    and every device returns the same pinned-version rows."""
    from caps_tpu.serve import QueryServer, ServerConfig
    s = make_session("tpu")
    vg = _vg(s)
    server = QueryServer(s, graph=vg, config=ServerConfig(devices=2))
    try:
        server.submit("CREATE (:Person {name:'Dave', age:52})"
                      ).result(timeout=30)
        handles = [server.submit("MATCH (p:Person) RETURN count(*) AS c")
                   for _ in range(10)]
        results = [h.rows(timeout=30)[0]["c"] for h in handles]
        assert set(results) == {4}
        devices = {h.info.get("device") for h in handles}
        assert devices == {0, 1}, \
            f"both devices should serve snapshot reads, got {devices}"
    finally:
        server.shutdown()


# -- review regressions ------------------------------------------------------

def test_recreating_a_deleted_base_id_does_not_resurrect_it(make_session):
    """A create with an explicit id that tombstones a deleted base
    entity must keep the tombstone: dropping it would unmask the base
    row and scans would return BOTH the old and the new entity."""
    s = make_session("tpu")
    vg = _vg(s)
    vg.apply([DeleteNode(0, detach=True)])  # base id 0 = Alice
    vg.apply([CreateNode(labels=("Person",),
                         properties={"name": "Alice2", "age": 1}, id=0)])
    rows = _rows(vg.cypher("MATCH (p:Person) WHERE p.name STARTS WITH "
                           "'Alice' RETURN p.name AS n"))
    assert [r["n"] for r in rows] == ["Alice2"]
    # and the overlay survives compaction identically
    assert vg.compact() is True
    rows = _rows(vg.cypher("MATCH (p:Person) WHERE p.name STARTS WITH "
                           "'Alice' RETURN p.name AS n"))
    assert [r["n"] for r in rows] == ["Alice2"]


def test_explicit_ids_advance_the_allocator(make_session):
    s = make_session("local")
    vg = _vg(s)
    hi = vg._next_id + 5
    vg.apply([CreateNode(labels=("Marker",), id=hi)])
    # auto-allocated creates must skip past the explicit id
    for _ in range(7):
        vg.apply([CreateNode(labels=("Marker",))])
    assert _rows(vg.cypher("MATCH (m:Marker) RETURN count(*) AS c")) == \
        [{"c": 8}]


def test_failed_compaction_never_clobbers_a_concurrent_commit(
        make_session, monkeypatch):
    """The optimistic fold runs outside the commit lock; if a write
    commits while it runs and the fold then FAILS, the fold's pool
    rollback must be skipped — truncating the pool past the committed
    write's interned strings would corrupt published data."""
    import caps_tpu.relational.updates as U
    s = make_session("tpu")
    vg = _vg(s)
    vg.cypher("CREATE (:Person {name:'Delta', age:1})")  # non-empty delta
    orig = U.build_node_tables
    state = {"fired": False}

    def sabotage(factory, nodes):
        if U.in_compaction() and not state["fired"]:
            state["fired"] = True
            # a write lands mid-fold (commit lock is free), interning a
            # fresh string past the fold's pool mark ...
            vg.apply([CreateNode(labels=("Person",),
                                 properties={"name": "RacerUnique",
                                             "age": 2})])
            # ... then the fold fails
            raise RuntimeError("injected fold failure")
        return orig(factory, nodes)

    monkeypatch.setattr(U, "build_node_tables", sabotage)
    with pytest.raises(RuntimeError):
        vg.compact()
    monkeypatch.setattr(U, "build_node_tables", orig)
    assert state["fired"]
    # the concurrently committed write decodes intact
    rows = _rows(vg.cypher(
        "MATCH (p:Person {name:'RacerUnique'}) RETURN p.name AS n"))
    assert rows == [{"n": "RacerUnique"}]
    # and the next compaction succeeds
    assert vg.compact() is True
    rows = _rows(vg.cypher(
        "MATCH (p:Person {name:'RacerUnique'}) RETURN p.name AS n"))
    assert rows == [{"n": "RacerUnique"}]


# -- lock ordering of the scoped-eviction paths ------------------------------

def test_catalog_dep_validation_no_lock_cycle(monkeypatch):
    """Regression (caught live by the runtime lock graph): plan-cache
    lookup validates catalog dep tokens while holding the cache lock,
    and catalog mutations fan out into the cache while holding the
    catalog lock — dep_token must therefore be lock-free, or the two
    paths form a deadlockable cycle.  Strict mode raises mid-run if the
    cycle ever re-forms."""
    monkeypatch.setenv("CAPS_TPU_LOCK_GRAPH", "1")
    from caps_tpu.obs import lockgraph
    from caps_tpu.testing.sessions import make_backend_session
    lockgraph.reset()
    s = make_backend_session("local")  # locks created under strict mode
    g = create_graph(s, "CREATE (:A {x:1})")
    s.catalog.store("dep_cycle_probe", g)
    q = "FROM GRAPH session.dep_cycle_probe MATCH (n:A) RETURN count(*) AS c"
    errors = []

    def mutator():
        try:
            for i in range(60):
                s.catalog.store(f"other{i % 3}", g)
        except Exception as ex:  # pragma: no cover
            errors.append(ex)

    def querier():
        try:
            for _ in range(60):
                assert _rows(s.cypher(q)) == [{"c": 1}]
        except Exception as ex:  # pragma: no cover
            errors.append(ex)

    threads = [threading.Thread(target=mutator),
               threading.Thread(target=querier)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert lockgraph.find_cycle() is None


# -- drop_in (the tombstone-mask primitive) ----------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_table_drop_in(make_session, backend):
    from caps_tpu.okapi.types import CTInteger
    s = make_session(backend)
    t = s.table_factory.from_columns(
        {"id": [0, 1, 2, 3, None, 5], "x": [10, 11, 12, 13, 14, 15]},
        {"id": CTInteger.nullable, "x": CTInteger})
    out = t.drop_in("id", {1, 3, 5})
    pairs = list(zip(out.column_values("id"), out.column_values("x")))
    rows = sorted(pairs, key=lambda p: (p[0] is None, p[0] or 0))
    # matching ids drop; nulls are kept (null never matches)
    assert rows == [(0, 10), (2, 12), (None, 14)]
    assert t.drop_in("id", set()) is t

"""``CALL algo.bfs`` served from the resident CSRs (caps_tpu/ops/bfs.py).

A device graph as ingested is searched where it lives: the ingest-time
CSRs over each relationship table's source and target, the node tables'
id indexes.  Every case compares with a breadth-first search written
here from the edge list alone (a queue over Python dicts), on seeded
Kronecker graphs whose hubs make levels go both ways (top-down from the
frontier, bottom-up from the unseen vertices).
"""
from __future__ import annotations

import collections
import importlib.util
import os
import sys

import numpy as np
import pytest

from caps_tpu.algo import kernels
from caps_tpu.algo.registry import ProcedureArgumentError
from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.datasets.graph500 import rmat_edges
from caps_tpu.okapi.types import CTInteger
from caps_tpu.ops import bfs as BFS
from caps_tpu.relational.entity_tables import (
    NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
)
from caps_tpu.testing import faults

SCALE = 9
QUERY = ("CALL algo.bfs($root, $depth, $direction) YIELD node, dist "
         "RETURN node, dist ORDER BY node")


def _edges(scale=SCALE, seed=11):
    """A seeded Kronecker edge list over dense ids, self-loops and
    repeated pairs kept (the search must not care)."""
    src, tgt = rmat_edges(scale, 8, seed)
    return 1 << scale, src.astype(np.int64), tgt.astype(np.int64)


def _graph(session, n, src, tgt):
    f = session.table_factory
    nodes = NodeTable(NodeMapping.on("_id").with_implied_labels("V"),
                      f.from_columns({"_id": np.arange(n, dtype=np.int64)},
                                     {"_id": CTInteger}))
    rels = RelationshipTable(
        RelationshipMapping.on("E"),
        f.from_columns({"_id": np.arange(n, n + src.size, dtype=np.int64),
                        "_src": src, "_tgt": tgt},
                       {"_id": CTInteger, "_src": CTInteger,
                        "_tgt": CTInteger}))
    return session.create_graph([nodes], [rels])


def _queue_bfs(n, src, tgt, root, direction, max_depth=-1):
    """{node: hop distance} by a queue, from the edge list alone."""
    out = collections.defaultdict(list)
    for a, b in zip(src.tolist(), tgt.tolist()):
        if direction in ("out", "both"):
            out[a].append(b)
        if direction in ("in", "both"):
            out[b].append(a)
    if not 0 <= root < n:
        return {}
    dist = {root: 0}
    queue = collections.deque([root])
    while queue:
        v = queue.popleft()
        if 0 <= max_depth <= dist[v]:
            continue
        for w in out[v]:
            if w not in dist:
                dist[w] = dist[v] + 1
                queue.append(w)
    return dist


@pytest.fixture(scope="module")
def kron():
    n, src, tgt = _edges()
    session = TPUCypherSession()
    return n, src, tgt, session, _graph(session, n, src, tgt)


def _hub(src, tgt):
    return int(np.argmax(np.bincount(np.concatenate([src, tgt]))))


def _run(graph, root, depth=-1, direction="out"):
    rows = graph.cypher(QUERY, {"root": root, "depth": depth,
                                "direction": direction}).records.to_maps()
    return {r["node"]: r["dist"] for r in rows}


@pytest.mark.parametrize("direction", ["out", "in", "both"])
@pytest.mark.parametrize("pick", ["hub", "leaf", "first"])
def test_device_search_matches_a_queue(kron, direction, pick):
    n, src, tgt, _session, graph = kron
    deg = np.bincount(np.concatenate([src, tgt]), minlength=n)
    root = {"hub": _hub(src, tgt),
            "leaf": int(np.flatnonzero(deg == 1)[0]) if (deg == 1).any()
            else int(np.argmin(np.where(deg > 0, deg, 10 ** 9))),
            "first": int(src[0])}[pick]
    want = _queue_bfs(n, src, tgt, root, direction)
    assert _run(graph, root, -1, direction) == want
    assert len(want) > 1 or pick == "leaf"


@pytest.mark.parametrize("depth", [0, 1, 2, 3])
def test_max_depth_stops_the_search(kron, depth):
    n, src, tgt, _session, graph = kron
    root = int(tgt[3])
    want = _queue_bfs(n, src, tgt, root, "both", depth)
    got = _run(graph, root, depth, "both")
    assert got == want
    assert max(got.values()) <= depth


@pytest.mark.parametrize("root", [-1, 1 << SCALE, 1 << 40])
def test_an_absent_root_reaches_nothing(kron, root):
    graph = kron[4]
    assert _run(graph, root, -1, "both") == {}


def test_the_search_is_resident_and_counted(kron):
    n, src, tgt, session, graph = kron
    root = _hub(src, tgt)
    before = session.metrics_snapshot()
    result = graph.cypher("PROFILE " + QUERY,
                          {"root": root, "depth": -1, "direction": "both"})
    (op,) = [m for m in result.metrics["operators"]
             if m["op"] == "AlgoProcedure"]
    assert op["strategy"] == "device-fixpoint"
    assert op["layout"] == "resident-csr"
    rows = result.records.to_maps()
    after = session.metrics_snapshot()
    levels = max(r["dist"] for r in rows) + 1     # the last finds nothing
    assert after["algo.bfs_searches"] - before.get("algo.bfs_searches", 0) \
        == 1
    assert after["algo.bfs_levels"] - before.get("algo.bfs_levels", 0) \
        == levels
    examined = after["algo.bfs_edges_examined"] \
        - before.get("algo.bfs_edges_examined", 0)
    assert 0 < examined <= 2 * src.size
    assert after.get("algo.fallbacks", 0) == before.get("algo.fallbacks", 0)
    # what came back: a few numbers a level and the result's rows, never
    # the edge list (src and tgt alone are 8 bytes an edge each)
    d2h = after["backend.d2h_bytes"] - before.get("backend.d2h_bytes", 0)
    assert d2h < 8 * src.size


def test_levels_go_both_ways(kron, monkeypatch):
    """The hub's frontier reads more rows than the vertices it leaves
    unseen: that level goes bottom-up, the first and the last top-down."""
    n, src, tgt, _session, graph = kron
    ways = []
    orig = BFS.bfs_pass
    monkeypatch.setattr(BFS, "bfs_pass", lambda *a, **k: ways.append(
        "top-down" if a[3] else "bottom-up") or orig(*a, **k))
    # a neighbour of the hub: the hub's rows are the second level's
    root = int(tgt[np.flatnonzero(src == _hub(src, tgt))[0]])
    assert _run(graph, root, -1, "both") \
        == _queue_bfs(n, src, tgt, root, "both")
    assert ways[0] == "top-down" and "bottom-up" in ways


@pytest.mark.parametrize("scale", [SCALE, 13])
def test_a_search_reads_once_a_level(monkeypatch, scale):
    """One counted read for the root and one a level, each bringing the
    level's pass lengths back; the counters add those up.  At scale 13
    the hub's level streams more than one ``CHUNK``, so the pass walks
    several chunks in its device loop."""
    n, src, tgt = _edges(scale)
    session = TPUCypherSession()
    graph = _graph(session, n, src, tgt)
    reads, searches = [], []
    orig = BFS.search

    def spy(*a, read, **k):
        def counted(x):
            out = read(x)
            reads.append([int(v) for v in out])
            return out
        searches.append(orig(*a, read=counted, **k))
        return searches[-1]

    monkeypatch.setattr(BFS, "search", spy)
    root = int(tgt[np.flatnonzero(src == _hub(src, tgt))[0]])
    before = session.metrics_snapshot()
    assert _run(graph, root, -1, "both") \
        == _queue_bfs(n, src, tgt, root, "both")
    after = session.metrics_snapshot()
    (found,) = searches
    assert len(reads) == found.levels + 1
    lengths = [x for level in reads[1:] for x in level[3:]]
    assert any(len(level) == 5 for level in reads[1:])   # a bottom-up one
    assert found.examined == sum(lengths)
    assert found.passes == len(lengths)
    assert found.chunks == sum(-(-x // BFS.CHUNK) for x in lengths)
    if scale > SCALE:
        assert max(lengths) > BFS.CHUNK
    for name, want in (("algo.bfs_passes", found.passes),
                       ("algo.bfs_chunks", found.chunks),
                       ("algo.bfs_edges_examined", found.examined)):
        assert after[name] - before.get(name, 0) == want


def test_the_local_oracle_agrees(kron):
    n, src, tgt, _session, graph = kron
    local = _graph(LocalCypherSession(), n, src, tgt)
    for direction in ("out", "in", "both"):
        params = {"root": int(src[7]), "depth": -1, "direction": direction}
        assert graph.cypher(QUERY, params).records.to_maps() \
            == local.cypher(QUERY, params).records.to_maps()


@pytest.mark.parametrize("direction", ["out", "in", "both"])
def test_numpy_twin_is_a_frontier_search(direction):
    n, src, tgt = _edges(8, seed=5)
    for root in (0, int(src[1]), n - 1):
        for depth in (-1, 1, 2):
            dist, levels, done = kernels.bfs(n, src, tgt, root, depth,
                                             direction)
            want = _queue_bfs(n, src, tgt, root, direction, depth)
            got = {i: int(d) for i, d in enumerate(dist)
                   if d != kernels.UNREACHED}
            assert got == want
            assert levels >= max(want.values())
            if depth < 0:
                assert done


def test_a_bad_direction_is_a_typed_error(kron):
    graph = kron[4]
    with pytest.raises(ProcedureArgumentError) as err:
        graph.cypher("CALL algo.bfs(0, -1, 'sideways') YIELD node "
                     "RETURN node")
    msg = str(err.value)
    assert "direction" in msg and "'both'" in msg and "sideways" in msg
    with pytest.raises(ProcedureArgumentError):
        _run(graph, 0, -1, "up")


def test_after_a_write_the_snapshot_path_agrees():
    from caps_tpu.relational.updates import versioned
    n, src, tgt = _edges(7, seed=3)
    session = TPUCypherSession()
    vg = versioned(session, _graph(session, n, src, tgt))
    session.cypher_on_graph(
        vg, "MATCH (a:V), (b:V) WHERE id(a) = 0 AND id(b) = 1 "
            "CREATE (a)-[:E]->(b)")
    res = session.cypher_on_graph(
        vg, "PROFILE " + QUERY, {"root": 0, "depth": -1,
                                 "direction": "both"})
    (op,) = [m for m in res.metrics["operators"]
             if m["op"] == "AlgoProcedure"]
    assert op["strategy"] == "device-fixpoint"
    assert op["layout"] != "resident-csr"
    want = _queue_bfs(n, np.append(src, 0), np.append(tgt, 1), 0, "both")
    assert want != _queue_bfs(n, src, tgt, 0, "both")
    assert {r["node"]: r["dist"] for r in res.records.to_maps()} == want


def test_a_fault_falls_back_to_the_host_twin(kron):
    n, src, tgt, session, graph = kron
    params = {"root": int(src[2]), "depth": -1, "direction": "both"}
    clean = graph.cypher(QUERY, params).records.to_maps()
    before = session.metrics_snapshot().get("algo.fallbacks", 0)
    with faults.failing_algo(n_times=1) as budget:
        res = graph.cypher("PROFILE " + QUERY, params)
        assert budget.injected == 1
    (op,) = [m for m in res.metrics["operators"]
             if m["op"] == "AlgoProcedure"]
    assert op["strategy"] == "fallback-host"
    assert res.records.to_maps() == clean
    assert session.metrics_snapshot()["algo.fallbacks"] == before + 1


@pytest.fixture(scope="module")
def graphalytics():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_generators_graphalytics_bfs",
        os.path.join(root, "benchmarks", "generators",
                     "graphalytics_bfs.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmark_query_gives_the_level_histogram(graphalytics):
    """The cell's own query text, served, against its generator's numpy
    reference: recorded once, then replayed generically root to root."""
    from caps_tpu.serve import QueryServer
    from caps_tpu.serve.server import ServerConfig
    sizes = {"scale": 9, "edgefactor": 16, "roots": 4}
    data = graphalytics.make_data(sizes, 2 ** 33 + 7)
    session = TPUCypherSession()
    graph = graphalytics.build_graph(session, data)
    text = graphalytics.QUERIES["bfs"]
    binds = graphalytics.bindings(data, {"kind": "roots", "count": 4}, None)
    with QueryServer(session, graph=graph,
                     config=ServerConfig(workers=1)) as server:
        for _round in range(2):
            for params in binds:
                rows = server.submit(text, params).rows(timeout=300)
                assert rows == graphalytics.reference(
                    data, ["bfs"], params)["bfs"]
    snap = session.metrics_snapshot()
    assert snap["fused.recordings"] == 1
    assert snap["fused.generic_replays"] + snap["fused.replays"] == 7
    assert snap["algo.bfs_searches"] == 8
    assert snap.get("algo.fallbacks", 0) == 0

"""Graph-algorithm procedures (``CALL algo.*`` — caps_tpu/algo/*): the
analytics tier over the shared iterative-fixpoint executor.

Correctness contract throughout: the device fixpoint is a physical
choice — it must NEVER change results.  Every behavioural test asserts
parity between the device backend and the local (NumPy-oracle) backend,
including on base+delta snapshots, and under injected device faults the
host fallback must be digest-equal.
"""
from __future__ import annotations

import numpy as np
import pytest

from caps_tpu.algo import registry
from caps_tpu.algo import kernels
from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.frontend.semantic import CypherSemanticError
from caps_tpu.obs.metrics import global_registry
from caps_tpu.relational.session import result_digest
from caps_tpu.testing import faults
from tests.util import make_graph


def _random_graph(session, n=60, e=240, seed=7, self_loops=True,
                  weighted=True):
    rng = np.random.RandomState(seed)
    nodes = {("P",): [{"_id": i, "name": f"n{i % 11}"} for i in range(n)]}
    edges = [(int(rng.randint(n)), int(rng.randint(n)),
              ({"w": float(1 + (i % 5))} if weighted else {}))
             for i in range(e)]
    if not self_loops:
        edges = [(a, b, p) for a, b, p in edges if a != b]
    return make_graph(session, nodes, {"K": edges})


def _two_islands(session):
    """Two disconnected components (0-1-2 and 3-4), plus an isolate."""
    nodes = {("P",): [{"_id": i} for i in range(6)]}
    edges = [(0, 1, {}), (1, 2, {}), (3, 4, {})]
    return make_graph(session, nodes, {"K": edges})


PROCEDURE_QUERIES = [
    "CALL algo.degree() YIELD node, degree "
    "RETURN node, degree ORDER BY node",
    "CALL algo.pagerank() YIELD node, score "
    "RETURN node, score ORDER BY node",
    "CALL algo.wcc() YIELD node, component "
    "RETURN node, component ORDER BY node",
    "CALL algo.bfs(0) YIELD node, dist RETURN node, dist ORDER BY node",
    "CALL algo.sssp(0, 'w') YIELD node, dist "
    "RETURN node, dist ORDER BY node",
]


def _algo_op(result):
    return [m for m in result.metrics["operators"]
            if m["op"] == "AlgoProcedure"]


# -- cross-backend parity (the oracle contract) ----------------------------

@pytest.mark.parametrize("query", PROCEDURE_QUERIES)
def test_device_matches_local_oracle(query):
    local = _random_graph(LocalCypherSession())
    device = _random_graph(TPUCypherSession())
    assert device.cypher(query).records.to_maps() == \
        local.cypher(query).records.to_maps()


@pytest.mark.parametrize("query", PROCEDURE_QUERIES)
def test_empty_graph(query):
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = make_graph(session, {("P",): []}, {"K": []})
        assert g.cypher(query).records.to_maps() == []


def test_self_loops_and_parallel_edges_parity():
    nodes = {("P",): [{"_id": i} for i in range(4)]}
    edges = [(0, 0, {}), (0, 1, {}), (0, 1, {}), (2, 3, {}), (3, 2, {})]
    local = make_graph(LocalCypherSession(), nodes, {"K": edges})
    device = make_graph(TPUCypherSession(), nodes, {"K": edges})
    for q in PROCEDURE_QUERIES:
        assert device.cypher(q).records.to_maps() == \
            local.cypher(q).records.to_maps(), q
    # self-loop + parallel edges count per edge occurrence
    deg = {r["node"]: r["degree"]
           for r in local.cypher(PROCEDURE_QUERIES[0]).records.to_maps()}
    # node 0: self-loop (1 out + 1 in) + 2 parallel out-edges = 4
    assert deg[0] == 4 and deg[1] == 2 and deg[2] == 2


def test_dense_tile_layout_parity():
    """A graph dense enough to approach the full capacity tile routes to
    the matrix-product (dense-tile) program family — a physical layout
    choice that must never change results vs the NumPy oracle; BFS
    searches CSRs at any density."""
    def dense(session, n=64, m=8192, seed=5):
        rng = np.random.RandomState(seed)
        nodes = {("P",): [{"_id": i} for i in range(n)]}
        edges = [(int(s), int(t), {"w": float(w)}) for s, t, w in
                 zip(rng.randint(0, n, m), rng.randint(0, n, m),
                     np.round(rng.rand(m) * 9 + 1, 3))]
        return make_graph(session, nodes, {"K": edges})

    from caps_tpu.relational.updates import versioned
    local = dense(LocalCypherSession())
    s = TPUCypherSession()
    device = dense(s)
    # algo.bfs searches CSRs, never a tile (tests/test_algo_bfs.py): the
    # ingested graph's own, or through a versioned handle the snapshot's
    snapshot = versioned(s, device)
    for q in PROCEDURE_QUERIES:
        bfs = q.startswith("CALL algo.bfs")
        profiled = (s.cypher_on_graph(snapshot, "PROFILE " + q) if bfs
                    else device.cypher("PROFILE " + q))
        (op,) = _algo_op(profiled)
        assert op["strategy"] == "device-fixpoint", q
        assert op["layout"] == ("snapshot-csr" if bfs else "dense-tile"), q
        assert profiled.records.to_maps() == \
            local.cypher(q).records.to_maps(), q
        if bfs:
            (op,) = _algo_op(device.cypher("PROFILE " + q))
            assert op["layout"] == "resident-csr"
    # the ordinary sparse graph keeps the edge-list layout
    sparse = _random_graph(TPUCypherSession())
    (op,) = _algo_op(sparse.cypher("PROFILE " + PROCEDURE_QUERIES[1]))
    assert op["layout"] == "edge-list"


def test_sparse_id_space_parity():
    """Node ids far apart (span >> n) take the binary-search compaction
    path instead of the O(1) lookup table — same results either way."""
    ids = [0, 70_000, 140_000, 999_999]
    nodes = {("P",): [{"_id": i} for i in ids]}
    edges = [(ids[0], ids[1], {"w": 2.0}), (ids[1], ids[2], {"w": 3.0}),
             (ids[2], ids[3], {"w": 1.0}), (ids[3], ids[0], {"w": 4.0})]
    local = make_graph(LocalCypherSession(), nodes, {"K": edges})
    device = make_graph(TPUCypherSession(), nodes, {"K": edges})
    for q in PROCEDURE_QUERIES:
        assert device.cypher(q).records.to_maps() == \
            local.cypher(q).records.to_maps(), q
    bfs = ("CALL algo.bfs(0) YIELD node, dist "
           "RETURN node, dist ORDER BY node")
    assert local.cypher(bfs).records.to_maps() == [
        {"node": 0, "dist": 0}, {"node": 70_000, "dist": 1},
        {"node": 140_000, "dist": 2}, {"node": 999_999, "dist": 3}]


def test_disconnected_components():
    local = _two_islands(LocalCypherSession())
    device = _two_islands(TPUCypherSession())
    q = ("CALL algo.wcc() YIELD node, component "
         "RETURN node, component ORDER BY node")
    rows = local.cypher(q).records.to_maps()
    assert device.cypher(q).records.to_maps() == rows
    comp = {r["node"]: r["component"] for r in rows}
    # components are named by their smallest member id
    assert comp[0] == comp[1] == comp[2] == 0
    assert comp[3] == comp[4] == 3
    assert comp[5] == 5  # the isolate is its own component
    # BFS yields REACHED nodes only: the far island never appears
    bq = "CALL algo.bfs(0) YIELD node, dist RETURN node, dist ORDER BY node"
    brows = local.cypher(bq).records.to_maps()
    assert device.cypher(bq).records.to_maps() == brows
    assert [r["node"] for r in brows] == [0, 1, 2]
    assert [r["dist"] for r in brows] == [0, 1, 2]


def test_sssp_weighted_vs_unit():
    nodes = {("P",): [{"_id": i} for i in range(4)]}
    # direct hop 0->3 costs 10; the 3-hop detour costs 3
    edges = [(0, 3, {"w": 10.0}), (0, 1, {"w": 1.0}),
             (1, 2, {"w": 1.0}), (2, 3, {"w": 1.0})]
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = make_graph(session, nodes, {"K": edges})
        q = ("CALL algo.sssp(0, 'w') YIELD node, dist "
             "RETURN node, dist ORDER BY node")
        assert [r["dist"] for r in g.cypher(q).records.to_maps()] == \
            [0.0, 1.0, 2.0, 3.0]
        # unknown weight property degrades to unit weights (= hop count)
        q_unit = ("CALL algo.sssp(0, 'nope') YIELD node, dist "
                  "RETURN node, dist ORDER BY node")
        assert [r["dist"] for r in g.cypher(q_unit).records.to_maps()] == \
            [0.0, 1.0, 2.0, 1.0]  # the direct hop 0->3 wins unweighted


def test_bfs_absent_source_yields_nothing():
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = _two_islands(session)
        q = "CALL algo.bfs(999) YIELD node, dist RETURN node, dist"
        assert g.cypher(q).records.to_maps() == []


def test_degree_directions():
    nodes = {("P",): [{"_id": i} for i in range(3)]}
    edges = [(0, 1, {}), (0, 2, {}), (1, 2, {})]
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = make_graph(session, nodes, {"K": edges})
        def deg(direction):
            q = (f"CALL algo.degree('{direction}') YIELD node, degree "
                 "RETURN node, degree ORDER BY node")
            return [r["degree"] for r in g.cypher(q).records.to_maps()]
        assert deg("out") == [2, 1, 0]
        assert deg("in") == [0, 1, 2]
        assert deg("both") == [2, 2, 2]


def test_pagerank_scores_sum_to_one():
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = _random_graph(session)
        rows = g.cypher(PROCEDURE_QUERIES[1]).records.to_maps()
        assert abs(sum(r["score"] for r in rows) - 1.0) < 1e-6


# -- delta overlay: live writes visible through the snapshot seam ----------

def test_delta_overlay_parity_after_live_writes():
    from caps_tpu.relational.updates import versioned
    nodes = {("P",): [{"_id": i, "name": f"n{i}"} for i in range(5)]}
    edges = [(0, 1, {}), (1, 2, {})]
    q = ("CALL algo.wcc() YIELD node, component "
         "RETURN node, component ORDER BY node")
    results = []
    for make_session in (LocalCypherSession, TPUCypherSession):
        s = make_session()
        vg = versioned(s, make_graph(s, nodes, {"K": edges}))
        before = s.cypher_on_graph(vg, q).records.to_maps()
        comp = {r["node"]: r["component"] for r in before}
        assert comp[3] == 3 and comp[4] == 4  # islands before the write
        # bridge the islands live: the overlay must be visible
        s.cypher_on_graph(
            vg, "MATCH (a:P), (b:P) WHERE a.name = 'n2' AND b.name = 'n4' "
                "CREATE (a)-[:K]->(b)")
        s.cypher_on_graph(
            vg, "MATCH (a:P), (b:P) WHERE a.name = 'n4' AND b.name = 'n3' "
                "CREATE (a)-[:K]->(b)")
        after = s.cypher_on_graph(vg, q)
        assert all(r["component"] == 0 for r in after.records.to_maps())
        results.append(result_digest(after))
    assert results[0] == results[1]  # device == oracle on base+delta


# -- convergence & iteration bounds ----------------------------------------

def test_pagerank_converges_within_bound():
    g = _random_graph(TPUCypherSession())
    r = g.cypher("PROFILE CALL algo.pagerank() YIELD node, score "
                 "RETURN node, score")
    (op,) = _algo_op(r)
    assert op["converged"] is True
    assert 0 < op["iterations"] <= 20


def test_pagerank_max_iteration_cutoff():
    g = _random_graph(TPUCypherSession())
    r = g.cypher("PROFILE CALL algo.pagerank(0.85, 2, 0.0) "
                 "YIELD node, score RETURN node, score")
    (op,) = _algo_op(r)
    assert op["iterations"] == 2 and op["converged"] is False
    # the truncated run still matches the oracle exactly
    lg = _random_graph(LocalCypherSession())
    q = ("CALL algo.pagerank(0.85, 2, 0.0) YIELD node, score "
         "RETURN node, score ORDER BY node")
    assert g.cypher(q).records.to_maps() == lg.cypher(q).records.to_maps()


# -- composition: YIELD into the relational pipeline -----------------------

def test_yield_composes_with_return_pipeline():
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = _two_islands(session)
        q = ("CALL algo.wcc() YIELD node, component "
             "WHERE component = 0 "
             "RETURN component, count(*) AS size")
        assert g.cypher(q).records.to_maps() == \
            [{"component": 0, "size": 3}]


def test_call_after_match_joins_on_yield():
    for session in (LocalCypherSession(), TPUCypherSession()):
        g = _random_graph(session, n=12, e=30)
        q = ("MATCH (p:P) CALL algo.degree() YIELD node, degree "
             "WHERE id(p) = node AND degree > 0 "
             "RETURN p.name AS name, degree ORDER BY node")
        rows = g.cypher(q).records.to_maps()
        assert rows and all(r["degree"] > 0 for r in rows)
    # cross-backend digest parity on the composed pipeline
    lg = _random_graph(LocalCypherSession(), n=12, e=30)
    dg = _random_graph(TPUCypherSession(), n=12, e=30)
    assert dg.cypher(q).records.to_maps() == lg.cypher(q).records.to_maps()


def test_yield_aliases_avoid_rebinding():
    g = _two_islands(LocalCypherSession())
    q = ("MATCH (node:P) CALL algo.degree() "
         "YIELD node AS nid, degree AS d "
         "WHERE id(node) = nid RETURN id(node) AS i, d ORDER BY i")
    rows = g.cypher(q).records.to_maps()
    assert [r["i"] for r in rows] == list(range(6))


# -- typed semantic errors (parser/semantic hardening satellite) -----------

def test_unknown_procedure_names_registered_signatures():
    g = _two_islands(LocalCypherSession())
    with pytest.raises(registry.UnknownProcedureError) as ei:
        g.cypher("CALL algo.nope() YIELD node RETURN node")
    msg = str(ei.value)
    assert "algo.nope" in msg and "algo.pagerank" in msg
    assert "damping" in msg  # renders full signatures, not just names


def test_arity_mismatch_is_typed_and_names_signature():
    g = _two_islands(LocalCypherSession())
    with pytest.raises(registry.ProcedureArgumentError) as ei:
        g.cypher("CALL algo.degree('out', 1, 2) YIELD node RETURN node")
    assert "algo.degree" in str(ei.value)
    assert "0..1" in str(ei.value)
    with pytest.raises(registry.ProcedureArgumentError):
        g.cypher("CALL algo.bfs() YIELD node, dist RETURN node")  # missing


def test_argument_type_mismatch_is_typed():
    g = _two_islands(LocalCypherSession())
    with pytest.raises(registry.ProcedureArgumentError) as ei:
        g.cypher("CALL algo.bfs('zero') YIELD node, dist RETURN node")
    msg = str(ei.value)
    assert "algo.bfs" in msg and "INTEGER" in msg and "source" in msg


def test_bad_yield_column_and_rebind_are_typed():
    g = _two_islands(LocalCypherSession())
    with pytest.raises(registry.ProcedureYieldError):
        g.cypher("CALL algo.degree() YIELD node, rank RETURN rank")
    with pytest.raises(CypherSemanticError, match="alias them with AS"):
        g.cypher("MATCH (node:P) CALL algo.degree() YIELD node, degree "
                 "RETURN degree")
    # errors are also CypherSemanticError: existing catchers keep working
    assert issubclass(registry.UnknownProcedureError, CypherSemanticError)


# -- compile ledger: once per first-seen shape, then zero ------------------

def test_compile_ledger_once_then_zero():
    s = TPUCypherSession()
    g = _random_graph(s)
    q = PROCEDURE_QUERIES[1]  # pagerank: priced onto the device path
    r1 = g.cypher(q)
    charges = [c for c in r1.metrics.get("compile_charges", ())
               if c["kind"] == "algo"]
    assert charges and charges[0]["seconds"] > 0.0
    (op,) = [m for m in r1.metrics["operators"]
             if m["op"] == "AlgoProcedure"]
    assert op["strategy"] == "device-fixpoint"
    r2 = g.cypher(q)
    assert r2.metrics["compile_s_charged"] == 0.0
    # a second graph landing in the same shape buckets reuses the program
    g2 = _random_graph(s, seed=11)
    r3 = g2.cypher(q)
    assert [c for c in r3.metrics.get("compile_charges", ())
            if c["kind"] == "algo"] == []


def test_cost_model_note_and_explain_render():
    g = _random_graph(TPUCypherSession())
    r = g.cypher("EXPLAIN " + PROCEDURE_QUERIES[1])
    assert "AlgoProcedure(algo.pagerank() YIELD node, score)" \
        in r.plans["relational"]
    assert "algo_strategy: procedure=algo.pagerank, " \
        "chosen=device-fixpoint" in r.plans["cost"]
    # tiny graphs price out: the pushdown must NOT win under the launch
    # overhead floor
    tiny = _two_islands(TPUCypherSession())
    rt = tiny.cypher("EXPLAIN " + PROCEDURE_QUERIES[1])
    assert "chosen=host" in rt.plans["cost"]


# -- fault injection: host fallback parity, then heal ----------------------

def test_injected_fault_falls_back_to_host_with_parity():
    s = TPUCypherSession()
    g = _random_graph(s)
    q = PROCEDURE_QUERIES[1]
    clean_rows = g.cypher(q).records.to_maps()
    fb0 = s.metrics_registry.snapshot().get("algo.fallbacks", 0)
    inj0 = global_registry().snapshot().get("faults.injected.algo", 0)
    with faults.failing_algo(n_times=1) as budget:
        faulted = g.cypher("PROFILE " + q)
        assert budget.injected == 1
    (op,) = _algo_op(faulted)
    assert op["strategy"] == "fallback-host"
    assert faulted.records.to_maps() == clean_rows  # digest-equal
    snap = s.metrics_registry.snapshot()
    assert snap["algo.fallbacks"] == fb0 + 1
    assert global_registry().snapshot()["faults.injected.algo"] == inj0 + 1
    # healed: the next execution takes the device path again
    healed = g.cypher("PROFILE " + q)
    (hop,) = _algo_op(healed)
    assert hop["strategy"] == "device-fixpoint"
    assert healed.records.to_maps() == clean_rows
    assert s.metrics_registry.snapshot()["algo.fallbacks"] == fb0 + 1


def test_fault_marker_is_stamped():
    class Boom(RuntimeError):
        pass
    with faults.failing_algo(exc=Boom, n_times=1):
        s = TPUCypherSession()
        g = _random_graph(s)
        rows = g.cypher(PROCEDURE_QUERIES[1]).records.to_maps()
    lg = _random_graph(LocalCypherSession())
    assert rows == lg.cypher(PROCEDURE_QUERIES[1]).records.to_maps()


# -- serve tier: warmed families & snapshot-keyed result cache -------------

def test_server_warmed_algo_family_charges_zero():
    from caps_tpu.relational.result_cache import ResultCacheConfig
    from caps_tpu.serve.server import QueryServer, ServerConfig
    s = TPUCypherSession()
    g = _random_graph(s)
    q = PROCEDURE_QUERIES[1]
    cfg = ServerConfig(workers=1,
                       result_cache=ResultCacheConfig(enabled=True))
    with QueryServer(s, graph=g, config=cfg) as server:
        h1 = server.submit(q)
        rows1 = h1.rows(timeout=60)
        assert h1.info["ledger"]["compile_s"] > 0.0
        h2 = server.submit(q)
        assert h2.rows(timeout=60) == rows1
        assert h2.info["ledger"]["compile_s"] == 0.0
        # the algo family is warm: nothing cold remains
        rep = server.warmup_report()
        assert rep["cold_families"] == []
        assert rep["compiled_hot_families"] == rep["hot_families"] == 1
        # the repeat was a snapshot-keyed cache hit (flight recorder)
        dump = server.dump_flight_recorder()
        assert dump["records"][-1]["outcome"] == "cache_hit"
        assert h2.info.get("cache") is not None


# -- host kernels as their own oracle (unit level) -------------------------

def test_host_kernels_unit_oracle():
    src = np.array([0, 1, 2, 0], dtype=np.int64)
    tgt = np.array([1, 2, 0, 2], dtype=np.int64)
    w = np.ones(4)
    deg, it, done = kernels.degree(4, src, tgt, "both")
    assert deg.tolist() == [3, 2, 3, 0] and done
    labels, _, done = kernels.wcc(4, src, tgt, 100)
    assert labels.tolist() == [0, 0, 0, 3] and done
    dist, _, done = kernels.bfs(4, src, tgt, 0, -1)
    assert dist[:3].tolist() == [0, 1, 1] and done
    assert dist[3] == kernels.UNREACHED
    r, it, done = kernels.pagerank(4, src, tgt, 0.85, 50, 1e-9)
    assert done and abs(r.sum() - 1.0) < 1e-6
    # quantized to the published decimal contract
    assert np.array_equal(r, np.round(r, kernels.SCORE_DECIMALS))

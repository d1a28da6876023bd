"""Worst-case-optimal multiway joins for cyclic patterns (ops/wcoj.py +
relational/wcoj.py, ROADMAP item 4): kernel-level sorted-adjacency
intersection, MultiwayJoinOp enumeration parity against the local oracle
AND the forced binary cascade, snapshot delta-overlay parity, the
degraded fallback ladder under injected WCOJ faults, cost-model
selection rendered in EXPLAIN, and fused-replay compile accounting.

Correctness contract throughout: the WCOJ path is a physical-plan
choice — it must NEVER change results.  Every behavioural test asserts
digest parity against a WCOJ-blind execution of the same query.
"""
from __future__ import annotations

import numpy as np
import pytest

from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.okapi.config import EngineConfig
from caps_tpu.relational.session import result_digest
from caps_tpu.testing import faults
from tests.util import make_graph


def _random_graph(session, n=40, e=200, seed=7, self_loops=True,
                  parallel=True, rel2=True):
    rng = np.random.RandomState(seed)
    nodes = {("P",): [{"_id": i, "name": f"n{i % 11}"} for i in range(n)]}
    edges = [(int(rng.randint(n)), int(rng.randint(n)), {})
             for _ in range(e)]
    if not self_loops:
        edges = [(a, b, p) for a, b, p in edges if a != b]
    if parallel:
        edges += edges[:12]
    rels = {"K": edges}
    if rel2:
        rels["L"] = edges[::3]
    return make_graph(session, nodes, rels)


def _ops(result):
    return [m["op"] for m in result.metrics["operators"]]


def _wcoj_strategy(result):
    return [m.get("strategy") for m in result.metrics["operators"]
            if m["op"] == "MultiwayJoin"]


TRIANGLE_ENUM = ("MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]->(c) "
                 "RETURN id(a) AS x, id(b) AS y, id(c) AS z")

CYCLIC_QUERIES = [
    TRIANGLE_ENUM,
    # closing edge written in the reverse orientation
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (c)-[r3:K]->(a) "
    "RETURN id(a) AS x, id(b) AS y, id(c) AS z",
    # closing edge as an incoming mention on a
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)<-[r3:K]-(c) "
    "RETURN id(a) AS x, id(b) AS y, id(c) AS z",
    # mixed rel types + mixed chain directions
    "MATCH (a:P)-[r1:K]->(b)<-[r2:L]-(c), (a)-[r3:K]->(c) "
    "RETURN id(b) AS x, id(c) AS y",
    # diamond: two 2-hop paths meeting (one closing edge)
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(d), (a)-[r3:K]->(c)-[r4:K]->(d) "
    "RETURN id(a) AS w, id(b) AS x, id(c) AS y, id(d) AS z",
    # 4-cycle
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c)-[r3:K]->(d), (d)-[r4:K]->(a) "
    "RETURN id(a) AS w, id(b) AS x, id(c) AS y, id(d) AS z",
    # predicates on multiple pattern vars
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]->(c) "
    "WHERE a.name = 'n3' AND c.name = 'n5' RETURN id(b) AS x, id(c) AS y",
    # full entity materialization through the gather path
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:L]->(c) RETURN a, r3, c",
    # cyclic count WITHOUT the count-pushdown triangle shape (diamond):
    # the aggregate rides the MultiwayJoin output
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(d), (a)-[r3:K]->(c)-[r4:K]->(d) "
    "RETURN count(*) AS c",
    # the same over the 4-cycle
    "MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c)-[r3:K]->(d), (d)-[r4:K]->(a) "
    "RETURN count(*) AS c",
]


# -- kernel layer ------------------------------------------------------------


def _np_sorted(frm, to, ok, n):
    import jax.numpy as jnp
    from caps_tpu.backends.tpu import kernels as K
    from caps_tpu.ops import wcoj as W
    keys = W.edge_keys(jnp.asarray(frm), jnp.asarray(to),
                       jnp.asarray(ok), jnp.int64(n))
    perm = K.sort_perm([keys], keys.shape[0])
    return keys[perm], perm


def test_probe_adj_counts_and_order():
    """Adjacency counts/offsets vs a numpy oracle, including duplicate
    edges, skew (one hub), and masked rows; neighbours within a segment
    come out sorted — the leapfrog ordering guarantee."""
    n = 8
    frm = np.array([0, 0, 0, 5, 5, 1, 2, 0], np.int64)
    to = np.array([3, 1, 3, 7, 0, 6, 2, 4], np.int64)
    ok = np.array([1, 1, 1, 1, 1, 1, 1, 0], bool)  # last edge dead
    ks, perm = _np_sorted(frm, to, ok, n)
    import jax.numpy as jnp
    from caps_tpu.ops import wcoj as W
    u = jnp.asarray(np.arange(n, dtype=np.int64))
    counts, lo = W.probe_adj(ks, u, jnp.ones(n, bool), jnp.int64(n))
    want = [np.sum((frm == i) & ok) for i in range(n)]
    assert list(np.asarray(counts)) == want
    # neighbours of 0 in sorted order: 1, 3, 3 (duplicate edge kept)
    seg = np.asarray(ks)[int(lo[0]):int(lo[0]) + int(counts[0])] % n
    assert list(seg) == [1, 3, 3]


def test_probe_pair_multiplicity_and_empty():
    import jax.numpy as jnp
    from caps_tpu.ops import wcoj as W
    n = 4
    frm = np.array([1, 1, 1, 2], np.int64)
    to = np.array([2, 2, 3, 0], np.int64)
    ks, _ = _np_sorted(frm, to, np.ones(4, bool), n)
    u = jnp.asarray(np.array([1, 1, 2, 3], np.int64))
    v = jnp.asarray(np.array([2, 3, 0, 3], np.int64))
    counts, _lo = W.probe_pair(ks, u, v, jnp.ones(4, bool), jnp.int64(n))
    assert list(np.asarray(counts)) == [2, 1, 1, 0]
    # fully-masked edge table: every probe misses
    ks0, _ = _np_sorted(frm, to, np.zeros(4, bool), n)
    c0, _ = W.probe_pair(ks0, u, v, jnp.ones(4, bool), jnp.int64(n))
    assert list(np.asarray(c0)) == [0, 0, 0, 0]


@pytest.mark.parametrize("out_cap", [256, 512, 300],
                         ids=["tile256", "tile512", "odd-cap"])
def test_extend_enumerates_each_edge(out_cap):
    """The extend step yields one output slot per (frontier row,
    incident edge) — duplicates included — with exact prefix validity
    at tileable AND non-tileable (jnp-twin) capacities."""
    import jax.numpy as jnp
    from caps_tpu.ops import wcoj as W
    n = 6
    frm = np.array([0, 0, 2, 2, 2, 4], np.int64)
    to = np.array([1, 1, 3, 5, 3, 0], np.int64)
    ks, perm = _np_sorted(frm, to, np.ones(6, bool), n)
    u = jnp.asarray(np.array([0, 2, 3], np.int64))
    valid = jnp.asarray(np.array([1, 1, 1], bool))
    l_idx, cand, erow, ok = W.extend(ks, perm, u, valid, n, out_cap)
    got = sorted((int(l), int(c)) for l, c, o in
                 zip(np.asarray(l_idx), np.asarray(cand), np.asarray(ok))
                 if o)
    assert got == [(0, 1), (0, 1), (1, 3), (1, 3), (1, 5)]
    # edge rows are genuine scan rows of the probed edges
    rows = sorted(int(r) for r, o in zip(np.asarray(erow), np.asarray(ok))
                  if o)
    assert rows == [0, 1, 2, 3, 4]
    assert int(np.asarray(ok).sum()) == 5  # exact live prefix
    assert list(np.asarray(ok)[:5]) == [True] * 5


def test_close_expands_parallel_edges():
    import jax.numpy as jnp
    from caps_tpu.ops import wcoj as W
    n = 4
    frm = np.array([1, 1, 3], np.int64)
    to = np.array([2, 2, 0], np.int64)
    ks, perm = _np_sorted(frm, to, np.ones(3, bool), n)
    u = jnp.asarray(np.array([1, 3, 0], np.int64))
    v = jnp.asarray(np.array([2, 0, 1], np.int64))
    l_idx, erow, ok = W.close(ks, perm, u, v,
                              jnp.ones(3, bool), n, 256)
    got = [(int(l), int(r)) for l, r, o in
           zip(np.asarray(l_idx), np.asarray(erow), np.asarray(ok)) if o]
    # row 0 closes twice (parallel edges 0 and 1), row 1 once, row 2 never
    assert sorted(got) == [(0, 0), (0, 1), (1, 2)]


# -- enumeration parity ------------------------------------------------------


@pytest.mark.parametrize("query", CYCLIC_QUERIES)
def test_enumeration_matches_oracle_and_cascade(query):
    """Digest-exact three ways: WCOJ == local oracle == forced cascade
    (use_wcoj=False), on a graph with self-loops, parallel edges, and a
    second relationship type."""
    oracle = _random_graph(LocalCypherSession())
    want = result_digest(oracle.cypher(query))
    s = TPUCypherSession()
    g = _random_graph(s)
    res = g.cypher(query)
    assert "MultiwayJoin" in _ops(res), res.plans["relational"]
    assert _wcoj_strategy(res) == ["wcoj"]
    assert result_digest(res) == want
    s2 = TPUCypherSession(config=EngineConfig(use_wcoj=False))
    g2 = _random_graph(s2)
    res2 = g2.cypher(query)
    assert "MultiwayJoin" not in _ops(res2)
    assert result_digest(res2) == want


def test_param_rebinding_through_plan_cache():
    """Cached-plan re-execution with fresh bindings: the same planned
    MultiwayJoinOp serves every $seed value, parity per binding."""
    q = ("MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]->(c) "
         "WHERE a.name = $seed RETURN id(b) AS x, id(c) AS y")
    oracle = _random_graph(LocalCypherSession())
    s = TPUCypherSession()
    g = _random_graph(s)
    for seed in ("n1", "n4", "n1", "n9"):
        res = g.cypher(q, {"seed": seed})
        assert result_digest(res) == result_digest(
            oracle.cypher(q, {"seed": seed})), seed
    assert s.plan_cache.stats()["hits"] >= 2


def test_uniqueness_pairs_absorbed_same_type():
    """A triangle over ONE rel type carries isomorphism filters between
    all three rels; whether pushed into the segment (absorbed as uniq
    pairs) or left above (plain FilterOps), results match the oracle."""
    q = ("MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]->(c) "
         "RETURN id(r1) AS x, id(r2) AS y, id(r3) AS z")
    oracle = _random_graph(LocalCypherSession())
    s = TPUCypherSession()
    g = _random_graph(s)
    res = g.cypher(q)
    assert "MultiwayJoin" in _ops(res)
    assert result_digest(res) == result_digest(oracle.cypher(q))
    rows = res.records.to_maps()
    assert all(len({r["x"], r["y"], r["z"]}) == 3 for r in rows)


def test_delta_overlay_parity_after_live_writes():
    """The operator reads scans through the snapshot seam: writes that
    create NEW triangles after planning must appear (masked base ∪
    delta), digest-equal to the cascade on the same snapshot."""
    nodes = {("P",): [{"_id": i, "name": f"n{i}"} for i in range(6)]}
    edges = [(0, 1, {}), (1, 2, {})]
    s = TPUCypherSession()
    base = make_graph(s, nodes, {"K": edges})
    from caps_tpu.relational.updates import versioned
    vg = versioned(s, base)
    q = TRIANGLE_ENUM
    res0 = s.cypher_on_graph(vg, q)
    assert res0.records.size() == 0
    # close the triangle live, then add a second (parallel) closing edge
    s.cypher_on_graph(
        vg, "MATCH (a:P), (c:P) WHERE a.name = 'n0' AND c.name = 'n2' "
            "CREATE (a)-[:K]->(c)")
    res1 = s.cypher_on_graph(vg, q)
    assert "MultiwayJoin" in _ops(res1)
    assert _wcoj_strategy(res1) == ["wcoj"]
    assert res1.records.size() == 1
    s.cypher_on_graph(
        vg, "MATCH (a:P), (c:P) WHERE a.name = 'n0' AND c.name = 'n2' "
            "CREATE (a)-[:K]->(c)")
    res2 = s.cypher_on_graph(vg, q)
    assert res2.records.size() == 2  # parallel closing edges: 2 matches
    # cascade parity on the live snapshot
    s2 = TPUCypherSession(config=EngineConfig(use_wcoj=False))
    base2 = make_graph(s2, nodes, {"K": edges})
    vg2 = versioned(s2, base2)
    for w in ("MATCH (a:P), (c:P) WHERE a.name = 'n0' AND c.name = 'n2' "
              "CREATE (a)-[:K]->(c)",) * 2:
        s2.cypher_on_graph(vg2, w)
    assert result_digest(res2) == result_digest(s2.cypher_on_graph(vg2, q))


def test_mesh_sharded_session_parity():
    """On a mesh-sharded (cross-shard) session the WCOJ path defers to
    the okapi distributed joins — the op falls back, results stay
    digest-equal."""
    oracle = _random_graph(LocalCypherSession(), self_loops=False)
    s = TPUCypherSession(config=EngineConfig(mesh_shape=(8,)))
    g = _random_graph(s, self_loops=False)
    res = g.cypher(TRIANGLE_ENUM)
    assert result_digest(res) == result_digest(oracle.cypher(TRIANGLE_ENUM))
    strat = _wcoj_strategy(res)
    assert strat in ([], ["fallback-cascade"])


def test_multi_closing_pattern_substitutes_once():
    """A segment with TWO closing edges yields ONE MultiwayJoinOp —
    never a second one buried in the first one's fallback cascade — and
    EXPLAIN carries exactly one wcoj_strategy decision line."""
    q = ("MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]->(c), "
         "(b)-[r4:L]->(c) RETURN id(a) AS x, id(b) AS y, id(c) AS z")
    oracle = _random_graph(LocalCypherSession())
    s = TPUCypherSession()
    g = _random_graph(s)
    sub0 = s.metrics_registry.snapshot().get("wcoj.substituted", 0)
    exp = g.cypher("EXPLAIN " + q)
    assert exp.plans["relational"].count("MultiwayJoin") == 1, \
        exp.plans["relational"]
    assert exp.plans["cost"].count("wcoj_strategy") == 1
    assert s.metrics_registry.snapshot()["wcoj.substituted"] == sub0 + 1
    res = g.cypher(q)
    assert result_digest(res) == result_digest(oracle.cypher(q))
    assert _wcoj_strategy(res) == ["wcoj"]


# -- degraded fallback -------------------------------------------------------


def test_failing_wcoj_falls_back_then_heals():
    """The degraded ladder, deterministic: an injected WCOJ fault serves
    the SAME answer via the embedded cascade (wcoj.fallbacks +
    faults.injected.wcoj tick), and the NEXT execution takes the fast
    path again."""
    from caps_tpu.obs.metrics import global_registry
    oracle = _random_graph(LocalCypherSession())
    want = result_digest(oracle.cypher(TRIANGLE_ENUM))
    s = TPUCypherSession()
    g = _random_graph(s)
    before = s.metrics_registry.snapshot().get("wcoj.fallbacks", 0)
    inj0 = global_registry().snapshot().get("faults.injected.wcoj", 0)
    with faults.failing_wcoj(n_times=1) as budget:
        res = g.cypher(TRIANGLE_ENUM)
        assert result_digest(res) == want
        assert _wcoj_strategy(res) == ["fallback-cascade"]
    assert budget.injected == 1
    assert global_registry().snapshot()["faults.injected.wcoj"] == inj0 + 1
    assert s.metrics_registry.snapshot()["wcoj.fallbacks"] == before + 1
    healed = g.cypher(TRIANGLE_ENUM)
    assert _wcoj_strategy(healed) == ["wcoj"]
    assert result_digest(healed) == want


def test_failing_wcoj_permanent_keeps_serving():
    s = TPUCypherSession()
    g = _random_graph(s)
    oracle = _random_graph(LocalCypherSession())
    want = result_digest(oracle.cypher(TRIANGLE_ENUM))
    with faults.failing_wcoj(n_times=None) as budget:
        for _ in range(3):
            res = g.cypher(TRIANGLE_ENUM)
            assert result_digest(res) == want
            assert _wcoj_strategy(res) == ["fallback-cascade"]
    assert budget.injected == 3


# -- cost-model selection & EXPLAIN ------------------------------------------


def test_explain_renders_wcoj_choice_before_execution():
    """EXPLAIN must show the substituted operator AND the model's
    wcoj-vs-cascade decision line without executing anything."""
    s = TPUCypherSession()
    g = _random_graph(s)
    exp = g.cypher("EXPLAIN " + TRIANGLE_ENUM)
    assert exp.records is None
    assert "MultiwayJoin" in exp.plans["relational"]
    assert "wcoj_strategy" in exp.plans["cost"]
    assert "wcoj_cost" in exp.plans["cost"]
    assert "cascade_cost" in exp.plans["cost"]
    # the relational line carries the chosen anchors + strategy tag
    assert "anchors=" in exp.plans["relational"]


def test_wcoj_decision_surface_prices_both_sides():
    from caps_tpu.ir.pattern import Direction
    from caps_tpu.relational.cost import CostModel
    from caps_tpu.relational.stats import GraphStatistics
    s = TPUCypherSession()
    g = _random_graph(s, n=60, e=600)
    from caps_tpu.relational.stats import graph_statistics
    model = CostModel(graph_statistics(g), lattice=s.shape_lattice)
    ext = [(("K",), Direction.OUTGOING, frozenset(), 1.0, ()),
           (("K",), Direction.OUTGOING, frozenset(), 1.0, (("K",),))]
    use, est, info = model.wcoj_vs_cascade(
        frozenset({"P"}), 1.0, ext, [("K",)])
    assert use is True  # dense cyclic pattern: intersection must win
    assert info["wcoj_cost"] < info["cascade_cost"]
    assert est >= 1.0
    assert model.decisions[-1]["kind"] == "wcoj_strategy"


def test_use_wcoj_off_is_the_cascade_everywhere():
    s = TPUCypherSession(config=EngineConfig(use_wcoj=False))
    g = _random_graph(s)
    exp = g.cypher("EXPLAIN " + TRIANGLE_ENUM)
    assert "MultiwayJoin" not in exp.plans["relational"]
    assert "Join" in exp.plans["relational"]


def test_model_off_still_substitutes():
    """With the cost model disabled the detected shape substitutes
    unconditionally (the heuristic default) — and stays correct."""
    oracle = _random_graph(LocalCypherSession())
    s = TPUCypherSession(config=EngineConfig(use_cost_model=False))
    g = _random_graph(s)
    res = g.cypher(TRIANGLE_ENUM)
    assert "MultiwayJoin" in _ops(res)
    assert result_digest(res) == result_digest(oracle.cypher(TRIANGLE_ENUM))


def test_est_rows_feed_op_stats():
    """The operator stamps its model estimate so the observed-statistics
    divergence loop (re-planning) sees WCOJ executions like any other
    operator's."""
    s = TPUCypherSession()
    g = _random_graph(s)
    res = g.cypher(TRIANGLE_ENUM)
    entry = [m for m in res.metrics["operators"]
             if m["op"] == "MultiwayJoin"][0]
    assert "est_rows" in entry and entry["rows"] >= 0
    from caps_tpu.frontend.parser import normalize_query
    fam_stats = s.op_stats.stats(normalize_query(TRIANGLE_ENUM))
    wcoj_keys = [k for k in fam_stats if k.endswith(":MultiwayJoin")]
    assert wcoj_keys, fam_stats  # the WCOJ op's actuals recorded
    assert fam_stats[wcoj_keys[0]].get("est_rows") is not None


# -- compile accounting ------------------------------------------------------


def test_wcoj_charges_compile_kind_once_then_zero():
    """First execution charges the ``wcoj`` compile kind for its
    first-seen step shapes; the SAME shapes never charge again — and a
    fused replay of the whole query charges zero compile seconds."""
    s = TPUCypherSession()
    g = _random_graph(s)
    r1 = g.cypher(TRIANGLE_ENUM)
    kinds1 = {c["kind"] for c in r1.metrics.get("compile_charges", ())}
    assert "wcoj" in kinds1
    replays0 = s.fused.replays + s.fused.generic_replays
    r2 = g.cypher(TRIANGLE_ENUM)  # fused replay
    assert r2.metrics["compile_s_charged"] == 0.0
    assert s.fused.replays + s.fused.generic_replays == replays0 + 1
    # a second graph with the same shape buckets reuses the compiled
    # steps: no new wcoj charges
    g2 = _random_graph(s, seed=9)
    r3 = g2.cypher(TRIANGLE_ENUM)
    kinds3 = [c for c in r3.metrics.get("compile_charges", ())
              if c["kind"] == "wcoj"]
    assert kinds3 == []


def test_cyclic_count_family_unseen_binding_compiles_nothing():
    """The item-1 tail, closed for the cycle family (PR 12 converted the
    main count path): count-fused closures are keyed by the parameter
    SHAPE signature, so an unseen binding of a warmed cyclic count
    family charges compile_s == 0.0 — predicate masks rebuild as eager
    device args, nothing re-traces."""
    from caps_tpu.relational.count_pattern import CountCycleOp
    # the sweep: no count-family op may override the shape-keyed contract
    assert "_value_keyed" not in CountCycleOp.__dict__
    s = TPUCypherSession()
    g = _random_graph(s, self_loops=False)
    q = ("MATCH (a:P)-[:K]->(b)-[:K]->(c), (a)-[:K]->(c) "
         "WHERE a.name = $seed RETURN count(*) AS c")
    first = g.cypher(q, {"seed": "n1"})
    assert [m for m in first.metrics["operators"]
            if m["op"] == "CountCycle"][0]["strategy"] == "cycle-probe"
    assert first.metrics["compile_s_charged"] > 0.0
    oracle = _random_graph(LocalCypherSession(), self_loops=False)
    for seed in ("n2", "n7"):  # unseen bindings: zero compile charged
        res = g.cypher(q, {"seed": seed})
        assert res.metrics["compile_s_charged"] == 0.0, seed
        assert res.records.to_maps() == \
            oracle.cypher(q, {"seed": seed}).records.to_maps()


# -- planner/optimizer analysis ----------------------------------------------


def test_match_cyclic_segment_shapes():
    from caps_tpu.logical.optimizer import match_cyclic_segment
    s = TPUCypherSession()
    g = _random_graph(s)

    def seg_of(query):
        # plan logically, then find the top into-Expand
        from caps_tpu.frontend.parser import parse_query
        from caps_tpu.ir.builder import IRBuilder
        from caps_tpu.logical.planner import LogicalPlanner
        from caps_tpu.logical.optimizer import LogicalOptimizer
        from caps_tpu.logical import ops as L
        from caps_tpu.relational.plan_cache import PlanParams
        ir = IRBuilder(g.schema, None, PlanParams({})).process(
            parse_query(query))
        logical = LogicalOptimizer(None).process(
            LogicalPlanner(g.schema, None, PlanParams({})).process(ir))
        found = []

        def walk(op):
            if isinstance(op, L.Expand) and op.into:
                found.append(op)
            for c in op.children:
                if isinstance(c, L.LogicalOperator):
                    walk(c)

        walk(logical.root)
        return match_cyclic_segment(found[0]) if found else None

    seg = seg_of(TRIANGLE_ENUM)
    assert seg is not None
    assert seg.order == ("a", "b", "c")
    assert sum(1 for e in seg.edges if e.closing) == 1
    # var-length in the chain: not a WCOJ shape
    assert seg_of("MATCH (a:P)-[r1:K*1..2]->(b), (a)-[r3:K]->(b) "
                  "RETURN id(b) AS x") is None
    # BOTH-direction closing edge: not a WCOJ shape
    assert seg_of("MATCH (a:P)-[r1:K]->(b)-[r2:K]->(c), (a)-[r3:K]-(c) "
                  "RETURN id(b) AS x") is None


def test_plan_steps_anchor_choice():
    """Anchors follow the model's expected degree: with no model the
    introducing edge anchors; the deferred closing edge semi-filters
    and closes."""
    from caps_tpu.logical.optimizer import match_cyclic_segment
    from caps_tpu.relational.wcoj import plan_steps
    from caps_tpu.logical import ops as L
    from caps_tpu.ir.pattern import Direction
    scan = L.NodeScan(L.Start(), "a", frozenset({"P"}),
                      fields=(("a", None),))
    e1 = L.Expand(scan, "a", "r1", ("K",), "b", frozenset(),
                  Direction.OUTGOING, fields=())
    e2 = L.Expand(e1, "b", "r2", ("K",), "c", frozenset(),
                  Direction.OUTGOING, fields=())
    e3 = L.Expand(e2, "a", "r3", ("K",), "c", frozenset(),
                  Direction.OUTGOING, into=True, fields=())
    seg = match_cyclic_segment(e3)
    assert seg is not None
    extends, closes = plan_steps(seg, model=None)
    assert [s.var for s in extends] == ["b", "c"]
    assert extends[1].anchor.rel == "r2"
    assert [c.rel_types for c in extends[1].checks] == [("K",)]
    assert [c.edge.rel for c in closes] == ["r3"]

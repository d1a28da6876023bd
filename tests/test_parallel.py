"""Sharded execution on the 8-virtual-device CPU mesh (SURVEY.md §4:
mesh size is config; same program runs 1-chip or v5e-8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caps_tpu.parallel.mesh import make_mesh
from caps_tpu.parallel.query_step import (
    make_collectives_smoke, make_sharded_two_hop, two_hop_count_kernel,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _graph(n_nodes, n_edges, seed=7):
    rng = np.random.RandomState(seed)
    names = jnp.asarray(rng.randint(0, 5, n_nodes, dtype=np.int32))
    src = jnp.asarray(rng.randint(0, n_nodes, n_edges, dtype=np.int32))
    dst = jnp.asarray(rng.randint(0, n_nodes, n_edges, dtype=np.int32))
    ok = jnp.ones(n_edges, bool)
    return names, src, dst, ok


def _expected_paths(names, src, dst, seed_code):
    names, src, dst = map(np.asarray, (names, src, dst))
    cnt1 = np.bincount(dst[names[src] == seed_code], minlength=len(names))
    return int(cnt1[src].sum())


def test_sharded_two_hop_matches_reference(mesh):
    names, src, dst, ok = _graph(64, 8 * 32)
    step = make_sharded_two_hop(mesh, 64)
    total, cnt2 = step(names, src, dst, ok, jnp.int32(3))
    assert int(total) == _expected_paths(names, src, dst, 3)
    assert int(cnt2.sum()) == int(total)


def test_mesh_size_is_config(mesh):
    """The same kernel runs on a 1-device mesh and the 8-device mesh."""
    names, src, dst, ok = _graph(32, 8 * 8, seed=9)
    expected = _expected_paths(names, src, dst, 2)
    for n in (1, 2, 8):
        sub = make_mesh(n)
        step = make_sharded_two_hop(sub, 32)
        assert int(step(names, src, dst, ok, jnp.int32(2))[0]) == expected


def test_collectives_smoke(mesh):
    smoke = make_collectives_smoke(mesh)
    out = smoke(jnp.arange(8 * 8, dtype=jnp.int32))
    assert np.isfinite(int(out))


def test_graft_entry_points():
    import __graft_entry__ as g
    fn, args = g.entry()
    total, cnt2 = jax.jit(fn)(*args)
    assert int(total) >= 0
    g.dryrun_multichip(8)


def test_ring_khop_matches_reference():
    """Ring-rotated k-hop expansion (ppermute schedule) vs the dense
    single-device twin (SURVEY.md §5.7)."""
    import numpy as np
    import jax.numpy as jnp
    from caps_tpu.parallel.mesh import make_mesh
    from caps_tpu.parallel.ring import make_ring_khop, ring_khop_reference

    n_shards, n_nodes, n_edges, hops = 8, 64, 256, 3
    rng = np.random.RandomState(7)
    src = jnp.asarray(rng.randint(0, n_nodes, n_edges, dtype=np.int32))
    dst = jnp.asarray(rng.randint(0, n_nodes, n_edges, dtype=np.int32))
    ok = jnp.asarray(rng.rand(n_edges) < 0.9)
    seed = jnp.asarray((rng.rand(n_nodes) < 0.2).astype(np.int32))

    mesh = make_mesh(n_shards)
    total, blocks = make_ring_khop(mesh, n_nodes, hops)(seed, src, dst, ok)
    want_total, want_cnt = ring_khop_reference(seed, src, dst, ok, hops,
                                               n_nodes)
    assert int(total) == int(want_total)
    np.testing.assert_array_equal(np.asarray(blocks), np.asarray(want_cnt))


def test_ring_varexpand_matrix_matches_reference(mesh):
    """Matrix-frontier ring expansion (general VarExpand form) vs the
    single-device twin, including self-loops (the length-2 isomorphism
    correction) and masked targets."""
    from caps_tpu.parallel.ring import (
        make_ring_varexpand, ring_varexpand_reference,
    )

    n_nodes, n_edges, n_seeds = 64, 256, 9
    rng = np.random.RandomState(11)
    src = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    # force a batch of self-loops so the correction has work to do
    src[:20] = dst[:20]
    ok = rng.rand(n_edges) < 0.9
    seeds = rng.choice(n_nodes, size=n_seeds, replace=False)
    f0 = np.zeros((n_seeds, n_nodes), dtype=np.int64)
    f0[np.arange(n_seeds), seeds] = 1
    tmask = (rng.rand(n_nodes) < 0.7).astype(np.int64)

    for lengths in [(1,), (2,), (1, 2), (0, 1, 2), (0,)]:
        fn = make_ring_varexpand(mesh, n_nodes, lengths)
        got = fn(jnp.asarray(f0), jnp.asarray(src), jnp.asarray(dst),
                 jnp.asarray(ok), jnp.asarray(tmask))
        want = ring_varexpand_reference(
            jnp.asarray(f0), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(ok), jnp.asarray(tmask), lengths)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"lengths={lengths}")


def test_ring_varexpand_pathcount_oracle(mesh):
    """The ring multiplicity matrix equals brute-force path enumeration
    with relationship isomorphism (r2 != r1)."""
    from caps_tpu.parallel.ring import make_ring_varexpand

    n_nodes, n_edges = 16, 48
    rng = np.random.RandomState(3)
    src = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    src[:6] = dst[:6]
    ok = np.ones(n_edges, bool)
    f0 = np.eye(n_nodes, dtype=np.int64)
    tmask = np.ones(n_nodes, dtype=np.int64)

    fn = make_ring_varexpand(mesh, n_nodes, (1, 2))
    got = np.asarray(fn(jnp.asarray(f0), jnp.asarray(src), jnp.asarray(dst),
                        jnp.asarray(ok), jnp.asarray(tmask)))
    want = np.zeros((n_nodes, n_nodes), dtype=np.int64)
    for e1 in range(n_edges):
        want[src[e1], dst[e1]] += 1  # length 1
        for e2 in range(n_edges):
            if e1 != e2 and dst[e1] == src[e2]:
                want[src[e1], dst[e2]] += 1  # length 2, r2 != r1
    np.testing.assert_array_equal(got, want)


def test_varexpand_rides_ring_on_mesh():
    """End-to-end: on a mesh, a var-length query whose rel variable is
    dead downstream executes with strategy=ring-matrix and matches the
    oracle; queries that need per-path rel data stay on joins."""
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.testing.bag import Bag
    from caps_tpu.testing.factory import create_graph

    create = ("CREATE (a:Person {name:'Alice'}), (b:Person {name:'Bob'}), "
              "(c:Person {name:'Carol'}), (d {name:'Dave'}), "
              "(a)-[:KNOWS]->(b), (b)-[:KNOWS]->(c), (a)-[:KNOWS]->(c), "
              "(c)-[:KNOWS]->(d), (d)-[:KNOWS]->(d), (c)-[:LIKES]->(a)")
    sharded = TPUCypherSession(config=EngineConfig(mesh_shape=(8,)))
    oracle = LocalCypherSession()
    gs = create_graph(sharded, create, {})
    go = create_graph(oracle, create, {})
    cases = [
        ("MATCH (a)-[:KNOWS*1..2]->(b) RETURN a.name AS a, b.name AS b",
         "ring-matrix"),
        ("MATCH (a)<-[:KNOWS*1..2]-(b) RETURN a.name AS a, b.name AS b",
         "ring-matrix"),
        ("MATCH (a)-[:KNOWS*0..2]->(b:Person) RETURN b.name AS b",
         "ring-matrix"),
        ("MATCH (a:Person)-[*1..2]->(b) RETURN a.name AS a, b.name AS b",
         "ring-matrix"),
        # size(r)-only use is rewritten to a path-length column and
        # stays on the matrix path
        ("MATCH (a)-[r:KNOWS*1..2]->(b) RETURN a.name AS a, size(r) AS n",
         "ring-matrix"),
        # rel var VALUE returned -> per-path data -> join path
        ("MATCH (a)-[r:KNOWS*1..2]->(b) RETURN a.name AS a, r AS r",
         "join"),
        # undirected rides the ring too (symmetrized edges + degree
        # correction)
        ("MATCH (a)-[:KNOWS*1..2]-(b) RETURN a.name AS a, b.name AS b",
         "ring-matrix"),
        ("MATCH (a)-[*0..2]-(b:Person) RETURN b.name AS b",
         "ring-matrix"),
        ("MATCH (a)-[:KNOWS*1..3]->(b) RETURN a.name AS a, b.name AS b",
         "ring-matrix"),
        # beyond the 3-hop correction bound -> join path
        ("MATCH (a)-[:KNOWS*1..4]->(b) RETURN a.name AS a, b.name AS b",
         "join"),
    ]
    for q, want_strategy in cases:
        res = gs.cypher(q)
        got = res.records.to_maps()
        want = go.cypher(q).records.to_maps()
        assert Bag(got) == Bag(want), (q, got, want)
        ve = [m for m in res.metrics["operators"] if m["op"] == "VarExpand"]
        assert ve and ve[0]["strategy"] == want_strategy, (q, ve)
    assert sharded.fallback_count == 0, sharded.backend.fallback_reasons


def test_ring_varexpand_undirected_oracle(mesh):
    """Degree-form correction vs brute-force undirected path
    enumeration with relationship isomorphism (e2 != e1), including
    self-loops and parallel edges."""
    from caps_tpu.parallel.ring import (
        make_ring_varexpand, ring_varexpand_reference,
    )

    n_nodes, n_edges = 16, 40
    rng = np.random.RandomState(9)
    src = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    src[:5] = dst[:5]               # self-loops
    src[5:8], dst[5:8] = src[8:11], dst[8:11]  # parallel edges

    # symmetrize exactly as the engine does
    nonloop = src != dst
    a = np.concatenate([src, dst[nonloop]])
    b = np.concatenate([dst, src[nonloop]])
    pad = (-len(a)) % 8
    a = np.concatenate([a, np.zeros(pad, np.int32)])
    b = np.concatenate([b, np.zeros(pad, np.int32)])
    okp = np.concatenate([np.ones(len(a) - pad, bool), np.zeros(pad, bool)])

    f0 = np.eye(n_nodes, dtype=np.int64)
    tmask = np.ones(n_nodes, dtype=np.int64)
    fn = make_ring_varexpand(mesh, n_nodes, (1, 2), correction="degree")
    got = np.asarray(fn(jnp.asarray(f0), jnp.asarray(a), jnp.asarray(b),
                        jnp.asarray(okp), jnp.asarray(tmask)))
    ref = np.asarray(ring_varexpand_reference(
        jnp.asarray(f0), jnp.asarray(a), jnp.asarray(b), jnp.asarray(okp),
        jnp.asarray(tmask), (1, 2), correction="degree"))
    np.testing.assert_array_equal(got, ref)

    # brute force: undirected steps carry (edge id, far end)
    steps = [[] for _ in range(n_nodes)]  # node -> [(eid, far)]
    for eid, (u, v) in enumerate(zip(src, dst)):
        steps[u].append((eid, v))
        if u != v:
            steps[v].append((eid, u))
    want = np.zeros((n_nodes, n_nodes), dtype=np.int64)
    for s0 in range(n_nodes):
        for e1, m in steps[s0]:
            want[s0, m] += 1                        # length 1
            for e2, t in steps[m]:
                if e2 != e1:
                    want[s0, t] += 1                # length 2
    np.testing.assert_array_equal(got, want)


def test_varexpand_matrix_single_chip():
    """Off-mesh, an eligible var-expand takes the single-device matrix
    strategy (same SpMV computation, no collectives) with oracle
    parity."""
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.testing.bag import Bag
    from caps_tpu.testing.factory import create_graph

    create = ("CREATE (a:Person {name:'Alice'}), (b:Person {name:'Bob'}), "
              "(c:Person {name:'Carol'}), (a)-[:KNOWS]->(b), "
              "(b)-[:KNOWS]->(c), (c)-[:KNOWS]->(c)")
    tpu = TPUCypherSession()
    oracle = LocalCypherSession()
    gt = create_graph(tpu, create, {})
    go = create_graph(oracle, create, {})
    for q, strat in [
        ("MATCH (a)-[:KNOWS*1..2]->(b) RETURN a.name AS a, b.name AS b",
         "matrix"),
        ("MATCH (a)-[:KNOWS*1..2]-(b) RETURN a.name AS a, b.name AS b",
         "matrix"),
        ("MATCH (a)-[r:KNOWS*1..2]->(b) RETURN size(r) AS n", "matrix"),
        ("MATCH (a)-[r:KNOWS*1..2]->(b) RETURN r AS r", "join"),
    ]:
        res = gt.cypher(q)
        assert Bag(res.records.to_maps()) == \
            Bag(go.cypher(q).records.to_maps()), q
        ve = [m for m in res.metrics["operators"] if m["op"] == "VarExpand"]
        assert ve and ve[0]["strategy"] == strat, (q, ve)
    assert tpu.fallback_count == 0


def test_two_level_mesh_parity():
    """A 2-D (DCN x ICI) mesh — multi-slice topology — runs the full
    engine with GSPMD sharding over both axes and oracle parity; the
    hand-scheduled rings correctly stand down to partitioner paths."""
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.testing.bag import Bag
    from caps_tpu.testing.factory import create_graph

    create = ("CREATE (a:Person {name:'Ada', age:30}), "
              "(b:Person {name:'Bo', age:40}), (c:Person {name:'Cy'}), "
              "(a)-[:KNOWS]->(b), (b)-[:KNOWS]->(c), (a)-[:KNOWS]->(c)")
    multi = TPUCypherSession(config=EngineConfig(mesh_shape=(2, 4)))
    assert multi.backend.mesh.axis_names == ("dcn", "shard")
    assert multi.backend.mesh.devices.shape == (2, 4)
    oracle = LocalCypherSession()
    gm = create_graph(multi, create, {})
    go = create_graph(oracle, create, {})
    queries = [
        "MATCH (a:Person)-[:KNOWS]->(b) RETURN a.name AS a, b.name AS b",
        "MATCH (a)-[:KNOWS*1..2]->(b) RETURN a.name AS a, b.name AS b",
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
        "WHERE a.name='Ada' RETURN count(*) AS c",
        "MATCH (p:Person) RETURN p.name AS n, min(p.age) AS a ORDER BY n",
    ]
    for q in queries:
        res = gm.cypher(q)
        assert Bag(res.records.to_maps()) == \
            Bag(go.cypher(q).records.to_maps()), q
    # var-expand must report the partitioner-backed matrix strategy
    res = gm.cypher("MATCH (a)-[:KNOWS*1..2]->(b) RETURN b.name AS b")
    ve = [m for m in res.metrics["operators"] if m["op"] == "VarExpand"]
    assert ve and ve[0]["strategy"] == "matrix", ve
    assert multi.fallback_count == 0, multi.backend.fallback_reasons


def test_varexpand_matrix_three_hops_oracle(mesh):
    """*1..3 / *3..3 / *0..3 on the matrix path — the 3-hop
    relationship-isomorphism inclusion-exclusion (W3 - A12 - A23 - A13
    + 2T) — against the join-path oracle, on a multigraph with
    self-loops and parallel edges, in all three directions, single-chip
    and ring."""
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.okapi.config import EngineConfig
    from caps_tpu.testing.bag import Bag
    from caps_tpu.testing.factory import create_graph

    rng = np.random.RandomState(5)
    n = 7
    parts = [f"(n{i}:P {{v: {i}}})" for i in range(n)]
    edges = []
    for _ in range(14):
        u, v = rng.randint(0, n), rng.randint(0, n)
        edges.append(f"(n{u})-[:K]->(n{v})")
    edges += ["(n0)-[:K]->(n0)",                    # self-loop
              "(n1)-[:K]->(n2)", "(n1)-[:K]->(n2)"]  # parallel edges
    create = "CREATE " + ", ".join(parts + edges)

    oracle = LocalCypherSession()
    single = TPUCypherSession()
    sharded = TPUCypherSession(config=EngineConfig(mesh_shape=(8,)))
    go = create_graph(oracle, create, {})
    gt = create_graph(single, create, {})
    gs = create_graph(sharded, create, {})
    for pat in ["-[:K*1..3]->", "<-[:K*1..3]-", "-[:K*1..3]-",
                "-[:K*3..3]->", "-[:K*0..3]-", "-[:K*2..3]-"]:
        q = f"MATCH (a){pat}(b) RETURN a.v AS a, b.v AS b"
        want = go.cypher(q).records.to_maps()
        for name, g, strat in (("single", gt, "matrix"),
                               ("sharded", gs, "ring-matrix")):
            res = g.cypher(q)
            assert Bag(res.records.to_maps()) == Bag(want), (name, pat)
            ve = [m for m in res.metrics["operators"]
                  if m["op"] == "VarExpand"]
            assert ve[0]["strategy"] == strat, (name, pat, ve)
    assert single.fallback_count == 0 and sharded.fallback_count == 0


def test_ring_varexpand3_kernel_vs_twin(mesh):
    """Sharded 3-hop program vs the single-device twin on random
    weighted sparse corrections."""
    from caps_tpu.parallel.ring import (
        build_iso3_sparse, make_ring_varexpand3,
        ring_varexpand3_reference,
    )

    n_nodes, n_rels = 16, 30
    rng = np.random.RandomState(2)
    src = rng.randint(0, n_nodes, n_rels).astype(np.int32)
    dst = rng.randint(0, n_nodes, n_rels).astype(np.int32)
    src[:4] = dst[:4]
    rid = np.arange(n_rels)
    nonloop = src != dst
    frm = np.concatenate([src, dst[nonloop]]).astype(np.int32)
    to = np.concatenate([dst, src[nonloop]]).astype(np.int32)
    rids = np.concatenate([rid, rid[nonloop]])
    sp13, spt = build_iso3_sparse(frm, to, rids, n_nodes)

    def pad(xs, fill=0):
        p = (-len(xs[0])) % 8
        return tuple(np.concatenate([x, np.full(p, fill, x.dtype)])
                     for x in xs)

    frm_p, to_p = pad((frm, to))
    ok_p = np.arange(len(frm_p)) < len(frm)
    sp13_p = pad(sp13)
    spt_p = pad(spt)
    f0 = np.eye(n_nodes, dtype=np.int64)
    tmask = np.ones(n_nodes, dtype=np.int64)

    fn = make_ring_varexpand3(mesh, n_nodes, (1, 2, 3),
                              correction="degree")
    got = np.asarray(fn(jnp.asarray(f0), jnp.asarray(frm_p),
                        jnp.asarray(to_p), jnp.asarray(ok_p),
                        jnp.asarray(tmask),
                        *[jnp.asarray(x) for x in sp13_p],
                        *[jnp.asarray(x) for x in spt_p]))
    want = np.asarray(ring_varexpand3_reference(
        jnp.asarray(f0), jnp.asarray(frm_p), jnp.asarray(to_p),
        jnp.asarray(ok_p), jnp.asarray(tmask), (1, 2, 3),
        tuple(jnp.asarray(x) for x in sp13_p),
        tuple(jnp.asarray(x) for x in spt_p), correction="degree"))
    np.testing.assert_array_equal(got, want)
    assert got.sum() > 0


def test_varexpand_matrix_seed_blocking(monkeypatch):
    """Large seed sets run the matrix path in fixed-size chunks whose
    pair tables union — forced here by shrinking the working-set cap —
    with identical results and strategy."""
    from caps_tpu.backends.local.session import LocalCypherSession
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.relational.var_expand import VarExpandOp
    from caps_tpu.testing.bag import Bag
    from caps_tpu.testing.factory import create_graph

    rng = np.random.RandomState(3)
    n = 9
    parts = [f"(n{i}:P {{v: {i}}})" for i in range(n)]
    edges = [f"(n{rng.randint(0, n)})-[:K]->(n{rng.randint(0, n)})"
             for _ in range(18)]
    create = "CREATE " + ", ".join(parts + edges)
    q = "MATCH (a)-[:K*1..2]-(b) RETURN a.v AS a, b.v AS b"
    want = create_graph(LocalCypherSession(), create, {}
                        ).cypher(q).records.to_maps()

    # force chunking: the per-seed cost is ~bucket-capacity (256-padded
    # edge list), so a ~3-seed budget splits the 9 seeds into chunks
    monkeypatch.setattr(VarExpandOp, "_RING_MAX_MATRIX", 2000)
    tpu = TPUCypherSession()
    res = create_graph(tpu, create, {}).cypher(q)
    assert Bag(res.records.to_maps()) == Bag(want)
    ve = [m for m in res.metrics["operators"] if m["op"] == "VarExpand"]
    assert ve and ve[0]["strategy"] == "matrix", ve
    assert tpu.fallback_count == 0


def test_int64_safe_collectives_match_lax(mesh):
    """``global_max``/``sum_scatter`` stand in for ``lax.pmax`` /
    ``lax.psum_scatter``, which the TPU backend does not lower for 64-bit
    values (tests/test_tpu_compile.py compiles them for a v5e); here they
    must compute what the lax forms compute."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from caps_tpu.parallel.collectives import global_max, sum_scatter
    n = int(mesh.devices.size)
    axis = mesh.axis_names[0]

    def on_mesh(body, x, out_specs):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=out_specs))(x)

    edge = [5, -3, 2**40, 2**40 + 7, -(2**62), 2**31, 2**32 - 1,
            -(2**31) - 1]
    for rot in range(len(edge)):
        vals = np.array((edge[rot:] + edge[:rot]) * n, np.int64)[:n]
        got = on_mesh(lambda v: global_max(v[0], axis), jnp.asarray(vals),
                      P())
        assert int(got) == int(vals.max()), (vals, int(got))
    got32 = on_mesh(lambda v: global_max(v[0], axis),
                    jnp.arange(n, dtype=jnp.int32), P())
    assert int(got32) == n - 1

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randint(-2**40, 2**40, (n * 2 * n, 3))
                    .astype(np.int64))
    got = on_mesh(lambda v: sum_scatter(v, axis, n), x, P(axis))
    want = on_mesh(lambda v: jax.lax.psum_scatter(
        v, axis, scatter_dimension=0, tiled=True), x, P(axis))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

"""TPU-backend specifics: differential parity vs the oracle and
zero-fallback guarantees on the hot path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.testing.bag import Bag
from caps_tpu.testing.factory import create_graph

SOCIAL = ("CREATE (a:Person {name: 'Alice', age: 23})-"
          "[:KNOWS {since: 2017}]->(b:Person {name: 'Bob', age: 42}), "
          "(b)-[:KNOWS {since: 2016}]->(c:Person {name: 'Carol', age: 1984})")

DIFFERENTIAL_QUERIES = [
    "MATCH (a:Person) RETURN a.name AS n, a.age AS age",
    "MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN a.name AS a, c.name AS c",
    "MATCH (a)-[k:KNOWS]-(b) WHERE k.since > 2016 RETURN a.name AS n",
    "MATCH (a:Person) WHERE a.name STARTS WITH 'A' OR a.age > 100 "
    "RETURN a.name AS n",
    "MATCH (a:Person) RETURN count(*) AS c, sum(a.age) AS s, avg(a.age) AS av,"
    " min(a.name) AS mn, max(a.name) AS mx",
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN a.name AS n, count(*) AS c",
    "MATCH (a:Person) RETURN a.name AS n ORDER BY a.age DESC SKIP 1 LIMIT 1",
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:KNOWS]->(b) "
    "RETURN a.name AS a, b.name AS b",
    "MATCH (a)-[rs:KNOWS*1..2]->(b) RETURN a.name AS a, b.name AS b, "
    "size(rs) AS hops",
    "UNWIND [3, 1, 2] AS x RETURN x ORDER BY x",
    "MATCH (a:Person) WITH DISTINCT a.age > 30 AS old RETURN old",
    "MATCH (a:Person) WHERE a.name IN ['Alice', 'Carol'] RETURN a.age AS v",
    "MATCH (a:Person) RETURN toUpper(a.name) AS u, size(a.name) AS s",
    "MATCH (a:Person), (b:Person) WHERE a.age < b.age "
    "RETURN a.name AS a, b.name AS b",
]


@pytest.fixture(scope="module")
def sessions():
    return LocalCypherSession(), TPUCypherSession()


@pytest.fixture(scope="module")
def graphs(sessions):
    local, tpu = sessions
    return create_graph(local, SOCIAL), create_graph(tpu, SOCIAL)


@pytest.mark.parametrize("query", DIFFERENTIAL_QUERIES)
def test_differential_parity(graphs, query):
    g_local, g_tpu = graphs
    expected = g_local.cypher(query).records.to_maps()
    actual = g_tpu.cypher(query).records.to_maps()
    assert Bag(actual) == Bag(expected), Bag(expected).diff(Bag(actual))


# (n, out_cap, kept rows, the form the shapes select): both sides of
# compact_indices' shape rule, far from its crossover and one bucket
# either side of it
COMPACT_CASES = {
    "narrow-empty": (1 << 16, 64, [], "search"),
    "narrow-first-row": (1 << 16, 64, [0], "search"),
    "narrow-last-row": (1 << 16, 64, [(1 << 16) - 1], "search"),
    "narrow-count-is-out_cap": (1 << 16, 64, range(5, 1 << 16, 1024),
                                "search"),
    "narrow-count-over-out_cap": (1 << 16, 64, range(0, 1 << 16, 3), "search"),
    "narrow-dense": (1 << 16, 64, range(40000, 40050), "search"),
    "n-far-over-out_cap": (1 << 18, 16, [7, 1 << 17, (1 << 18) - 1], "search"),
    "narrow-odd-n": (70001, 32, [0, 1, 35000, 70000], "search"),
    "wide-empty": (256, 256, [], "sort"),
    "wide-first-row": (256, 256, [0], "sort"),
    "wide-last-row": (256, 256, [255], "sort"),
    "wide-full": (256, 256, range(256), "sort"),
    "wide-count-over-out_cap": (1024, 800, range(1024), "sort"),
    "wide-odd-n": (100, 128, range(1, 100, 2), "sort"),
    "wide-million": (1 << 20, 1 << 20, range(0, 1 << 20, 1 << 10), "sort"),
    "wide-one-row-dropped": (1 << 18, 1 << 18,
                             [i for i in range(1 << 18) if i != 77_777],
                             "sort"),
    "wide-none-kept": (1 << 18, 1 << 18, [], "sort"),
    "wide-all-kept": (1 << 18, 1 << 18, range(1 << 18), "sort"),
    "out_cap-over-n-all-kept": (100, 128, range(100), "sort"),
    "wide-sparse-ends": (4096, 4096, [0, 1, 2047, 4095], "sort"),
    "narrow-two-rows": (1 << 16, 64, [3, 60000], "search"),
    "small-narrow-sorts": (4096, 256, [0, 17, 4095], "sort"),
    "crossover-search-side": (1 << 20, 1024, range(0, 1 << 20, 1031),
                              "search"),
    "crossover-sort-side": (1 << 20, 2048, range(0, 1 << 20, 1031), "sort"),
}


@pytest.mark.parametrize("case", list(COMPACT_CASES))
def test_compact_indices_matches_flatnonzero(case):
    """Values, order, fill, dtype and shape are jnp.nonzero's whichever
    form the two static shapes select, and neither form scatters."""
    from caps_tpu.backends.tpu import kernels as K
    n, out_cap, kept, form = COMPACT_CASES[case]
    rows = np.zeros(n, bool)
    rows[list(kept)] = True
    expected = np.zeros(out_cap, np.int64)
    first = np.flatnonzero(rows)[:out_cap]
    expected[:len(first)] = first
    mask = jnp.asarray(rows)
    got = K.compact_indices(mask, out_cap)
    assert got.dtype == jnp.int64 and got.shape == (out_cap,)
    np.testing.assert_array_equal(np.asarray(got), expected)
    assert K.compact_form(n, out_cap) == form
    program = str(jax.make_jaxpr(
        lambda m: K.compact_indices(m, out_cap))(mask))
    assert "scatter" not in program
    assert ("sort[" in program) == (form == "sort")


# (rows in the node table, the filter, rows it keeps, the form its two
# shapes select): a look-up among 2^18 rows into the smallest bucket
# searches; a filter that keeps most rows, or any over a small table, sorts
FILTER_FORMS = {
    "one-of-262144": (1 << 18, "a.v = 5", 1, "search"),
    "most-of-65536": (1 << 16, "a.v >= 5", (1 << 16) - 5, "sort"),
    "one-of-100": (100, "a.v = 5", 1, "sort"),
}


@pytest.mark.parametrize("case", list(FILTER_FORMS))
def test_filter_counts_its_compaction_by_form(case):
    """``backend.sort_compactions`` / ``backend.search_compactions`` move
    by the form ``compact_indices`` takes for the filter's shapes."""
    from caps_tpu.backends.tpu import kernels as K
    from caps_tpu.okapi.types import CTInteger
    from caps_tpu.relational.entity_tables import NodeMapping, NodeTable
    n, where, kept, form = FILTER_FORMS[case]
    session = TPUCypherSession()
    be = session.backend
    assert K.compact_form(be.bucket(n), be.bucket(kept)) == form
    people = NodeTable(
        NodeMapping.on("_id").with_implied_labels("Person").with_property("v"),
        session.table_factory.from_columns(
            {"_id": list(range(n)), "v": list(range(n))},
            {"_id": CTInteger, "v": CTInteger}))
    g = session.create_graph([people], [])
    keys = ("backend.sort_compactions", "backend.search_compactions")
    before = session.metrics_snapshot()
    rows = g.cypher(f"MATCH (a:Person) WHERE {where} RETURN a.v AS v"
                    ).records.to_maps()
    after = session.metrics_snapshot()
    assert len(rows) == kept
    moved = tuple(after[k] - before[k] for k in keys)
    assert moved == ((1, 0) if form == "sort" else (0, 1))
    assert session.fallback_count == 0


def test_hot_path_has_no_fallbacks():
    session = TPUCypherSession()
    g = create_graph(session, SOCIAL)
    before = session.fallback_count
    g.cypher("MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
             "WHERE a.name = 'Alice' RETURN c.name AS n").records.to_maps()
    assert session.fallback_count == before, session.backend.fallback_reasons


def test_collect_stays_on_device():
    session = TPUCypherSession()
    g = create_graph(session, SOCIAL)
    before = session.fallback_count
    rows = g.cypher("MATCH (a:Person) RETURN collect(a.age) AS l").records.to_maps()
    assert sorted(rows[0]["l"]) == [23, 42, 1984]
    # collect gained a device path (table.py device collect); it must no
    # longer bounce the query to the oracle backend.
    assert session.fallback_count == before, session.backend.fallback_reasons


def test_string_pool_roundtrip():
    session = TPUCypherSession()
    g = create_graph(session, "CREATE ({s: 'zeta'}), ({s: 'alpha'}), ({s: 'beta'})")
    rows = g.cypher("MATCH (n) RETURN n.s AS s ORDER BY s").records.to_maps()
    assert [r["s"] for r in rows] == ["alpha", "beta", "zeta"]


def test_distinct_aggregates_stay_on_device():
    """DISTINCT aggregation has a device path (one extra stable sort per
    distinct column marks first occurrences — table.py _group_device);
    count/sum/avg/collect(DISTINCT x) must not bounce to the oracle
    (round-4 VERDICT item 6)."""
    session = TPUCypherSession()
    g = create_graph(session, "CREATE (:P {v: 1, g: 'a'}), (:P {v: 1, g: 'a'}), "
                              "(:P {v: 2, g: 'a'}), (:P {v: 2, g: 'b'}), "
                              "(:P {v: 3, g: 'b'})")
    before = session.fallback_count
    rows = g.cypher("MATCH (n:P) RETURN count(DISTINCT n.v) AS c, "
                    "sum(DISTINCT n.v) AS s, collect(DISTINCT n.v) AS l"
                    ).records.to_maps()
    assert rows[0]["c"] == 3 and rows[0]["s"] == 6
    assert sorted(rows[0]["l"]) == [1, 2, 3]
    rows = g.cypher("MATCH (n:P) RETURN n.g AS g, count(DISTINCT n.v) AS c "
                    "ORDER BY g").records.to_maps()
    assert rows == [{"g": "a", "c": 2}, {"g": "b", "c": 2}]
    assert session.fallback_count == before, session.backend.fallback_reasons


# -- persistent compile cache placement (backends/tpu/table.py) -------------

@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_is_placed_from_outside(monkeypatch, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set the engine configures no
    directory (JAX reads the variable itself); unset, it is one fixed
    path inside the checkout — never under ~/.cache, never a temp name.
    The min-compile-time threshold drops to 0 either way."""
    import os
    import jax
    from caps_tpu.backends.tpu import table as T
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    T._place_compile_cache()
    assert updates.pop("jax_persistent_cache_min_compile_time_secs") == 0.0
    if env_dir is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert updates == {
            "jax_compilation_cache_dir": os.path.join(repo, ".jax_cache")}
    else:
        assert updates == {}


def test_compile_cache_untouched_on_cpu(monkeypatch):
    import jax
    from caps_tpu.backends.tpu import table as T
    monkeypatch.setattr(jax.config, "update", lambda k, v: pytest.fail(
        f"CPU session configured {k}"))
    T._place_compile_cache()


# -- datasets/foaf.py: the smoke's oracles against the oracle backend -------

def test_foaf_oracles_honour_relationship_uniqueness():
    """Self-loops on seed people make r1 == r2 reachable; the numpy
    oracles must agree with the local backend there."""
    import numpy as np
    from caps_tpu.datasets import foaf
    session = LocalCypherSession()
    graph, src, dst, names, ages = foaf.build_graph(
        session, 40, 400, 6, np.random.RandomState(5))
    is_alice = np.asarray(names) == "Alice"
    assert ((src == dst) & is_alice[src]).sum() > 0  # the case at stake
    seeds = ["Alice", names[int(src[0])]]
    want = foaf.expected_paths(src, dst, names, seeds)
    for seed in seeds:
        got = graph.cypher(foaf.PARAM_QUERY, {"seed": seed}) \
            .records.to_maps()[0]["c"]
        assert got == want[seed], seed
        assert graph.cypher(foaf.AGE_TOP_QUERY, {"seed": seed}) \
            .records.to_maps() \
            == foaf.expected_age_top(src, dst, names, ages, seed)
        assert graph.cypher(foaf.AGE_SPLIT_QUERY, {"seed": seed}) \
            .records.to_maps() \
            == foaf.expected_age_split(src, dst, names, ages, seed)


# -- keep= on the two gather sites of the Table SPI --------------------------

def _keep_factories():
    from caps_tpu.okapi.config import EngineConfig
    return {
        "local": lambda: LocalCypherSession().table_factory,
        "tpu-expand-kernel": lambda: TPUCypherSession().table_factory,
        "tpu-jnp": lambda: TPUCypherSession(
            config=EngineConfig(use_pallas=False)).table_factory,
    }


def _keep_tables(factory):
    from caps_tpu.okapi.types import CTInteger, CTString
    left = factory.from_columns(
        {"l_id": [1, 2, 3, 4, None], "l_k": [10, 20, 20, 40, 50],
         "l_s": ["a", "b", None, "d", "e"]},
        {"l_id": CTInteger, "l_k": CTInteger, "l_s": CTString})
    right = factory.from_columns(
        {"r_id": [1, 2, 2, 3, 7], "r_k": [10, 20, 21, 20, 70],
         "r_s": ["u", "v", "w", None, "y"]},
        {"r_id": CTInteger, "r_k": CTInteger, "r_s": CTString})
    return left, right


def _projected(table, cols):
    return Bag([{c: r[c] for c in cols} for r in table.rows()])


JOIN_KEEP_CASES = {
    "inner-one-side-each": ("inner", [("l_id", "r_id")], ["l_s", "r_k"]),
    "inner-omits-both-keys": ("inner", [("l_id", "r_id")], ["l_k", "r_s"]),
    "inner-keys-only": ("inner", [("l_id", "r_id")], ["l_id", "r_id"]),
    "inner-left-only": ("inner", [("l_id", "r_id")], ["l_s"]),
    "inner-right-only": ("inner", [("l_id", "r_id")], ["r_s"]),
    "left-omits-both-keys": ("left", [("l_id", "r_id")], ["l_s", "r_s"]),
    "left-right-only": ("left", [("l_id", "r_id")], ["r_k"]),
    "two-pairs-omits-all-keys": (
        "inner", [("l_id", "r_id"), ("l_k", "r_k")], ["l_s", "r_s"]),
    "two-pairs-keeps-second-key": (
        "inner", [("l_id", "r_id"), ("l_k", "r_k")], ["r_k", "l_s"]),
    "left-two-pairs": (
        "left", [("l_id", "r_id"), ("l_k", "r_k")], ["l_s", "r_s"]),
    "cross": ("cross", [], ["l_s", "r_k"]),
}


@pytest.mark.parametrize("case", list(JOIN_KEEP_CASES))
@pytest.mark.parametrize("backend", list(_keep_factories()))
def test_join_keep_returns_exactly_those_columns(backend, case):
    how, pairs, keep = JOIN_KEEP_CASES[case]
    left, right = _keep_tables(_keep_factories()[backend]())
    full = left.join(right, how, pairs)
    pruned = left.join(right, how, pairs, keep=keep)
    assert set(pruned.columns) == set(keep)
    assert len(pruned.columns) == len(keep)
    assert pruned.size == full.size
    assert _projected(pruned, keep) == _projected(full, keep)


FILTER_KEEP_CASES = {
    "omits-the-operand": ["l_s"],
    "keeps-the-operand": ["l_k", "l_id"],
    "one-column": ["l_id"],
}


@pytest.mark.parametrize("case", list(FILTER_KEEP_CASES))
@pytest.mark.parametrize("backend", list(_keep_factories()))
def test_filter_keep_returns_exactly_those_columns(backend, case):
    from caps_tpu.ir import exprs as E
    from caps_tpu.okapi.types import CTInteger, CTString
    from caps_tpu.relational.header import RecordHeader
    keep = FILTER_KEEP_CASES[case]
    left, _ = _keep_tables(_keep_factories()[backend]())
    header = RecordHeader([(E.Var("l_id"), "l_id", CTInteger),
                           (E.Var("l_k"), "l_k", CTInteger),
                           (E.Var("l_s"), "l_s", CTString)])
    pred = E.GreaterThan(E.Var("l_k"), E.Lit(10))
    full = left.filter(pred, header, {})
    pruned = left.filter(pred, header, {}, keep=keep)
    assert set(pruned.columns) == set(keep)
    assert pruned.size == full.size == 4
    assert _projected(pruned, keep) == _projected(full, keep)


def test_keep_counts_gathered_and_pruned_columns():
    """``backend.gathered_columns`` is every column handed to
    ``_gather_cols``; ``backend.pruned_columns`` what ``keep`` left out."""
    import caps_tpu.backends.tpu.table as T
    session = TPUCypherSession()
    left, right = _keep_tables(session.table_factory)
    seen = []
    orig = T._gather_cols

    def spy(cols, idx):
        seen.append(len(cols))
        return orig(cols, idx)

    T._gather_cols = spy
    try:
        left.join(right, "inner", [("l_id", "r_id")])
        assert session.backend.pruned_columns == 0
        assert session.backend.gathered_columns == sum(seen) == 6
        left.join(right, "inner", [("l_id", "r_id")], keep=["l_s", "r_k"])
        assert session.backend.gathered_columns == sum(seen) == 8
        assert session.backend.pruned_columns == 4
    finally:
        T._gather_cols = orig
    snap = session.metrics_snapshot()
    assert snap["backend.gathered_columns"] == 8
    assert snap["backend.pruned_columns"] == 4

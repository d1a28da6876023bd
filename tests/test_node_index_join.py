"""Joins against a node scan probe the id index an ingested node table
carries (``DeviceTableFactory.prepare_node_table``), and search where
the scan is a derived table without one.  The TPU backend (on the CPU
here) against the local oracle and against itself with ``use_csr`` off,
over node joins the benchmark's cells do not reach; and the two
counters that say which way a join went."""
from __future__ import annotations

import pytest

from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.okapi.config import EngineConfig
from caps_tpu.relational.updates import versioned
from caps_tpu.testing.factory import create_graph

# one person nobody knows and who knows nobody (Eve), one with no
# outgoing KNOWS (Dan): OPTIONAL MATCH leaves nulls.  One node table, or
# two label combinations and so two.
_GRAPH = ("CREATE (a:Person {name:'Alice'})-[:KNOWS]->(b:Person {name:'Bob'}),"
          " (b)-[:KNOWS]->(c:Person%s {name:'Carol'}), "
          "(c)-[:KNOWS]->(a), (a)-[:KNOWS]->(c), "
          "(c)-[:KNOWS]->(d:Person {name:'Dan'}), "
          "(e:Person%s {name:'Eve'})")
ONE_TABLE = _GRAPH % ("", "")
TWO_TABLES = _GRAPH % (":Admin", ":Admin")

_SESSIONS = {
    "local": LocalCypherSession,
    "tpu": TPUCypherSession,
    "tpu-no-csr": lambda: TPUCypherSession(
        config=EngineConfig(use_csr=False)),
}


def _rows(result):
    return sorted(result.records.to_maps(), key=repr)


def _probes(session, run):
    """``run()``'s rows and how its joins probed: (index, search)."""
    before = session.metrics_snapshot()
    rows = run()
    after = session.metrics_snapshot()
    return rows, tuple(after.get(k, 0) - before.get(k, 0) for k in (
        "backend.index_probes", "backend.search_probes"))


def _optional_null_left_key(session):
    # b is null for Dan and Eve: the second OPTIONAL MATCH's joins (on
    # b's id into KNOWS, on the target's id into the Person scan) have
    # null left keys, and keep those rows null-extended
    g = create_graph(session, ONE_TABLE)
    return g.cypher(
        "MATCH (a:Person) OPTIONAL MATCH (a)-[:KNOWS]->(b:Person) "
        "OPTIONAL MATCH (b)-[:KNOWS]->(c:Person) "
        "RETURN a.name AS a, b.name AS b, c.name AS c")


def _unlabelled_over_two_tables(session):
    # (c) scans the union of the Person and the Person:Admin table: new
    # columns, no index
    g = create_graph(session, TWO_TABLES)
    return g.cypher("MATCH (a:Person)-[:KNOWS]->(c) WHERE a.name = $n "
                    "RETURN c.name AS c, c:Admin AS admin", {"n": "Carol"})


def _deleted_through_the_overlay(session):
    vg = versioned(session, create_graph(session, ONE_TABLE))
    vg.cypher("MATCH (p:Person {name:'Dan'}) DETACH DELETE p")
    return vg.cypher("MATCH (a:Person)-[:KNOWS]->(b:Person) "
                     "RETURN a.name AS a, b.name AS b")


def _constructed_then_queried(session):
    g = create_graph(session, ONE_TABLE)
    out = g.cypher("MATCH (a:Person)-[:KNOWS]->(b:Person) "
                   "CONSTRUCT CLONE a, b NEW (b)-[:KNOWN_BY]->(a) "
                   "RETURN GRAPH").graph
    return out.cypher("MATCH (x:Person)-[:KNOWN_BY]->(y:Person) "
                      "RETURN x.name AS x, y.name AS y")


# scenario -> (what runs it, the TPU backend's (index, search) probes)
SCENARIOS = {
    # a rel and a node probe a hop, on the index; the two left joins that
    # put the optional rows back are against derived tables, and search
    "optional-match-null-left-key": (_optional_null_left_key, (4, 2)),
    # KNOWS by its index, the union of two node tables by search
    "unlabelled-over-two-tables": (_unlabelled_over_two_tables, (1, 1)),
    # both scans lost a row to a tombstone: new columns, no index
    "deleted-through-the-overlay": (_deleted_through_the_overlay, (0, 2)),
    # the CONSTRUCTed graph's tables are ingested like any other's
    "constructed-then-queried": (_constructed_then_queried, (4, 0)),
}


@pytest.fixture(scope="module")
def answers():
    """Every scenario's rows and probe counts from every session kind."""
    out = {}
    for name, (run, _) in SCENARIOS.items():
        for kind, make in _SESSIONS.items():
            session = make()
            out[name, kind] = _probes(session, lambda: _rows(run(session)))
            if kind != "local":
                assert session.fallback_count == 0, (name, kind)
    return out


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_node_join_parity_with_oracle_and_with_csr_off(answers, name):
    want, _ = answers[name, "local"]
    assert want, name
    assert answers[name, "tpu"][0] == want
    assert answers[name, "tpu-no-csr"][0] == want


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_node_join_probes_the_index_where_the_scan_carries_one(answers, name):
    """A scan of one ingested table finds the index on its ``Column``;
    whatever writes new columns has none, and the join searches."""
    index, search = SCENARIOS[name][1]
    assert answers[name, "tpu"][1] == (index, search)
    assert answers[name, "tpu-no-csr"][1] == (0, index + search)


def test_optional_match_rows_with_null_keys_survive(answers):
    rows, _ = answers["optional-match-null-left-key", "tpu"]
    assert {"a": "Eve", "b": None, "c": None} in rows
    assert {"a": "Dan", "b": None, "c": None} in rows
    assert {"a": "Bob", "b": "Carol", "c": "Alice"} in rows


@pytest.mark.parametrize("how", ["inner", "left"])
def test_table_join_against_a_node_scan_with_null_and_foreign_keys(how):
    """No plan of the engine LEFT-joins a node scan directly (OPTIONAL
    MATCH goes through a row id), so at the table: a null key, a key
    below 0 and one beyond the ids count 0 on the index as they do in
    the search, and a LEFT join keeps their rows null-extended."""
    from caps_tpu.okapi.types import CTInteger
    out = {}
    for kind in ("tpu", "tpu-no-csr"):
        session = _SESSIONS[kind]()
        (nt,) = create_graph(session, ONE_TABLE).node_tables
        scan = nt.table.select([nt.mapping.id_col, "name"]).rename(
            {nt.mapping.id_col: "c__id", "name": "c__name"})
        ids = sorted(scan.column_values("c__id"))
        left = session.table_factory.from_columns(
            {"k": [ids[2], None, -3, ids[-1] + 7, 2 ** 31 + ids[1], ids[0]],
             "row": [0, 1, 2, 3, 4, 5]},
            {"k": CTInteger.nullable, "row": CTInteger})
        joined, probes = _probes(session, lambda: left.join(
            scan, how, [("k", "c__id")]))
        assert probes == ((1, 0) if kind == "tpu" else (0, 1))
        out[kind] = sorted(
            (r["row"], r["k"], r["c__id"]) for r in joined.rows())
        assert session.fallback_count == 0
    matched = [(0, ids[2], ids[2]), (5, ids[0], ids[0])]
    unmatched = [(1, None, None), (2, -3, None), (3, ids[-1] + 7, None),
                 (4, 2 ** 31 + ids[1], None)]
    assert out["tpu"] == out["tpu-no-csr"] == sorted(
        matched + (unmatched if how == "left" else []),
        key=lambda r: r[0])


# -- the benchmark's own query ------------------------------------------------

@pytest.mark.parametrize("use_csr,query,want", [
    (True, "fof2", (4, 0)), (True, "fof3", (6, 0)),
    (False, "fof2", (0, 4)), (False, "fof3", (0, 6)),
])
def test_fof_read_counts_its_probes(fof, use_csr, query, want):
    """A ``fof`` read has one rel probe and one node probe a hop: all on
    the index, none searched; the other way round with ``use_csr`` off.
    Recorded, replayed and replayed again: the counts are a read's, not
    a first read's."""
    session = TPUCypherSession(config=EngineConfig(use_csr=use_csr))
    data = fof.make_data({"people": 300, "friends_per_person": 6}, 7)
    graph = fof.build_graph(session, data)
    for name in ("p17", "p17", "p203"):
        params = {"name": name}
        rows, probes = _probes(session, lambda: graph.cypher(
            fof.QUERIES[query], params).records.to_maps())
        assert rows == fof.reference(data, [query], params)[query]
        assert probes == want
    assert session.fallback_count == 0

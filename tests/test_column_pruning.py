"""Projection push-down (relational/live_columns.py): a join and a
filter gather only the columns the plan above them still reads.

Counts and sets, never times: (a) the ``keep`` of every join and filter
of ``fof3`` at the widest bucket, and the backend's two counters;
(b) row-for-row parity with the unpruned oracle on queries chosen to
break a careless pass, with a check on the sets beside the check on the
rows; (c) a plan served from the plan cache keeps its sets; (d) an
operator unknown to the pass keeps everything below it.
"""
import pytest

import caps_tpu.backends.tpu.table as T
from caps_tpu.backends.local.session import LocalCypherSession
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.frontend.parser import parse_query
from caps_tpu.ir import exprs as E
from caps_tpu.ir.builder import IRBuilder
from caps_tpu.relational import live_columns
from caps_tpu.relational import ops as R
from caps_tpu.relational.live_columns import Required, annotate_required
from caps_tpu.relational.plan_cache import PlanParams
from caps_tpu.relational.result_cache import ResultCache, ResultCacheConfig
from caps_tpu.testing.bag import Bag
from caps_tpu.testing.factory import create_graph


def _unpruned(monkeypatch):
    """Plans made from here on run as the parent commit ran them."""
    monkeypatch.setattr(live_columns, "annotate_required", lambda root: None)


def _ops(root):
    seen, stack, out = set(), [root], []
    while stack:
        op = stack.pop()
        if id(op) in seen:
            continue
        seen.add(id(op))
        out.append(op)
        stack.extend(reversed(op.children))
    return out


def _plan(session, graph, query, params=None):
    """The relational tree ``graph.cypher(query)`` would run."""
    params = dict(params or {})
    plan_params = PlanParams(params)
    ir = IRBuilder(graph.schema, session._schema_resolver,
                   plan_params).process(parse_query(query))
    return session._plan_ir(graph, ir, plan_params, params)[3]


# -- (a) fof3: the sets at the widest bucket, and the counters ---------------

@pytest.fixture(scope="module")
def fof_graph(fof):
    data = fof.make_data({"people": 2000, "friends_per_person": 50}, 7)
    session = TPUCypherSession()
    return session, fof.build_graph(session, data), data


def _spied_read(monkeypatch, session, graph, query, params):
    """One read's ``_gather_cols`` calls as (rows, sorted column names),
    its rows, and the two counters' deltas."""
    calls = []
    orig = T._gather_cols

    def spy(cols, idx):
        calls.append((int(idx.shape[0]), sorted(cols)))
        return orig(cols, idx)

    monkeypatch.setattr(T, "_gather_cols", spy)
    be = session.backend
    g0, p0 = be.gathered_columns, be.pruned_columns
    rows = graph.cypher(query, params).records.to_maps()
    monkeypatch.setattr(T, "_gather_cols", orig)
    return calls, rows, be.gathered_columns - g0, be.pruned_columns - p0


def test_fof3_gathers_only_live_columns_at_the_widest_bucket(
        monkeypatch, fof, fof_graph):
    session, graph, data = fof_graph
    params = {"name": "p3"}
    want = fof.reference(data, ["fof3"], params)["fof3"]
    calls, rows, gathered, pruned = _spied_read(
        monkeypatch, session, graph, fof.QUERIES["fof3"], params)
    assert rows == want
    widest = max(n for n, _ in calls)
    assert widest == 262144
    assert [cols for n, cols in calls if n == widest] == [
        # Join(__node3 = StartNode(__rel5)): left, then right
        ["__rel2__id", "__rel4__id"],
        ["__rel5__id", "__rel5__tgt"],
        # Join(EndNode(__rel5) = c): left, then right
        ["__rel2__id", "__rel4__id", "__rel5__id"],
        ["c__id"],
        # Filter(NOT id(__rel2) = id(__rel5)), Filter(NOT id(__rel4) = ...)
        ["__rel4__id", "__rel5__id", "c__id"],
        ["c__id"],
    ]
    assert gathered == sum(len(cols) for _, cols in calls)
    assert pruned > 0

    # the parent's figure: the same read with the pass switched off (the
    # alias differs so that the plan cache does not serve the pruned plan)
    _unpruned(monkeypatch)
    calls0, rows0, gathered0, pruned0 = _spied_read(
        monkeypatch, session, graph,
        fof.QUERIES["fof3"].replace("AS fof", "AS fof_unpruned"), params)
    assert rows0 == [{"fof_unpruned": want[0]["fof"]}]
    assert len(calls0) == len(calls)  # the same launches, narrower
    assert sum(len(c) for n, c in calls0 if n == widest) == 93
    assert pruned0 == 0
    assert gathered <= 0.4 * gathered0


def test_fof3_explain_prints_what_each_join_and_filter_keeps(fof, fof_graph):
    session, graph, _ = fof_graph
    plan = graph.cypher("EXPLAIN " + fof.QUERIES["fof3"],
                        {"name": "p3"}).plans["relational"]
    lines = [ln.strip() for ln in plan.splitlines()]
    assert lines[1].startswith("└─Filter(NOT Id() = Id() keeps=[c])")
    assert lines[2].startswith(
        "└─Filter(NOT Id() = Id() keeps=[__rel4, __rel5, c])")
    assert lines[3].startswith(
        "└─Join(inner: EndNode()=c keeps=[__rel2, __rel4, __rel5, c])")
    assert lines[4].startswith(
        "└─Join(inner: __node3=StartNode() "
        "keeps=[EndNode(__rel5), __rel2, __rel4, __rel5])")
    assert "keeps=" not in lines[0]  # Aggregate gathers nothing by keep
    assert all("keeps=" in ln for ln in lines
               if ln.startswith(("└─Join(", "└─Filter(")))


# -- (b) parity with the unpruned oracle, rows and sets ----------------------

SOCIAL = """
    CREATE (a:Person:Admin {name: 'Alice', age: 33}),
           (b:Person {name: 'Bob', age: 44}),
           (c:Person {name: 'Carol', age: 27, nick: 'C'}),
           (d:Person {name: 'Dana', age: 51}),
           (e:Robot {name: 'Eve', model: 7}),
           (a)-[:KNOWS {since: 2011}]->(b),
           (b)-[:KNOWS {since: 2015}]->(c),
           (a)-[:KNOWS {since: 2019}]->(c),
           (c)-[:KNOWS {since: 2021}]->(d),
           (c)-[:KNOWS {since: 2022}]->(a),
           (d)-[:LIKES {stars: 5}]->(e),
           (a)-[:LIKES {stars: 3}]->(e)
"""

PARITY_QUERIES = {
    "return-node":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 30 RETURN c",
    "return-node-and-rel":
        "MATCH (a:Person)-[r:KNOWS]->(c) WHERE a.name <> 'Bob' RETURN c, r",
    "labels":
        "MATCH (a)-[:KNOWS]->(c) WHERE a.age < 50 "
        "RETURN labels(c) AS l, a.name AS n",
    "properties":
        "MATCH (a)-[:KNOWS]->(c) WHERE a.age < 50 RETURN properties(c) AS p",
    "keys":
        "MATCH (a)-[r:KNOWS]->(c) WHERE a.age < 50 "
        "RETURN keys(c) AS k, keys(r) AS kr",
    "collect-node":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE c.age > 20 "
        "RETURN a.name AS n, collect(c) AS cs",
    "with-star":
        "MATCH (a:Person)-[r:KNOWS]->(c) WHERE r.since > 2012 WITH * "
        "WHERE c.age > 20 RETURN a.name AS a, c, r.since AS s",
    "return-distinct":
        "MATCH (a:Person)-[:KNOWS]->()-[:KNOWS]->(c) WHERE a.age > 20 "
        "RETURN DISTINCT c.name AS n",
    "order-by-unreturned-property":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 20 "
        "RETURN c.name AS n ORDER BY c.age DESC, a.name",
    "optional-match":
        "MATCH (a:Person) WHERE a.age > 30 OPTIONAL MATCH (a)-[r:LIKES]->(e) "
        "RETURN a.name AS n, e, r.stars AS s",
    "exists-subquery":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE EXISTS { (c)-[:LIKES]->() } "
        "RETURN a.name AS a, c.name AS c",
    "union":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 40 RETURN c.name AS n "
        "UNION MATCH (a)-[:LIKES]->(c) WHERE a.age > 40 RETURN c.name AS n",
    "undirected":
        "MATCH (a:Person)-[r:KNOWS]-(c) WHERE a.name = 'Carol' "
        "RETURN c.name AS n, r.since AS s",
    "into-expand-two-pairs":
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c), (a)-[r:KNOWS]->(c) "
        "WHERE a.age > 20 RETURN a.name AS a, c.name AS c, r.since AS s",
    "var-length":
        "MATCH (a:Person)-[rs:KNOWS*1..3]->(c) WHERE a.name = 'Alice' "
        "RETURN c.name AS n, size(rs) AS hops",
    "count-star-all-columns-dead":
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WHERE a.age > 20 "
        "RETURN count(*) AS n",
    "count-distinct-three-hops":
        "MATCH (a:Person)-[:KNOWS]->()-[:KNOWS]->()-[:KNOWS]->(c) "
        "WHERE a.name = 'Alice' RETURN count(DISTINCT c) AS n",
    "has-label-and-type":
        "MATCH (a)-[r]->(c) WHERE c:Person AND type(r) = 'KNOWS' "
        "AND a.age > 20 RETURN a.name AS a, c:Admin AS admin",
    "group-by-entity":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE c.age > 20 "
        "RETURN a, count(c) AS n",
    "alias-then-read":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 20 WITH c AS x, a "
        "WHERE x.age > 25 RETURN x.name AS n, labels(a) AS l",
    "start-and-end-node":
        "MATCH (a:Person)-[r:KNOWS]->(c) WHERE c.age > 20 "
        "RETURN startNode(r).name AS s, endNode(r).name AS e",
    "named-path":
        "MATCH p = (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WHERE a.age > 30 "
        "RETURN length(p) AS len, nodes(p) AS ns, c.name AS c",
    "is-null-and-entity-equality":
        "MATCH (a:Person)-[:KNOWS]->(b), (c:Person)-[:KNOWS]->(d) "
        "WHERE a = d AND b.nick IS NULL RETURN a.name AS a, c.name AS c",
    "list-comprehension-over-collected":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 20 "
        "WITH a, collect(c) AS cs RETURN a.name AS n, "
        "[x IN cs WHERE x.age > 30 | x.name] AS older",
    "construct":
        "MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 40 "
        "CONSTRUCT CREATE (a)-[:FRIEND]->(c) "
        "MATCH (x)-[:FRIEND]->(y) RETURN x.name AS x, y.name AS y",
}

_SESSIONS = {"local": LocalCypherSession, "tpu": TPUCypherSession}


@pytest.fixture(scope="module")
def oracle():
    """The rows of every parity query from the local backend with the
    pass switched off: what the parent commit answers."""
    orig = live_columns.annotate_required
    live_columns.annotate_required = lambda root: None
    try:
        graph = create_graph(LocalCypherSession(), SOCIAL)
        return {name: _rows(graph, q) for name, q in PARITY_QUERIES.items()}
    finally:
        live_columns.annotate_required = orig


@pytest.fixture(scope="module")
def social():
    out = {}
    for name, make in _SESSIONS.items():
        session = make()
        out[name] = (session, create_graph(session, SOCIAL))
    return out


def _rows(graph, query):
    return graph.cypher(query).records.to_maps()


def _ordered(query):
    return "ORDER BY" in query


@pytest.mark.parametrize("backend", list(_SESSIONS))
@pytest.mark.parametrize("name", list(PARITY_QUERIES))
def test_pruned_plan_answers_as_the_unpruned_oracle(social, oracle, backend,
                                                    name):
    _, graph = social[backend]
    query = PARITY_QUERIES[name]
    got = _rows(graph, query)
    if _ordered(query):
        assert got == oracle[name]
    else:
        assert Bag(got) == Bag(oracle[name]), Bag(oracle[name]).diff(Bag(got))


def _own_exprs(op):
    """(child index, expression) for every expression ``op`` evaluates."""
    if isinstance(op, R.FilterOp):
        return [(0, op.predicate)]
    if isinstance(op, R.ProjectOp):
        return [(0, e) for _, e, _ in op.items]
    if isinstance(op, R.AggregateOp):
        return [(0, e) for _, e, _ in op.group + op.aggregations]
    if isinstance(op, R.OrderByOp):
        return [(0, e) for e, _ in op.items]
    if isinstance(op, R.UnwindOp):
        return [(0, op.list_expr)]
    if isinstance(op, R.JoinOp):
        return [(i, p[i]) for p in op.pairs for i in (0, 1)]
    return []


def _reads(expr, header):
    """The header entries ``expr`` resolves to against ``header``: every
    sub-expression that is an entry, and for the nodes that scan the
    header (labels(), keys(), properties(), a bare entity) all a var
    owns."""
    resolved = R.resolve_expr(expr, header)
    out = set()
    for n in resolved.walk():
        if not isinstance(n, E.Expr):
            continue
        if header.has(n):
            out.add(n)
        if isinstance(n, (E.Labels, E.Keys, E.Properties)):
            for c in n.children:
                if isinstance(c, E.Var):
                    out.update(header.exprs_for(c.name))
    return out


@pytest.mark.parametrize("name", list(PARITY_QUERIES))
def test_every_operator_still_finds_what_it_resolves(monkeypatch, social,
                                                     name):
    """A dropped column is silent (``resolve_expr`` turns the read into
    null or false), so check the sets beside the rows: each header entry
    an operator's own expressions resolve to on the unpruned plan is
    there on the pruned one, for every operator that ran."""
    session, graph = social["local"]
    query = PARITY_QUERIES[name]
    pruned = _plan(session, graph, query)
    _unpruned(monkeypatch)
    full = _plan(session, graph, query)
    ops_p, ops_f = _ops(pruned), _ops(full)
    assert [type(o) for o in ops_p] == [type(o) for o in ops_f]
    assert all(o.required is None for o in ops_f)
    pruned.result, full.result
    checked = 0
    for op_p, op_f in zip(ops_p, ops_f):
        if op_p._result is None or op_f._result is None:
            continue
        for i, expr in _own_exprs(op_f):
            child_p, child_f = op_p.children[i], op_f.children[i]
            if child_p._result is None or child_f._result is None:
                continue
            want = _reads(expr, child_f.header)
            missing = [e for e in want if not child_p.header.has(e)]
            assert not missing, (type(op_f).__name__, expr, missing)
            checked += len(want)
        if isinstance(op_f, R.SelectOp):
            want = set(op_f.header.exprs)
            assert want == set(op_p.header.exprs)
            checked += len(want)
    assert checked > 0


def test_result_cache_keys_a_filter_prefix_by_what_it_keeps():
    """Two plan families share a scan->filter prefix only when the
    filter writes the same columns."""
    session = LocalCypherSession()
    session.result_cache = ResultCache(ResultCacheConfig(),
                                       registry=session.metrics_registry)
    graph = create_graph(session, SOCIAL)
    assert graph.cypher("MATCH (p:Person) WHERE p.age > 30 "
                        "RETURN count(*) AS c").records.to_maps() == [{"c": 3}]
    got = graph.cypher("MATCH (p:Person) WHERE p.age > 30 "
                       "RETURN p.name AS n ORDER BY n").records.to_maps()
    assert got == [{"n": "Alice"}, {"n": "Bob"}, {"n": "Dana"}]


# -- (c) the plan cache keeps the sets ---------------------------------------

def test_a_cached_plan_keeps_its_sets(monkeypatch):
    session = TPUCypherSession()
    graph = create_graph(session, SOCIAL)
    query = ("MATCH (a:Person)-[:KNOWS]->()-[:KNOWS]->(c) "
             "WHERE a.name = $name RETURN count(DISTINCT c) AS n")
    want = {"Alice": 3, "Bob": 2, "Carol": 2}
    calls = []
    orig = T._gather_cols

    def spy(cols, idx):
        calls.append(sorted(cols))
        return orig(cols, idx)

    monkeypatch.setattr(T, "_gather_cols", spy)
    per_read = []
    for name in ("Alice", "Bob", "Carol", "Alice"):
        calls.clear()
        result = graph.cypher(query, {"name": name})
        assert result.records.to_maps() == [{"n": want[name]}]
        per_read.append((result.metrics["plan_cache"], list(calls)))
    assert [hit for hit, _ in per_read] == ["miss", "hit", "hit", "hit"]
    first = per_read[0][1]
    assert all(calls == first for _, calls in per_read[1:])
    widest = max(len(c) for c in first)
    assert widest <= 4  # the parent gathers up to 18 columns here
    assert session.backend.pruned_columns > 0


# -- (d) what the pass does not model keeps everything -----------------------

class _UnknownOp(R.RelationalOperator):
    def __init__(self, context, parent):
        super().__init__(context, [parent])

    def _compute(self):
        return self.children[0].result


def _chain(session, graph):
    """Filter -> Join(Scan a, Scan r) under a fresh context."""
    context = R.RelationalRuntimeContext(session, {})
    from caps_tpu.okapi.types import CTNode, CTRelationship
    a = R.ScanOp(context, graph, "a", CTNode(["Person"]))
    r = R.ScanOp(context, graph, "r", CTRelationship(["KNOWS"]))
    join = R.JoinOp(context, a, r, [(E.Var("a"), E.StartNode(E.Var("r")))])
    pred = E.GreaterThan(E.Property(E.Var("a"), "age"), E.Lit(30))
    return context, join, R.FilterOp(context, join, pred)


def test_an_operator_unknown_to_the_pass_keeps_everything_below_it(social):
    session, graph = social["local"]
    context, join, filt = _chain(session, graph)
    root = R.SelectOp(context, _UnknownOp(context, filt), ["a"])
    annotate_required(root)
    assert root.required is None
    assert root.children[0].required == Required(owners=["a"])
    assert filt.required is None and join.required is None
    assert all(s.required is None for s in join.children)
    assert set(root.header.exprs) == set(
        join.children[0].header.exprs)  # a's id, labels, properties


def test_a_shared_subtree_takes_the_union_of_its_parents_demands(social):
    session, graph = social["local"]
    context, join, filt = _chain(session, graph)
    ids = R.AggregateOp(context, filt, [], [
        ("n", E.Count(E.Var("r")), None)])
    names = R.SelectOp(context, filt, ["a"])
    root = R.CrossOp(context, ids, names)
    annotate_required(root)
    assert filt.required == Required([E.Var("r")], owners=["a"])
    assert join.required == Required(
        [E.Var("r"), E.Property(E.Var("a"), "age")], owners=["a"])
    # ... and None from either parent wins
    context, join, filt = _chain(session, graph)
    root = R.CrossOp(context, R.SelectOp(context, filt, ["a"]),
                     _UnknownOp(context, filt))
    annotate_required(root)
    assert filt.required is None and join.required is None


def test_the_pass_never_fails_a_query(monkeypatch, social):
    session, graph = social["local"]

    def boom(op, req):
        raise RuntimeError("a bug in the pass")

    monkeypatch.setattr(live_columns, "_child_demands", boom)
    query = ("MATCH (a:Person)-[:KNOWS]->(c) WHERE a.age > 41 "
             "RETURN c.name AS pass_failed")
    assert all(op.required is None for op in _ops(_plan(session, graph,
                                                        query)))
    assert graph.cypher(query).records.to_maps() == [{"pass_failed": "Carol"}]


READS_CASES = {
    "id": (E.Id(E.Var("c")), {E.Var("c")}, set()),
    "count": (E.Count(E.Var("c"), True), {E.Var("c")}, set()),
    "is-null": (E.IsNull(E.Var("c")), {E.Var("c")}, set()),
    "entity-equality": (E.Not(E.Equals(E.Var("a"), E.Var("b"))),
                        {E.Var("a"), E.Var("b")}, set()),
    "property": (E.Property(E.Var("a"), "name"),
                 {E.Var("a"), E.Property(E.Var("a"), "name")}, set()),
    "has-type-reads-type": (E.HasType(E.Var("r"), "KNOWS"),
                            {E.Var("r"), E.Type(E.Var("r"))}, set()),
    "labels": (E.Labels(E.Var("c")), set(), {"c"}),
    "properties": (E.Properties(E.Var("c")), set(), {"c"}),
    "keys": (E.Keys(E.Var("c")), set(), {"c"}),
    "collect": (E.Collect(E.Var("c")), set(), {"c"}),
    "bare": (E.Var("c"), set(), {"c"}),
    "function-argument": (E.FunctionExpr("toString", (E.Var("c"),)),
                          set(), {"c"}),
    "equality-with-a-value": (E.Equals(E.Var("c"), E.Lit(1)), set(), {"c"}),
    "owner-covers-its-entries": (
        E.Ands((E.Equals(E.Property(E.Var("c"), "k"), E.Lit(1)),
                E.IsNotNull(E.FunctionExpr("f", (E.Var("c"),))))),
        set(), {"c"}),
}


@pytest.mark.parametrize("case", list(READS_CASES))
def test_what_an_expression_reads(case):
    expr, exprs, owners = READS_CASES[case]
    got = Required().reading([expr])
    assert got.exprs == frozenset(exprs)
    assert got.owners == frozenset(owners)

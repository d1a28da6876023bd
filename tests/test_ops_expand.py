"""Differential tests for the expand-positions Pallas kernel and the
HBM-resident CSR adjacency (ops/expand.py) — the pattern of
tests/test_ops_pallas.py: every kernel result must equal its jnp twin
exactly, and the engine must produce identical results with the fast
paths on and off (SURVEY.md §7 step 6)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from caps_tpu.ops.expand import (
    DeviceCSR, build_csr, csr_probe, expand_positions, expand_positions_ref,
    join_expand_via_positions,
)
from caps_tpu.backends.tpu import kernels as K
from caps_tpu.backends.tpu.session import TPUCypherSession
from caps_tpu.okapi.config import EngineConfig
from tests.util import make_graph


def _random_case(rng, cap_l, max_count, zero_frac):
    counts = rng.randint(0, max_count + 1, cap_l)
    counts = np.where(rng.rand(cap_l) < zero_frac, 0, counts)
    lo = rng.randint(0, 1 << 20, cap_l)
    return counts, lo


@pytest.mark.parametrize("cap_l,max_count,zero_frac", [
    (256, 4, 0.0),
    (256, 4, 0.9),
    (1024, 7, 0.5),
    (4096, 3, 0.97),
    (1024, 0, 1.0),      # fully empty
    (256, 1, 0.0),       # degree exactly 1 everywhere
])
def test_expand_positions_matches_twin(cap_l, max_count, zero_frac):
    rng = np.random.RandomState(cap_l + max_count)
    counts, lo = _random_case(rng, cap_l, max_count, zero_frac)
    total = int(counts.sum())
    out_cap = max(256, 1 << (max(1, total) - 1).bit_length())
    got = expand_positions(jnp.asarray(counts), jnp.asarray(lo), out_cap,
                           interpret=True)
    want = expand_positions_ref(jnp.asarray(counts), jnp.asarray(lo), out_cap)
    for g, w, name in zip(got, want, ("l_idx", "r_pos", "valid")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


def test_expand_positions_heavy_skew():
    """One hub row holding almost all the output (the power-law case)."""
    cap_l = 1024
    counts = np.zeros(cap_l, np.int64)
    counts[7] = 2000
    counts[900] = 48
    lo = np.arange(cap_l)
    got = expand_positions(jnp.asarray(counts), jnp.asarray(lo), 2048,
                           interpret=True)
    want = expand_positions_ref(jnp.asarray(counts), jnp.asarray(lo), 2048)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_expand_positions_sparse_rows_with_zero_ends():
    """A tileable ``cap_l`` whose rows are mostly empty, the first and
    last among them: the prelude compacts the live rows by one key sort
    (ops/compact.py), and neither it nor the program scatters."""
    cap_l = 4096
    rng = np.random.RandomState(38)
    counts = np.where(rng.rand(cap_l) < 0.05, rng.randint(1, 9, cap_l), 0)
    counts[:3] = 0
    counts[-5:] = 0
    lo = rng.randint(0, 1 << 20, cap_l)
    total = int(counts.sum())
    out_cap = 1 << (total - 1).bit_length()
    args = (jnp.asarray(counts), jnp.asarray(lo))
    got = expand_positions(*args, out_cap, interpret=True)
    want = expand_positions_ref(*args, out_cap)
    for g, w, name in zip(got, want, ("l_idx", "r_pos", "valid")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    lowered = expand_positions.lower(*args, out_cap=out_cap,
                                     interpret=True).as_text()
    assert "scatter" not in lowered


@pytest.mark.parametrize("kept", [[], [0], [4095], [0, 5, 4095],
                                  list(range(4096))])
def test_kept_first_carries_payload(kept):
    """The prelude's one sort: kept rows' indices first and in order,
    every dropped row's key at or past n, each payload beside its key."""
    from caps_tpu.ops.compact import kept_first
    n = 4096
    rows = np.zeros(n, bool)
    rows[kept] = True
    vals = np.arange(n, dtype=np.int32) * 7 + 3
    key, payload = kept_first(jnp.asarray(rows), jnp.asarray(vals))
    key, payload = np.asarray(key), np.asarray(payload)
    k = len(kept)
    assert np.array_equal(key[:k], np.flatnonzero(rows))
    assert (key[k:] >= n).all()
    assert np.array_equal(payload, vals[np.where(key < n, key, key - n)])


def test_join_expand_via_positions_matches_join_expand():
    rng = np.random.RandomState(3)
    cap_l, cap_r = 512, 1024
    n_r = 700
    r_key = rng.randint(0, 50, cap_r)
    r_ok = K.row_mask(cap_r, n_r)
    rk_sorted, perm = K.sort_right(jnp.asarray(r_key), r_ok)
    l_key = rng.randint(0, 60, cap_l)
    l_ok = jnp.asarray(rng.rand(cap_l) < 0.8)
    counts, lo = K.probe_count(jnp.asarray(l_key), l_ok, rk_sorted)
    for left_join in (False, True):
        total = int(K.join_total(counts, l_ok, left_join))
        out_cap = max(256, 1 << (max(1, total) - 1).bit_length())
        li1, ri1, v1, m1 = join_expand_via_positions(
            counts, lo, perm, l_ok, out_cap, left_join, interpret=True)
        li2, ri2, v2, m2, _ = K.join_expand(counts, lo, perm, l_ok,
                                            out_cap, left_join)
        assert np.array_equal(np.asarray(v1), np.asarray(v2))
        assert np.array_equal(np.asarray(m1), np.asarray(m2))
        v = np.asarray(v1)
        assert np.array_equal(np.asarray(li1)[v], np.asarray(li2)[v])
        m = np.asarray(m1)
        assert np.array_equal(np.asarray(ri1)[m], np.asarray(ri2)[m])


def test_build_csr_native_and_numpy_agree():
    rng = np.random.RandomState(11)
    cap, n = 2048, 1500
    keys = np.zeros(cap, np.int64)
    keys[:n] = rng.randint(0, 300, n)
    ok = np.zeros(cap, bool)
    ok[:n] = rng.rand(n) < 0.85
    a = build_csr(jnp.asarray(keys), jnp.asarray(ok), n, use_native=True)
    b = build_csr(jnp.asarray(keys), jnp.asarray(ok), n, use_native=False)
    assert np.array_equal(np.asarray(a.indptr), np.asarray(b.indptr))
    # perms may order rows within a key differently across builders; the
    # row *sets* per key must match
    ia, pa = np.asarray(a.indptr), np.asarray(a.perm)
    ib, pb = np.asarray(b.indptr), np.asarray(b.perm)
    for k in range(a.n_keys):
        assert set(pa[ia[k]:ia[k + 1]]) == set(pb[ib[k]:ib[k + 1]]), k


def test_build_csr_rejects_sparse_domain():
    keys = jnp.asarray(np.array([0, 5, 10**7], np.int64))
    ok = jnp.ones(3, bool)
    assert build_csr(keys, ok, 3) is None


def test_csr_probe_int64_keys_out_of_range():
    csr = DeviceCSR(jnp.asarray(np.array([0, 1, 2], np.int32)),
                    jnp.asarray(np.array([0, 1], np.int32)), 2)
    keys = jnp.asarray(np.array([0, 1, 2, -1, 2**40], np.int64))
    ok = jnp.ones(5, bool)
    counts, lo = csr.probe(keys, ok)
    assert list(np.asarray(counts)) == [1, 1, 0, 0, 0]


# right keys (the build side, all inside build_csr's domain rule), and
# left keys that hit, miss, repeat, and leave the domain on either end
_RIGHT_KEYS = {
    "unique": lambda rng: rng.permutation(700),
    "repeated": lambda rng: rng.randint(0, 50, 700),
    "unique-with-gaps": lambda rng: rng.permutation(2000)[:700],
}
def _half(rng, cap, other):
    """Half the keys among the first 50 ids (every right side has
    them), half from ``other``."""
    return np.where(rng.rand(cap) < 0.5, rng.randint(0, 50, cap),
                    np.asarray(other, np.int64))


_LEFT_KEYS = {
    "in-domain": lambda rng, cap: _half(rng, cap, rng.randint(0, 700, cap)),
    "below-zero": lambda rng, cap: _half(rng, cap, rng.randint(-40, 0, cap)),
    "beyond-domain": lambda rng, cap: _half(
        rng, cap, rng.randint(600, 5000, cap)),
    "beyond-2^31": lambda rng, cap: _half(
        rng, cap, 2 ** 31 + rng.randint(0, 700, cap).astype(np.int64)),
    # wraps to an in-domain key if the range check truncated to int32
    "2^32-aliases": lambda rng, cap: _half(
        rng, cap, 2 ** 32 + rng.randint(0, 700, cap).astype(np.int64)),
}


@pytest.mark.parametrize("right", sorted(_RIGHT_KEYS))
@pytest.mark.parametrize("left", sorted(_LEFT_KEYS))
@pytest.mark.parametrize("masked_rows,null_keys", [
    (False, False), (True, False), (False, True), (True, True)])
def test_csr_probe_matches_probe_count(right, left, masked_rows, null_keys):
    """The one-program index probe against the binary search it stands
    in for: same counts a left row, and ``perm[lo + j]`` names the same
    right rows."""
    rng = np.random.RandomState(len(right) * 31 + len(left))
    cap_l, cap_r, n_r = 512, 1024, 700
    r_key = np.zeros(cap_r, np.int64)
    r_key[:n_r] = _RIGHT_KEYS[right](rng)
    r_ok = np.zeros(cap_r, bool)
    r_ok[:n_r] = rng.rand(n_r) < 0.9 if masked_rows else True
    l_key = _LEFT_KEYS[left](rng, cap_l).astype(np.int64)
    l_ok = jnp.asarray(rng.rand(cap_l) < 0.8 if masked_rows
                       else np.ones(cap_l, bool))
    if null_keys:
        # what DeviceTable._masked_left_key hands both probes for a
        # null key of a live row
        l_key = np.where(rng.rand(cap_l) < 0.2, int(K._L_NULL), l_key)
    l_key = jnp.asarray(l_key)

    rk_sorted, perm_s = K.sort_right(jnp.asarray(r_key), jnp.asarray(r_ok))
    want_counts, want_lo = K.probe_count(l_key, l_ok, rk_sorted)
    csr = build_csr(jnp.asarray(r_key), jnp.asarray(r_ok), n_r)
    assert csr is not None and csr.indptr.shape[0] == csr.n_keys + 1
    got_counts, got_lo = csr_probe(csr.indptr, l_key, l_ok)
    assert got_counts.dtype == jnp.int32 and got_lo.dtype == jnp.int32
    for a, b in zip(csr.probe(l_key, l_ok), (got_counts, got_lo)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    got_counts, got_lo = np.asarray(got_counts), np.asarray(got_lo)
    want_counts, want_lo = np.asarray(want_counts), np.asarray(want_lo)
    assert np.array_equal(got_counts, want_counts)
    assert got_counts.sum() > 0
    perm_c, perm_s = np.asarray(csr.perm), np.asarray(perm_s)
    for i in np.nonzero(got_counts)[0]:
        got = perm_c[got_lo[i]:got_lo[i] + got_counts[i]]
        want = perm_s[want_lo[i]:want_lo[i] + want_counts[i]]
        assert sorted(got) == sorted(want), i
        assert r_ok[got].all() and (r_key[got] == int(l_key[i])).all()


def _social(session):
    return make_graph(
        session,
        {("Person",): [{"_id": i, "name": f"p{i}"} for i in range(30)]},
        {"KNOWS": [(i, (i * 7 + 3) % 30, {}) for i in range(30)]
                  + [(i, (i * 11 + 1) % 30, {}) for i in range(0, 30, 2)]},
    )


QUERIES = [
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN count(*) AS c",
    "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c",
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:KNOWS]->(b) "
    "RETURN a.name AS a, b.name AS b ORDER BY a, b",
    "MATCH (a:Person)-[:KNOWS]->(b) WHERE a.name = 'p3' "
    "RETURN b.name AS n ORDER BY n",
    "MATCH (a:Person)-[:KNOWS*1..3]->(b) WHERE a.name = 'p1' "
    "RETURN count(*) AS c",
    "MATCH (a:Person)<-[:KNOWS]-(b) WHERE a.name = 'p4' "
    "RETURN count(*) AS c",
]


@pytest.mark.parametrize("query", QUERIES)
def test_engine_parity_csr_on_off(query):
    on = TPUCypherSession(config=EngineConfig(use_csr=True))
    off = TPUCypherSession(config=EngineConfig(use_csr=False,
                                              use_pallas=False))
    got = _social(on).cypher(query).records.to_maps()
    want = _social(off).cypher(query).records.to_maps()
    assert got == want
    assert on.fallback_count == 0


def test_csr_attached_at_ingest():
    session = TPUCypherSession()
    g = _social(session)
    (rt,) = g.rel_tables
    src_col = rt.table._cols[rt.mapping.source_col]
    tgt_col = rt.table._cols[rt.mapping.target_col]
    assert getattr(src_col, "_csr", None) is not None
    assert getattr(tgt_col, "_csr", None) is not None
    assert src_col._csr[1] is not None  # suitable dense domain -> built


def _node_id_col(graph):
    (nt,) = graph.node_tables
    return nt.table, nt.table._cols[nt.mapping.id_col]


def test_node_index_attached_at_ingest():
    session = TPUCypherSession()
    t, id_col = _node_id_col(_social(session))
    shape, csr = id_col._csr
    assert shape == (t._n,) == (30,)
    assert csr is not None and csr.n_keys == 30
    # a node id names one row: the index is a permutation of the rows
    assert list(np.asarray(csr.indptr)) == list(range(31))
    assert sorted(np.asarray(csr.perm)[:30]) == list(range(30))
    # the scan keeps the Column, so the join finds the index on it
    scan = t.select(["_id"]).rename({"_id": "c__id"})
    assert scan._cols["c__id"] is id_col
    assert scan._csr_for(scan, id_col) is csr


def test_node_index_not_built_when_csr_is_off():
    session = TPUCypherSession(config=EngineConfig(use_csr=False))
    t, id_col = _node_id_col(_social(session))
    assert getattr(id_col, "_csr", None) is None
    assert t._csr_for(t, id_col) is None


@pytest.mark.parametrize("ids", [
    [i * 1000 for i in range(100)],           # sparse: 99,000 for 100 rows
    [-1] + list(range(1, 100)),               # one negative id
], ids=["spaced-1000-apart", "negative"])
def test_node_index_refused_for_unsuitable_ids(ids):
    session = TPUCypherSession()
    g = make_graph(
        session,
        {("Person",): [{"_id": i, "name": f"p{i}"} for i in ids]},
        {"KNOWS": [(ids[i], ids[(i * 7 + 3) % 100], {})
                   for i in range(100)]})
    t, id_col = _node_id_col(g)
    # looked at once, refused by build_csr's own rule: the join searches
    assert id_col._csr == ((100,), None)
    before = session.metrics_snapshot()
    rows = g.cypher("MATCH (a:Person)-[:KNOWS]->(b) WHERE a.name = $n "
                    "RETURN b.name AS b", {"n": f"p{ids[5]}"}
                    ).records.to_maps()
    assert rows == [{"b": f"p{ids[38]}"}]
    after = session.metrics_snapshot()
    assert after["backend.search_probes"] > before["backend.search_probes"]


def test_distinct_and_group_do_not_collide_large_int64():
    """Keys >= 2^53 are distinct in int64 but equal in float64 — the
    boundary detection must compare them in their own dtype (round-1
    VERDICT weak #6)."""
    session = TPUCypherSession()
    big = 2 ** 53
    g = make_graph(
        session,
        {("N",): [{"_id": 1, "v": big}, {"_id": 2, "v": big + 1},
                  {"_id": 3, "v": big}]},
        {},
    )
    rows = g.cypher("MATCH (n:N) RETURN DISTINCT n.v AS v ORDER BY v"
                    ).records.to_maps()
    assert rows == [{"v": big}, {"v": big + 1}]
    rows = g.cypher("MATCH (n:N) RETURN n.v AS v, count(*) AS c ORDER BY v"
                    ).records.to_maps()
    assert rows == [{"v": big, "c": 2}, {"v": big + 1, "c": 1}]
    assert session.fallback_count == 0


def test_build_csr_refuses_negative_keys():
    keys = jnp.asarray(np.array([3, -5, 7, 0], np.int64))
    ok = jnp.asarray(np.array([True, True, True, False]))
    assert build_csr(keys, ok, 4) is None
    # a negative key hidden behind ok=False must NOT block the build
    keys2 = jnp.asarray(np.array([3, -5, 7, 0], np.int64))
    ok2 = jnp.asarray(np.array([True, False, True, True]))
    csr = build_csr(keys2, ok2, 4)
    assert csr is not None
    # live keys {3, 7, 0}: cumulative counts over domain [0, 8)
    assert list(np.asarray(csr.indptr)) == [0, 1, 1, 1, 2, 2, 2, 2, 3]


def test_expand_positions_non_tileable_out_cap():
    counts = jnp.asarray(np.array([2, 0, 3], np.int64))
    lo = jnp.asarray(np.array([10, 0, 20], np.int64))
    got = expand_positions(counts, lo, 100, interpret=True)
    want = expand_positions_ref(counts, lo, 100)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))

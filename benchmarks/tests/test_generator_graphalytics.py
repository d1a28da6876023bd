"""The Graphalytics BFS deployment's data and reference: the graph is
graph500's shape (undirected, no self-loop, no repeated edge, no
isolated vertex), every seed searches the same graph relabelled (the
same work, other ids), the reference agrees with a queue over the
stored edges, and the control's copy answers differently."""
import collections

import numpy as np
import pytest

from generators import graphalytics_bfs as g

SIZES = {"scale": 9, "edgefactor": 16, "roots": 4}


def queue_levels(data, root):
    """{vertex id: hop distance} over the stored edges, both ways."""
    src, tgt = data.edges()
    nbrs = collections.defaultdict(list)
    for a, b in zip(src.tolist(), tgt.tolist()):
        nbrs[a].append(b)
        nbrs[b].append(a)
    dist = {root: 0}
    queue = collections.deque([root])
    while queue:
        v = queue.popleft()
        for w in nbrs[v]:
            if w not in dist:
                dist[w] = dist[v] + 1
                queue.append(w)
    return dist


def rows_of(dist):
    out = collections.defaultdict(lambda: [0, 0])
    for v, d in dist.items():
        out[d][0] += 1
        out[d][1] += v
    return [{"dist": d, "vertices": c, "ids": s}
            for d, (c, s) in sorted(out.items())]


def test_the_graph_has_graph500s_shape():
    s = g.structure(SIZES)
    assert (s.lo < s.hi).all()                            # no self-loop
    key = s.lo.astype(np.int64) * s.n + s.hi
    assert np.unique(key).size == key.size                # no repeat
    deg = np.diff(s.indptr)
    assert (deg > 0).all() and deg.sum() == 2 * s.lo.size  # no isolate
    dist = g.bfs_levels(s.indptr, s.nbr, int(s.roots[0]))
    assert s.component == int((dist >= 0).sum())
    assert (dist[s.roots] >= 0).all()                     # one component


@pytest.mark.parametrize("seed", [5, 2 ** 40 + 3])
def test_the_reference_equals_a_queue(seed):
    data = g.make_data(SIZES, seed)
    for params in g.bindings(data, {"kind": "roots", "count": 4}, None):
        want = rows_of(queue_levels(data, params["root"]))
        assert g.reference(data, ["bfs"], params) == {"bfs": want}
    assert g.reference(data, ["bfs"], {"root": -1}) == {"bfs": []}


def test_seeds_do_the_same_work_on_other_ids():
    a = g.make_data(SIZES, 2 ** 33 + 1)
    b = g.make_data(SIZES, 2 ** 33 + 2)
    ra = g.bindings(a, {"kind": "roots", "count": 4}, None)
    rb = g.bindings(b, {"kind": "roots", "count": 4}, None)
    assert ra != rb
    for pa, pb in zip(ra, rb):
        wa = g.reference(a, ["bfs"], pa)["bfs"]
        wb = g.reference(b, ["bfs"], pb)["bfs"]
        assert [r["vertices"] for r in wa] == [r["vertices"] for r in wb]
        assert [r["ids"] for r in wa] != [r["ids"] for r in wb]
    same = g.make_data(SIZES, 2 ** 33 + 1)
    assert (same.label == a.label).all() and (same.order == a.order).all()


def test_the_stale_copy_answers_differently():
    data = g.make_data(SIZES, 11)
    stale = g.stale_copy(data, 0.01)
    assert stale.edges()[0].size == data.edges()[0].size \
        - data.order.size // 100
    params = g.bindings(data, {"kind": "roots", "count": 4}, None)
    assert any(g.reference(data, ["bfs"], p) != g.reference(stale, ["bfs"], p)
               for p in params)

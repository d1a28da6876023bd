"""LDBC Graphalytics BFS on graph500-22: data from a seed, the served
query text and its numpy reference.

Graphalytics (Iosup et al., VLDB 2016; specification v1.0) runs its BFS
job on the dataset graph500-22: the Graph500 Kronecker generator at
scale 22 and edge factor 16 (initiator A = 0.57, B = C = 0.19,
D = 0.05), stored undirected with no self-loops, duplicate edges or
isolated vertices.  Its output is every reached vertex's hop distance
from one source.

The Kronecker structure comes from one fixed generator seed
(``STRUCTURE_SEED``): it stands for the one graph500-22 file, and so are
the roots, drawn inside its largest component among the top hub's
neighbours of degree 1, so that every root's search reads the same rows.  ``--seed`` draws what
Graph500's own generator draws per run, the vertex labels and the order
of the edges: every seed searches the same component from the same
roots (the same work), and the ids in the answers differ.

One ``V`` table (``_id`` = the vertex label) and one ``E`` table holding
each undirected edge once, in the drawn order.  The answer of a search
is its level histogram with a checksum of the ids on each level: a few
rows, exact, in a total order.

It imports nothing of the program except, inside :func:`build_graph`,
the public ingest API that any client of the engine uses.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: query name -> text; a traffic file names queries by these keys
QUERIES: Dict[str, str] = {
    "bfs": "CALL algo.bfs($root, -1, 'both') YIELD node, dist "
           "RETURN dist, count(*) AS vertices, sum(node) AS ids "
           "ORDER BY dist",
}

#: the one graph500-22 "file": the Kronecker draws and the roots
STRUCTURE_SEED = 22_16_2016
#: Graph500's initiator (A, B, C; D = 1 - A - B - C)
INITIATOR = (0.57, 0.19, 0.19)
#: the generator's draws come in this many parts, each of its own seed
_PARTS = 64
#: vertices whose rows a reference search reads at once
_BLOCK_ROWS = 1 << 18


@dataclasses.dataclass(frozen=True)
class Structure:
    """The deduplicated undirected Kronecker graph in its generator's
    labels, ``0..n-1`` (isolated vertices dropped), one row a vertex in
    ``indptr``/``nbr`` (both directions of every edge)."""
    n: int
    lo: np.ndarray          # int32 [m], lo < hi, sorted by (lo, hi)
    hi: np.ndarray          # int32 [m]
    indptr: np.ndarray      # int64 [n + 1]
    nbr: np.ndarray         # int32 [2m]
    roots: np.ndarray       # int64 [roots], in the largest component
    component: int          # vertices of the largest component
    component_edges: int    # its edges


@dataclasses.dataclass
class Data:
    structure: Structure
    label: np.ndarray       # int64 [n]: the vertex's id in the graph
    order: np.ndarray       # int64 [m]: structure edge of stored edge i
    #: which stored edges exist; None = all (only the control drops some)
    alive: Optional[np.ndarray] = None

    @property
    def n_vertices(self) -> int:
        return self.structure.n

    def edges(self) -> Tuple[np.ndarray, np.ndarray]:
        """(src, tgt) ids of the stored edges, in stored order."""
        s = self.structure
        keep = self.order if self.alive is None else self.order[self.alive]
        return self.label[s.lo[keep]], self.label[s.hi[keep]]


def _kronecker_part(seq, m: int, scale: int) -> Tuple[np.ndarray, np.ndarray]:
    """``m`` of Graph500's draws: every level of (src, dst) from the 2x2
    initiator, one 64-bit draw a level and an edge (the low half decides
    the row bit, the high half the column bit given the row)."""
    a, b, c = INITIATOR
    t_row = np.uint32(round((a + b) * 2 ** 32))
    t_top = np.uint32(round(a / (a + b) * 2 ** 32))
    t_bottom = np.uint32(round(c / (1.0 - a - b) * 2 ** 32))
    bits = np.random.Generator(np.random.PCG64(seq)).bit_generator
    src = np.zeros(m, np.uint32)
    dst = np.zeros(m, np.uint32)
    for _level in range(scale):       # levels are alike: any bit order
        half = bits.random_raw(m).view(np.uint32).reshape(m, 2)
        row = half[:, 0] >= t_row
        col = half[:, 1] >= np.where(row, t_bottom, t_top)
        src <<= 1
        src |= row
        dst <<= 1
        dst |= col
    return src, dst


def _threads() -> int:
    return min(_PARTS, os.cpu_count() or 1)


def _kronecker(scale: int, edgefactor: int) -> Tuple[np.ndarray, np.ndarray]:
    """Graph500's generator: ``edgefactor * 2**scale`` directed edges, in
    ``_PARTS`` parts of their own seeds (the same edges however many
    threads draw them)."""
    m = edgefactor << scale
    seqs = np.random.SeedSequence(STRUCTURE_SEED).spawn(_PARTS)
    cuts = np.linspace(0, m, _PARTS + 1).astype(np.int64)
    with ThreadPoolExecutor(_threads()) as pool:
        parts = list(pool.map(
            lambda i: _kronecker_part(seqs[i], int(cuts[i + 1] - cuts[i]),
                                      scale), range(_PARTS)))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def _ranges(values: np.ndarray, parts: int) -> List[Tuple[int, int]]:
    """``parts`` value ranges ``[a, b)`` that split ``values`` about
    evenly (bounds from a sample; the last range is open above)."""
    sample = np.sort(values[::max(1, values.size // 65536)])
    cut = sample[(np.arange(1, parts) * sample.size) // parts]
    bounds = [int(values.min())] + [int(c) for c in cut] \
        + [int(values.max()) + 1]
    return [(a, b) for a, b in zip(bounds, bounds[1:]) if a < b]


def _undirected(scale: int, src: np.ndarray, dst: np.ndarray
                ) -> Tuple[int, np.ndarray, np.ndarray]:
    """Self-loops and duplicates dropped, each edge once as (lo, hi),
    isolated vertices dropped and the rest numbered densely."""
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key = (lo.astype(np.int64) << scale) | hi
    key = np.unique(key[lo != hi])          # sorted by (lo, hi)
    lo, hi = key >> scale, key & ((1 << scale) - 1)
    present = np.zeros(1 << scale, bool)
    present[lo] = True
    present[hi] = True
    dense = (np.cumsum(present) - 1).astype(np.int32)
    return int(present.sum()), dense[lo], dense[hi]


def _csr(n: int, lo: np.ndarray, hi: np.ndarray
         ) -> Tuple[np.ndarray, np.ndarray]:
    """One row a vertex, both directions of every (lo, hi).  ``lo`` comes
    sorted, so the lo -> hi halves are in place already; the hi -> lo
    halves go in by a sort of ``hi``, range by range (a row's order is
    no matter)."""
    m = lo.shape[0]
    deg_lo = np.bincount(lo, minlength=n)
    deg_hi = np.bincount(hi, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg_lo + deg_hi, out=indptr[1:])
    nbr = np.empty(2 * m, np.int32)
    first_lo = np.zeros(n, np.int64)
    np.cumsum(deg_lo[:-1], out=first_lo[1:])
    nbr[indptr[lo] + np.arange(m) - first_lo[lo]] = hi
    start = indptr[:-1] + deg_lo         # where a row's hi -> lo part starts

    def place(bounds):          # the rows of one range of hi
        a, b = bounds
        at = np.flatnonzero((hi >= a) & (hi < b))
        at = at[np.argsort(hi[at], kind="stable")]
        h = hi[at]
        first = np.searchsorted(h, h)
        nbr[start[h] + np.arange(h.size) - first] = lo[at]

    if m:
        with ThreadPoolExecutor(_threads()) as pool:
            list(pool.map(place, _ranges(hi, 4 * _threads())))
    return indptr, nbr


def _rows(indptr: np.ndarray, vertices: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Positions in ``nbr`` of the rows of ``vertices``, concatenated,
    and each row's length."""
    starts = indptr[vertices]
    counts = indptr[vertices + 1] - starts
    ends = np.cumsum(counts)
    return (np.repeat(starts - (ends - counts), counts)
            + np.arange(int(ends[-1]) if ends.size else 0)), counts


def _found(indptr: np.ndarray, nbr: np.ndarray, dist: np.ndarray,
           level: int, vertices: np.ndarray, down: bool) -> np.ndarray:
    """The vertices of the next level that the rows of ``vertices``
    show: top-down (``down``) the unseen vertices they name, bottom-up
    those of ``vertices`` whose row names a vertex at ``level``."""
    at, counts = _rows(indptr, vertices)
    if down:
        found = nbr[at]
        return found[dist[found] < 0]
    hit = np.zeros(at.size + 1, np.int64)
    np.cumsum(dist[nbr[at]] == level, out=hit[1:])
    ends = np.cumsum(counts)
    return vertices[hit[ends] > hit[ends - counts]]


def bfs_levels(indptr: np.ndarray, nbr: np.ndarray, root: int
               ) -> np.ndarray:
    """Hop distance of every vertex from ``root`` (-1: not reached), level
    by level from whichever side reads fewer rows: the frontier's rows
    (a vertex not seen yet that one of them names is on the next level),
    or the rows of the vertices not seen yet (one is on the next level
    if its row names a vertex of the frontier).  The rows are read
    ``_BLOCK_ROWS`` vertices at a time, so that a search holds a few
    hundred MB whatever the level's size."""
    n = indptr.shape[0] - 1
    degree = np.diff(indptr)
    dist = np.full(n, -1, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    unseen_rows = int(indptr[-1]) - int(degree[root])
    level = 0
    while frontier.size:
        down = int(degree[frontier].sum()) <= unseen_rows
        rows = frontier if down else np.flatnonzero(dist < 0)
        found = np.concatenate([np.zeros(0, np.int64)] + [
            _found(indptr, nbr, dist, level, rows[i:i + _BLOCK_ROWS], down)
            for i in range(0, rows.size, _BLOCK_ROWS)])
        level += 1
        dist[found] = level
        frontier = np.flatnonzero(dist == level)
        unseen_rows -= int(degree[frontier].sum())
    return dist


_STRUCTURES: Dict[Tuple[int, int, int], Structure] = {}


def structure(sizes: dict) -> Structure:
    """The deployment's graph for ``sizes`` (``scale``, ``edgefactor``,
    ``roots``), made once a process: it does not depend on the seed."""
    key = (int(sizes["scale"]), int(sizes["edgefactor"]),
           int(sizes["roots"]))
    if key not in _STRUCTURES:
        scale, edgefactor, n_roots = key
        n, lo, hi = _undirected(scale, *_kronecker(scale, edgefactor))
        indptr, nbr = _csr(n, lo, hi)
        # the top hub is in the largest component; a search from it
        # finds the component.  The roots are neighbours of the hub of
        # the least degree (degree 1 where there are enough): a search
        # from one is the hub's shifted by a level, so every root reads
        # the same rows, and a read costs the same whichever is drawn
        degree = np.diff(indptr)
        hub = int(np.argmax(degree))
        dist = bfs_levels(indptr, nbr, hub)
        inside = np.flatnonzero(dist >= 0)
        near = np.unique(nbr[indptr[hub]:indptr[hub + 1]])
        near = near[np.argsort(degree[near], kind="stable")]
        least = near[degree[near] <= max(1, int(degree[near[n_roots - 1]]))]
        rng = np.random.Generator(np.random.PCG64(STRUCTURE_SEED + 1))
        roots = np.sort(rng.choice(least, size=n_roots, replace=False))
        _STRUCTURES[key] = Structure(
            n, lo, hi, indptr, nbr, roots.astype(np.int64),
            component=int(inside.size),
            component_edges=int(np.diff(indptr)[inside].sum()) // 2)
    return _STRUCTURES[key]


def make_data(sizes: dict, seed: int) -> Data:
    """The deployment's arrays from ``seed``: the structure's labels and
    edge order drawn from it; ``sizes`` is the config file's group."""
    s = structure(sizes)
    rng = np.random.Generator(np.random.PCG64(seed % (2 ** 64)))
    return Data(s, rng.permutation(s.n).astype(np.int64),
                rng.permutation(s.lo.shape[0]).astype(np.int64))


def build_graph(session, data: Data):
    """Ingest ``data`` through the engine's public table API: columns
    go in as arrays."""
    from caps_tpu.okapi.types import CTInteger
    from caps_tpu.relational.entity_tables import (
        NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
    )
    n = data.n_vertices
    src, tgt = data.edges()
    f = session.table_factory
    nt = NodeTable(NodeMapping.on("_id").with_implied_labels("V"),
                   f.from_columns({"_id": np.arange(n, dtype=np.int64)},
                                  {"_id": CTInteger}))
    rt = RelationshipTable(
        RelationshipMapping.on("E"),
        f.from_columns(
            {"_id": np.arange(n, n + src.shape[0], dtype=np.int64),
             "_src": src, "_tgt": tgt},
            {"_id": CTInteger, "_src": CTInteger, "_tgt": CTInteger}))
    return session.create_graph([nt], [rt])


def bindings(data: Data, rule: dict, rng) -> List[dict]:
    """``{"kind": "roots", "count": k}``: the first ``k`` of the
    structure's roots, under this seed's labels.  ``rng`` is not used:
    the roots are part of the deployment."""
    if rule["kind"] != "roots":
        raise ValueError(f"unknown bindings rule {rule['kind']!r}")
    roots = data.structure.roots[:int(rule["count"])]
    return [{"root": int(data.label[r])} for r in roots]


def _csr_of(data: Data) -> Tuple[np.ndarray, np.ndarray]:
    """The undirected CSR the reference searches, once per data: the
    structure's own, or rebuilt from the stored edges that are alive."""
    cached = getattr(data, "_csr_cache", None)
    if cached is None:
        s = data.structure
        if data.alive is None:
            cached = (s.indptr, s.nbr)
        else:
            keep = np.sort(data.order[data.alive])
            cached = _csr(s.n, s.lo[keep], s.hi[keep])
        data._csr_cache = cached
    return cached


def _answer(data: Data, vertex: int) -> List[dict]:
    """The rows of a search from ``vertex`` (structure labels)."""
    dist = bfs_levels(*_csr_of(data), vertex)
    reached = dist >= 0
    counts = np.bincount(dist[reached])
    # float64 sums of ids below 2**31 are exact up to 2**22 of them
    ids = np.bincount(dist[reached], weights=data.label[reached])
    return [{"dist": d, "vertices": int(counts[d]), "ids": int(ids[d])}
            for d in range(counts.size)]


def reference(data: Data, queries: Sequence[str], params: dict
              ) -> Dict[str, List[dict]]:
    """``{query: the rows QUERIES[query] must return under params}``.  A
    search runs in the structure's labels (the stored graph is the same
    graph relabelled); each level's ids are summed under the seed's.
    The roots' searches are made together on the first call, on as many
    threads as there are roots and cores."""
    answers = getattr(data, "_answers", None)
    if answers is None:
        roots = [int(r) for r in data.structure.roots]
        _csr_of(data)           # once, before the threads share it
        with ThreadPoolExecutor(min(len(roots), _threads())) as pool:
            answers = dict(zip(roots, pool.map(
                lambda r: _answer(data, r), roots)))
        data._answers = answers
    where = np.flatnonzero(data.label == int(params["root"]))
    rows: List[dict] = []
    if where.size:
        vertex = int(where[0])
        rows = answers[vertex] if vertex in answers \
            else _answer(data, vertex)
    return {q: rows for q in queries}


def stale_copy(data: Data, fraction: float) -> Data:
    """The control's data: the same graph without every
    ``1/fraction``-th stored edge -- what a replica that missed those
    writes would answer from.  Breaks the configuration's guarantee of
    exact answers on the graph as loaded."""
    every = max(2, int(round(1.0 / fraction)))
    alive = np.arange(data.order.size) % every != every - 1
    return Data(data.structure, data.label, data.order, alive)

"""The friend-of-friend graph of benchmark config 1, its queries and
their numpy oracles.

One ``Person`` table (``name``, ``age``) and one ``KNOWS`` table with
uniformly random endpoints; ``n_seeds`` people are named ``'Alice'``,
everyone else ``p<i>``.  ``chip_smoke.py`` builds it from ``--seed``.

The oracles compute from the raw ``src``/``dst``/``names``/``ages``
arrays, independent of the engine, and honour openCypher relationship
uniqueness: in ``(a)-[r1]->(b)-[r2]->(c)`` ``r1`` and ``r2`` are distinct
relationships, which only bites where ``r1`` is a self-loop.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

_TWO_HOP = "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "

#: config 1: plans as one ``CountPattern`` (count push-down, fused SpMV)
QUERY = _TWO_HOP + "WHERE a.name = 'Alice' RETURN count(*) AS c"
#: the canonical serving shape: same text, rotating ``$seed`` bindings
PARAM_QUERY = _TWO_HOP + "WHERE a.name = $seed RETURN count(*) AS c"
#: join-shaped reads the count push-down cannot take: four Joins (Expand
#: materialisation) + Aggregate + OrderBy (+ Limit).  ``AGE_TOP_QUERY``
#: groups by an int key (sorted group-by), ``AGE_SPLIT_QUERY`` by a bool
#: key (the dense segment kernel).
AGE_TOP_QUERY = (_TWO_HOP + "WHERE a.name = $seed "
                 "RETURN c.age AS age, count(*) AS n "
                 "ORDER BY n DESC, age LIMIT 10")
AGE_SPLIT_QUERY = (_TWO_HOP + "WHERE a.name = $seed "
                   "RETURN c.age >= 50 AS older, count(*) AS n, "
                   "min(c.age) AS lo, max(c.age) AS hi ORDER BY older")


def build_graph(session, n_people: int, n_edges: int, n_seeds: int, rng):
    """``(graph, src, dst, names, ages)`` on ``session``'s backend."""
    from caps_tpu.okapi.types import CTInteger, CTString
    from caps_tpu.relational.entity_tables import (
        NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
    )
    names = [f"p{i}" for i in range(n_people)]
    for s in rng.choice(n_people, size=n_seeds, replace=False):
        names[s] = "Alice"
    ages = rng.randint(18, 90, n_people)
    src = rng.randint(0, n_people, n_edges)
    dst = rng.randint(0, n_people, n_edges)
    f = session.table_factory
    nt = NodeTable(
        NodeMapping.on("_id").with_implied_labels("Person")
        .with_property("name").with_property("age"),
        f.from_columns(
            {"_id": list(range(n_people)), "name": names,
             "age": [int(a) for a in ages]},
            {"_id": CTInteger, "name": CTString, "age": CTInteger}))
    rt = RelationshipTable(
        RelationshipMapping.on("KNOWS"),
        f.from_columns(
            {"_id": list(range(n_people, n_people + n_edges)),
             "_src": [int(x) for x in src], "_tgt": [int(x) for x in dst]},
            {"_id": CTInteger, "_src": CTInteger, "_tgt": CTInteger}))
    return session.create_graph([nt], [rt]), src, dst, names, ages


def _second_hop_weights(src, dst, names, seed: str) -> np.ndarray:
    """Per edge ``r2``: how many ``r1 != r2`` start at a ``seed``-named
    person and end where ``r2`` starts."""
    is_seed = np.asarray(names) == seed
    arrivals = np.bincount(dst[is_seed[src]], minlength=len(names))
    loop_on_seed = (src == dst) & is_seed[src]  # r1 == r2 would count
    return arrivals[src] - loop_on_seed.astype(np.int64)


def expected_paths(src, dst, names, seeds: Sequence[str]) -> Dict[str, int]:
    """Oracle for the count queries: 2-hop path count per seed name."""
    return {s: int(_second_hop_weights(src, dst, names, s).sum())
            for s in seeds}


def expected_age_top(src, dst, names, ages, seed: str) -> List[dict]:
    """Oracle for :data:`AGE_TOP_QUERY`."""
    w = _second_hop_weights(src, dst, names, seed)
    per_age = np.bincount(ages[dst], weights=w,
                          minlength=int(ages.max()) + 1).astype(np.int64)
    live = np.flatnonzero(per_age)
    order = live[np.lexsort((live, -per_age[live]))][:10]
    return [{"age": int(a), "n": int(per_age[a])} for a in order]


def expected_age_split(src, dst, names, ages, seed: str) -> List[dict]:
    """Oracle for :data:`AGE_SPLIT_QUERY`."""
    w = _second_hop_weights(src, dst, names, seed)
    c_age = ages[dst]
    rows = []
    for older in (False, True):
        hit = (w > 0) & ((c_age >= 50) == older)
        if hit.any():
            rows.append({"older": older, "n": int(w[hit].sum()),
                         "lo": int(c_age[hit].min()),
                         "hi": int(c_age[hit].max())})
    return rows

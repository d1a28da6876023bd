"""Friends-of-friends deployment: data from a seed, the served query
texts and their numpy reference.

The experiment of Partner and Vukotic ("Neo4j in Action", ch. 1; retold
as table 2-1 of Robinson, Webber and Eifrem, "Graph Databases"): a social
network of 1,000,000 people with about 50 friends each, and for a person
chosen at random the number of distinct friends-of-friends at depth 2, 3,
4, 5 (about 2,500, 110,000, 600,000, 800,000 records).

One ``Person(name)`` table, ``name`` = ``p<i>``, and one ``KNOWS`` table:
person ``i`` knows ``friends[i, :]``, drawn uniformly among the others
(never ``i``; a pair may repeat).  With no self-loop, openCypher's
relationship uniqueness never bites: two hops of one path are always
two relationships.

It imports nothing of the program except, inside :func:`build_graph`,
the public ingest API that any client of the engine uses.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

_HOP = "-[:KNOWS]->"
_COUNT = " WHERE a.name = $name RETURN count(DISTINCT c) AS fof"

#: query name -> text; a traffic file names queries by these keys
QUERIES: Dict[str, str] = {
    "fof2": "MATCH (a:Person)" + _HOP + "()" + _HOP + "(c)" + _COUNT,
    "fof3": "MATCH (a:Person)" + _HOP + "()" + _HOP + "()" + _HOP + "(c)"
            + _COUNT,
}
_DEPTH = {"fof2": 2, "fof3": 3}


@dataclasses.dataclass
class Data:
    friends: np.ndarray                  # int64 [n_people, friends each]
    #: which of ``friends`` exist; None = all (only the control drops some)
    alive: Optional[np.ndarray] = None

    @property
    def n_people(self) -> int:
        return self.friends.shape[0]

    def walk_ends(self, start: int, hops: int) -> np.ndarray:
        """Where every walk of ``hops`` relationships from ``start`` ends,
        one entry a walk.  A walk uses no relationship twice (openCypher's
        uniqueness; it bites from three hops on, where ``a->b->a->b``
        would take ``a->b`` twice).  A relationship's id is its place in
        ``friends``."""
        k = self.friends.shape[1]
        ends, used = np.array([start], dtype=np.int64), []
        for _hop in range(hops):
            rel = (ends[:, None] * k + np.arange(k)).ravel()
            used = [np.repeat(u, k) for u in used]
            keep = np.ones(len(rel), dtype=bool) if self.alive is None \
                else self.alive.ravel()[rel]
            for u in used:
                keep &= u != rel
            rel = rel[keep]
            used = [u[keep] for u in used] + [rel]
            ends = self.friends.ravel()[rel]
        return ends


def make_data(sizes: dict, seed: int) -> Data:
    """The deployment's arrays from ``seed``; ``sizes`` is the config
    file's ``sizes`` group."""
    n, k = int(sizes["people"]), int(sizes["friends_per_person"])
    rng = np.random.RandomState(seed % (2 ** 32))
    friends = rng.randint(0, n - 1, (n, k)).astype(np.int64)
    friends += friends >= np.arange(n)[:, None]      # anyone but oneself
    return Data(friends)


def build_graph(session, data: Data):
    """Ingest ``data`` through the engine's public table API."""
    from caps_tpu.okapi.types import CTInteger, CTString
    from caps_tpu.relational.entity_tables import (
        NodeMapping, NodeTable, RelationshipMapping, RelationshipTable,
    )
    n, k = data.friends.shape
    names = np.char.add("p", np.arange(n).astype(str))
    f = session.table_factory
    nt = NodeTable(
        NodeMapping.on("_id").with_implied_labels("Person")
        .with_property("name"),
        f.from_columns({"_id": list(range(n)), "name": names.tolist()},
                       {"_id": CTInteger, "name": CTString}))
    rt = RelationshipTable(
        RelationshipMapping.on("KNOWS"),
        f.from_columns(
            {"_id": list(range(n, n + n * k)),
             "_src": np.repeat(np.arange(n), k).tolist(),
             "_tgt": data.friends.ravel().tolist()},
            {"_id": CTInteger, "_src": CTInteger, "_tgt": CTInteger}))
    return session.create_graph([nt], [rt])


def bindings(data: Data, rule: dict, rng: np.random.RandomState) -> List[dict]:
    """``{"kind": "people", "count": n}``: ``n`` distinct people drawn
    from ``rng`` among all (everyone knows someone)."""
    if rule["kind"] != "people":
        raise ValueError(f"unknown bindings rule {rule['kind']!r}")
    picked = rng.choice(data.n_people, size=int(rule["count"]), replace=False)
    return [{"name": f"p{int(i)}"} for i in picked]


def reference(data: Data, queries: Sequence[str], params: dict
              ) -> Dict[str, List[dict]]:
    """``{query: the rows QUERIES[query] must return under params}``."""
    name = params["name"]
    known = name[1:].isdigit() and name == f"p{int(name[1:])}" \
        and int(name[1:]) < data.n_people
    return {q: [{"fof": int(len(np.unique(
        data.walk_ends(int(name[1:]), _DEPTH[q])))) if known else 0}]
        for q in queries}


def stale_copy(data: Data, fraction: float) -> Data:
    """The control's data: the same graph without every ``1/fraction``-th
    ``KNOWS`` relationship -- what a replica that missed those writes
    would answer from.  Breaks the configuration's guarantee of exact
    answers on the graph as loaded."""
    every = max(2, int(round(1.0 / fraction)))
    alive = (np.arange(data.friends.size) % every != every - 1)
    return dataclasses.replace(data, alive=alive.reshape(data.friends.shape))

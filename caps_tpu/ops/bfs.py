"""Breadth-first search over the resident CSRs of an ingested graph.

The relationship tables of a device graph carry, from ingest on, a CSR
over their source and over their target column (``DeviceCSR``,
``prepare_rel_table``), and the node tables an id index
(``prepare_node_table``).  A search reads those and nothing that lives
on the host: a vertex is its id, the distances are one int32 array over
the id domain on the device, and each level reads the adjacency rows of
one vertex set, chosen the way that reads fewer entries (Beamer,
Asanovic, Patterson, SC 2012):

* **top-down**: the rows of the frontier (distance = level); an entry
  names a vertex on the next level when that vertex is unseen;
* **bottom-up**: the rows *the other way* of the unseen vertices; an
  unseen vertex is on the next level when one of its entries names a
  frontier vertex.  It reads the first ``_TRIES`` entries of every row
  first, and the rest only of the rows that found nothing: most unseen
  vertices of a large level find the frontier at once.

Either way a level is one or two passes over a stream of adjacency
entries, and a pass is one program (``bfs_pass``): it lays the chosen
rows out (``_rows``: the non-empty rows first, and a prefix sum of
their lengths), then walks the stream ``CHUNK`` entries at a time in a
device loop whose carry is the distances (``_chunk``): the expand
kernel (``expand_positions``) over the rows the window covers, the
neighbour ids, a gather of their distances, a key sort that keeps each
vertex found once, and a scatter of those, ``_MARK`` lanes at a time:
a search writes each vertex it reaches once, and fewer than ``_MARK``
lanes more a chunk.  The pass's length stays on the device; the host
launches one program a pass and reads one stack of numbers a level
(``bfs_level``: the next frontier's size and rows, and the level's
pass lengths).  Every program has a shape fixed by the graph, not by
the level, so a search compiles the same few programs whatever its
root.  The distances stay on the device and come out as a table of
reached vertices in id order.

No program sorts more than ``CHUNK`` keys at once, and no prefix sum
runs as one scan over a whole array: the TPU's compiler takes about a
second for a sort of 2^13 keys and 15-45 s for one of 2^16 to 2^22, and
~9 s for a single cumsum of 2^21.  So the row layout and the emitted
rows are compacted block by block (``_kept_first_blocked``: a loop of
sorts of ``CHUNK`` keys), and prefix sums go by rows of 1024
(``_prefix_sum``).

``Adjacency`` is built once per graph: beside the CSRs it keeps each
one's neighbour ids in CSR order (one gather of the other endpoint
column through ``perm``; 4 bytes an entry), so that the stream reads a
neighbour with one gather instead of two.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from caps_tpu.ops.compact import kept_first
from caps_tpu.ops.expand import (csr_probe, expand_positions,
                                 expand_positions_ref)

#: the distance of a vertex not reached yet
UNSEEN = int(np.iinfo(np.int32).max)
#: entries a chunk of the stream holds, and the keys a compaction block
#: sorts at once
CHUNK = 1 << 13
#: the width of the rows a prefix sum goes by
_SCAN_ROW = 1024
#: lanes a chunk's scatter of the vertices it found writes at once
_MARK = 256
#: entries of each row a bottom-up level reads before the rest
_TRIES = 2


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive int32 prefix sums of ``x``: sums within rows of
    ``_SCAN_ROW``, then the rows' carries."""
    n = x.shape[0]
    rows = -(-n // _SCAN_ROW)
    y = jnp.pad(x.astype(jnp.int32), (0, rows * _SCAN_ROW - n))
    inner = jnp.cumsum(y.reshape(rows, _SCAN_ROW), axis=1, dtype=jnp.int32)
    ends = inner[:, -1]
    carry = jnp.cumsum(ends, dtype=jnp.int32) - ends
    return (inner + carry[:, None]).reshape(-1)[:n]


def _kept_first_blocked(mask: jnp.ndarray, *payload: jnp.ndarray):
    """What ``kept_first`` gives, block by block: a loop over blocks of
    ``CHUNK`` rows, each sorted on its own and written after the rows
    kept before it.  Returns the number of rows kept, their indices, then
    each payload; every array has ``CHUNK`` rows more than the blocks
    cover, and its rows from the count on are not kept rows."""
    n = mask.shape[0]
    blocks = -(-n // CHUNK)
    pad = blocks * CHUNK - n
    mask = jnp.pad(mask, (0, pad))
    arrays = (jnp.arange(blocks * CHUNK, dtype=jnp.int32),) + tuple(
        jnp.pad(p, (0, pad)) for p in payload)
    outs = tuple(jnp.zeros((blocks + 1) * CHUNK, a.dtype) for a in arrays)

    def block(b, carry):
        done, outs = carry
        at = b * CHUNK
        m = jax.lax.dynamic_slice(mask, (at,), (CHUNK,))
        _key, *rows = kept_first(m, *(jax.lax.dynamic_slice(a, (at,),
                                                            (CHUNK,))
                                      for a in arrays))
        outs = tuple(jax.lax.dynamic_update_slice(o, r, (done,))
                     for o, r in zip(outs, rows))
        return done + m.sum(dtype=jnp.int32), outs

    kept, outs = jax.lax.fori_loop(0, blocks, block, (jnp.int32(0), outs))
    return (kept,) + outs


@dataclasses.dataclass
class Adjacency:
    """The search's view of a graph's CSRs: one *segment* per (table,
    direction), each with its ``indptr`` over the whole id domain and its
    neighbour ids at ``nbr[offset + position]``."""
    indptrs: Tuple[jnp.ndarray, ...]    # each (domain + 1,) int32
    offsets: Tuple[int, ...]
    entries: Tuple[int, ...]            # live adjacency entries a segment
    nbr: jnp.ndarray                    # int32
    node_indptrs: Tuple[jnp.ndarray, ...]  # id indexes of the node tables
    domain: int

    def rows(self, segments: Sequence[int]) -> int:
        return sum(self.entries[k] for k in segments)


@functools.partial(jax.jit, static_argnames=("domain",))
def bfs_neighbours(indptrs, perms, others, *, domain: int):
    """Each segment's ``indptr`` padded to the domain, and its neighbour
    ids in CSR order: the other endpoint column read through ``perm``."""
    padded = tuple(jnp.pad(ip, (0, domain + 1 - ip.shape[0]), mode="edge")
                   for ip in indptrs)
    nbr = jnp.concatenate([o[p].astype(jnp.int32)
                           for p, o in zip(perms, others)])
    return padded, nbr


def build_adjacency(segments: Sequence[Tuple[object, jnp.ndarray]],
                    node_csrs: Sequence[object], read) -> Adjacency:
    """``segments``: (``DeviceCSR``, the other endpoint's column data) a
    (table, direction); ``node_csrs``: the node tables' id indexes;
    ``read``: the backend's counted device->host read (one, for the
    segments' live entries)."""
    domain = max([c.n_keys for c, _o in segments]
                 + [c.n_keys for c in node_csrs])
    indptrs, nbr = bfs_neighbours(
        tuple(c.indptr for c, _o in segments),
        tuple(c.perm for c, _o in segments),
        tuple(o for _c, o in segments), domain=domain)
    sizes = tuple(int(c.perm.shape[0]) for c, _o in segments)
    offsets = tuple(int(x) for x in np.cumsum((0,) + sizes[:-1]))
    entries = read(jnp.stack([ip[-1] for ip in indptrs]))
    return Adjacency(tuple(indptrs), offsets,
                     tuple(int(e) for e in entries), nbr,
                     tuple(c.indptr for c in node_csrs), domain)


@jax.jit
def bfs_degree(indptrs):
    """Adjacency entries of every vertex over the given segments."""
    deg = indptrs[0][1:] - indptrs[0][:-1]
    for ip in indptrs[1:]:
        deg = deg + (ip[1:] - ip[:-1])
    return deg


@jax.jit
def bfs_start(root, node_indptrs, degree, rdegree):
    """The root's distances, and ``[in the graph, its entries, its
    entries the other way]``.  ``root`` is a 0-d int64 operand: one
    program serves every root."""
    domain = degree.shape[0]
    key = jnp.reshape(root, (1,))
    yes = jnp.ones((1,), bool)
    found = jnp.zeros((1,), bool)
    for ip in node_indptrs:
        found = found | (csr_probe(ip, key, yes)[0] > 0)
    ok = found[0] & (root >= 0) & (root < domain)
    at = jnp.where(ok, root, 0).astype(jnp.int32)
    dist = jnp.where((jnp.arange(domain) == at) & ok, 0, UNSEEN)
    head = jnp.stack([ok.astype(jnp.int32), jnp.where(ok, degree[at], 0),
                      jnp.where(ok, rdegree[at], 0)])
    return dist.astype(jnp.int32), head


def _rows(dist, level, down, indptrs, rindptrs, offsets, skip, take, *,
          pad: int):
    """A pass's stream: top-down (``down``) the rows of the vertices at
    ``level``, else the rows the other way (``rindptrs``) of the unseen
    ones; of each row the entries from ``skip`` on, at most ``take``
    (``offsets``: where each row set's entries start in ``nbr``).
    Returns the non-empty rows in order -- their end in the stream, their
    length, their first entry in ``nbr``, their vertex -- padded with
    ``pad`` empty rows, and the stream's length."""
    domain = dist.shape[0]
    mine = jnp.where(down, dist == level, dist == UNSEEN)
    counts, lo = [], []
    for k, (ip, rip) in enumerate(zip(indptrs, rindptrs)):
        ptr = jnp.where(down, ip, rip)
        counts.append(jnp.where(mine, jnp.clip(ptr[1:] - ptr[:-1] - skip,
                                               0, take), 0))
        lo.append(ptr[:-1] + skip + offsets[k])
    counts = jnp.concatenate(counts)
    kept, slot, counts, lo = _kept_first_blocked(counts > 0, counts,
                                                 jnp.concatenate(lo))
    vertex = slot % domain
    counts = jnp.where(jnp.arange(counts.shape[0]) < kept, counts, 0)
    ends = _prefix_sum(counts)
    grow = lambda x: jnp.pad(x, (0, pad))               # noqa: E731
    # the ends stay sorted through the pad: a chunk searches them
    return (jnp.pad(ends, (0, pad), mode="edge"), grow(counts), grow(lo),
            grow(vertex), ends[-1])


def _chunk(dist, nbr, ends, counts, lo, vertex, base, level, down, *,
           chunk: int, kernel: bool, interpret: bool):
    """Entries ``[base, base + chunk)`` of a pass's stream (``_rows``):
    the vertices they find -- top-down the unseen neighbours, bottom-up
    the rows' own vertices whose neighbour is at ``level`` -- each marked
    ``level + 1`` once."""
    domain = dist.shape[0]
    first = jnp.searchsorted(ends, base, side="right").astype(jnp.int32)
    take = lambda x: jax.lax.dynamic_slice(x, (first,), (chunk + 1,))  # noqa
    w_ends, w_counts, w_lo, w_vertex = (take(ends), take(counts), take(lo),
                                        take(vertex))
    # the first row may have started before the window
    cut = base - (w_ends[0] - w_counts[0])
    w_counts = w_counts.at[0].add(-cut)
    w_lo = w_lo.at[0].add(cut)
    if kernel:
        # the window's rows are non-empty up to the stream's end
        row, pos, valid = expand_positions(w_counts, w_lo, chunk,
                                           interpret=interpret,
                                           compacted=True)
    else:
        row, pos, valid = expand_positions_ref(w_counts, w_lo, chunk)
    w = nbr[pos]
    seen = dist[w]
    found = valid & jnp.where(down, seen == UNSEEN, seen == level)
    key = jnp.sort(jnp.where(found, jnp.where(down, w, w_vertex[row]),
                             UNSEEN))
    once = (key != UNSEEN) & jnp.concatenate(
        [jnp.ones((1,), bool), key[1:] != key[:-1]])
    # the vertices found, each once, first; marked _MARK at a time, so
    # that the scatter writes them and fewer than _MARK lanes more
    new = jnp.sort(jnp.where(once, key, UNSEEN))
    spare = domain + jnp.arange(_MARK, dtype=jnp.int32)

    def mark(i, d):
        at = jax.lax.dynamic_slice(new, (i * _MARK,), (_MARK,))
        at = jnp.where(at != UNSEEN, at, spare)
        return d.at[at].set(level + 1, mode="drop", unique_indices=True)

    return jax.lax.fori_loop(0, -(-once.sum(dtype=jnp.int32) // _MARK),
                             mark, dist)


@functools.partial(jax.jit, static_argnames=("pad", "chunk", "kernel",
                                             "interpret"))
def bfs_pass(dist, nbr, level, down, indptrs, rindptrs, offsets, skip,
             take, *, pad: int, chunk: int, kernel: bool, interpret: bool):
    """One pass of a level in one program: its stream laid out
    (``_rows``), then walked ``chunk`` entries at a time by a device loop
    over ``_chunk`` that carries the distances.  Returns them and the
    stream's length, which stays on the device."""
    *rows, length = _rows(dist, level, down, indptrs, rindptrs, offsets,
                          skip, take, pad=pad)

    def step(carry):
        base, dist = carry
        return base + chunk, _chunk(dist, nbr, *rows, base, level, down,
                                    chunk=chunk, kernel=kernel,
                                    interpret=interpret)

    _end, dist = jax.lax.while_loop(lambda carry: carry[0] < length, step,
                                    (jnp.int32(0), dist))
    return dist, length


@jax.jit
def bfs_level(dist, level, degree, rdegree, lengths):
    """``[vertices at level, their entries, their entries the other
    way]``, then the lengths of the passes that found them
    (``lengths``): one read brings back all the host needs of a level."""
    at = dist == level
    return jnp.stack([at.sum(dtype=jnp.int32),
                      jnp.where(at, degree, 0).sum(dtype=jnp.int32),
                      jnp.where(at, rdegree, 0).sum(dtype=jnp.int32),
                      *lengths])


@functools.partial(jax.jit, static_argnames=("cap",))
def bfs_emit(dist, *, cap: int):
    """The reached vertices in id order, their distances, and validity."""
    reached, ids, d = _kept_first_blocked(dist != UNSEEN, dist)
    if cap > ids.shape[0]:
        ids, d = (jnp.pad(x, (0, cap - x.shape[0])) for x in (ids, d))
    ok = jnp.arange(cap) < reached
    return (jnp.where(ok, ids[:cap], 0).astype(jnp.int64),
            jnp.where(ok, d[:cap], 0).astype(jnp.int64), ok)


@dataclasses.dataclass
class Search:
    """What one search leaves: the reached vertices (``ids``, ``dist``,
    ``ok``: device arrays of one bucket), how many, the levels expanded,
    the adjacency entries read, and the passes and ``CHUNK``-entry
    chunks they took."""
    ids: jnp.ndarray
    dist: jnp.ndarray
    ok: jnp.ndarray
    reached: int
    levels: int
    examined: int
    passes: int
    chunks: int
    converged: bool


def search(adj: Adjacency, segments: Sequence[int], root: int,
           max_depth: int, *, bucket: Callable[[int], int],
           read: Callable, use_kernel: Callable[[int], bool],
           interpret: bool, level_span: Callable) -> Search:
    """Hop distances from ``root`` over ``segments`` (indices into
    ``adj``; segment ``k ^ 1`` is ``k`` the other way), at most
    ``max_depth`` levels (< 0: no limit).  ``bucket`` rounds a size to a
    capacity, ``read`` is the counted device->host read (one for the
    root, then one a level), ``use_kernel(chunk)`` says whether the
    expand kernel serves a pass's chunks, ``interpret`` whether it runs
    in Pallas' interpreter, ``level_span()`` opens the span of one
    level."""
    back = tuple(k ^ 1 for k in segments)
    indptrs = tuple(adj.indptrs[k] for k in segments)
    rindptrs = tuple(adj.indptrs[k] for k in back)
    offsets = {True: np.asarray([adj.offsets[k] for k in segments],
                                np.int32),
               False: np.asarray([adj.offsets[k] for k in back], np.int32)}
    degree, rdegree = bfs_degree(indptrs), bfs_degree(rindptrs)
    dist, head = bfs_start(np.int64(root), adj.node_indptrs, degree,
                           rdegree)
    ok, f_rows, f_back = (int(x) for x in read(head))
    f_n, reached = ok, ok
    # entries the other way of the vertices not reached yet
    unseen_rows = adj.rows(back) - f_back
    levels = examined = passes = chunks = 0

    def walk(down: bool, skip: int, take: int):
        """One pass of the level; its length, on the device.  The host
        scalars go in as numpy ones: no conversion program each."""
        nonlocal dist
        dist, length = bfs_pass(dist, adj.nbr, np.int32(levels), down,
                                indptrs, rindptrs, offsets[down],
                                np.int32(skip), np.int32(take),
                                pad=CHUNK + 1, chunk=CHUNK,
                                kernel=use_kernel(CHUNK),
                                interpret=interpret)
        return length

    big = int(np.iinfo(np.int32).max)
    while f_n and (max_depth < 0 or levels < max_depth):
        with level_span():
            if unseen_rows + len(segments) * adj.domain < f_rows:
                lengths = (walk(False, 0, _TRIES), walk(False, _TRIES, big))
            else:
                lengths = (walk(True, 0, big),)
            f_n, f_rows, f_back, *lengths = (int(x) for x in read(
                bfs_level(dist, np.int32(levels + 1), degree, rdegree,
                          lengths)))
        levels += 1
        reached += f_n
        unseen_rows -= f_back
        examined += sum(lengths)
        passes += len(lengths)
        chunks += sum(-(-x // CHUNK) for x in lengths)
    ids, d, ok_rows = bfs_emit(dist, cap=bucket(max(reached, 1)))
    return Search(ids, d, ok_rows, reached, levels, examined, passes,
                  chunks, f_n == 0)

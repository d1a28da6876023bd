"""Row compaction: the indices of a mask's kept rows, in order.

Every operator that shrinks a table (a filter, ``distinct``, a keyed
``group``, the WCOJ frontier, ``union_all`` closing a replayed gap) and
the expand kernel's prelude turn a row mask into the positions of its
kept rows here.  ``jnp.nonzero(mask, size=)`` does the same with a
scatter-add of one update per *input* row, which XLA serializes on the
TPU (61-70 ns an update on the v5e, 18 ms at 2^18 rows where the sort
below takes 0.1 ms); neither form here scatters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: Rank search is taken while the elements its binary searches gather,
#: ``out_cap * n.bit_length()``, stay under ``n / _ROWS_PER_GATHER``.  On
#: the v5e a gathered element costs 7.1-7.7 ns, a row of the search's
#: int32 scan ~0.2 ns and a row of the sort 0.32-0.52 ns (1.5 ns at 2^12
#: rows), so the search wins below ~n / 28 gathers (PERF.md §6, the
#: shape table: scatter, rank search and sort timed at n from 2^12 to
#: 2^22, out_cap from 256 to n).  The sort takes every compaction of up to 2^16 rows;
#: the search keeps the narrow ones of larger masks, such as a name
#: look-up among 2^20 rows into its 256 bucket.
_ROWS_PER_GATHER = 32


def compact_form(n: int, out_cap: int) -> str:
    """``"search"`` or ``"sort"``: the form :func:`compact_indices` takes
    for a mask of ``n`` rows into ``out_cap`` slots, by shape alone."""
    if out_cap * n.bit_length() * _ROWS_PER_GATHER < n:
        return "search"
    return "sort"


def kept_first(mask: jnp.ndarray, *payload: jnp.ndarray):
    """One sort of a single key per row, ``i`` for a kept row and
    ``i + n`` for a dropped one: the kept rows' indices come first and in
    order, and a sorted key below ``n`` is a kept row.  Each ``payload``
    array (one value a row) rides the sort beside the key, so a caller
    that needs the kept rows' values gathers nothing.  The keys are
    unique, so the sort need not be stable.  Returns the sorted keys,
    then each payload in the same order."""
    n = mask.shape[0]
    # the largest key is 2n - 1; int64 only where that passes int32
    key_dtype = jnp.int32 if n <= 1 << 30 else jnp.int64
    row = jnp.arange(n, dtype=key_dtype)
    return jax.lax.sort((jnp.where(mask, row, row + n), *payload),
                        num_keys=1, is_stable=False)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def compact_indices(mask: jnp.ndarray, out_cap: int) -> jnp.ndarray:
    """Indices of the first ``out_cap`` kept rows, in order, padded with
    0: what ``jnp.nonzero(mask, size=out_cap, fill_value=0)`` returns,
    in its default integer dtype (int64 under the engine's x64 mode).

    Where the output is narrow against the input, the k-th kept row is
    searched for: the first position whose prefix count reaches k, one
    int32 scan and ``out_cap`` binary searches of ``n.bit_length()``
    gathers each.  Otherwise :func:`kept_first` sorts one key a row."""
    n = mask.shape[0]
    if compact_form(n, out_cap) == "search":
        kept = jnp.cumsum(mask, dtype=jnp.int32)
        ranks = jnp.arange(1, out_cap + 1, dtype=jnp.int32)
        pos = jnp.searchsorted(kept, ranks, side="left")
        return jnp.where(pos < n, pos, 0).astype(int)
    (first,) = kept_first(mask)
    if out_cap > n:
        first = jnp.pad(first, (0, out_cap - n), constant_values=n)
    return jnp.where(first[:out_cap] < n, first[:out_cap], 0).astype(int)

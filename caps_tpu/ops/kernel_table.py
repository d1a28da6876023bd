"""Which Pallas kernel families the engine routes to, per device.

A static table, read in the engine's own process: the process that runs
queries holds the chip, so nothing here starts a child, compiles a trial
kernel or keeps a verdict on disk.  A family that is on and then fails to
compile or run raises at its call site; it never degrades to a jnp twin
behind the caller's back.

    expand  — ops/expand.py ``expand_positions`` (join / Expand
              materialisation, WCOJ step expansion)
    segment — ops/segment.py ``dense_segment_agg`` (dense-domain group-by)
    sort    — ops/sort.py ``bitonic_sort_perm`` (order_by / distinct /
              group sorts at one-tile capacities)

Evidence for an entry is a compile for that device kind
(tests/test_tpu_compile.py keeps one per family) plus a run on the chip
that agrees with the jnp twin (``chip_smoke.py``; CHANGES.md PR 22).
"""
from __future__ import annotations

from typing import Dict, FrozenSet

import jax

FAMILIES = ("expand", "segment", "sort")

#: ``device_kind`` -> families whose COMPILED kernel the engine uses.
#: TPU v5 lite (v5e), jax/jaxlib 0.9.0, libtpu 0.0.34: all three compile
#: at the shapes the 1M-node / 5M-edge smoke reaches and equal their
#: twins on the chip (PR 22).
_COMPILED: Dict[str, FrozenSet[str]] = {
    "TPU v5 lite": frozenset(FAMILIES),
}

#: Families with a ``shard_map`` form (ops/segment.py
#: ``dense_segment_agg_sharded``).  The others take row-sharded operands
#: straight into a jitted ``pallas_call``, which XLA refuses to partition
#: ("Mosaic kernels cannot be automatically partitioned. Please wrap the
#: call in a shard_map" — compiled for v5e 2x2, PR 22), so a mesh session
#: runs their jnp twins under GSPMD.
_SHARDED: FrozenSet[str] = frozenset({"segment"})

#: Off the TPU the kernels run in Pallas interpret mode (the CPU suite
#: exercises the same kernel code).  The sort family stays off there: its
#: 105-stage network is far slower interpreted than ``lax.sort``.
_INTERPRETED: FrozenSet[str] = frozenset({"expand", "segment"})


class UnknownDeviceKind(RuntimeError):
    """A TPU whose ``device_kind`` has no row in the kernel table."""


def pallas_usable(family: str, sharded: bool = False) -> bool:
    """True when the engine should take ``family``'s Pallas kernel on the
    default device: compiled per the table on TPU, interpreted elsewhere.
    ``sharded``: the operands are row-sharded over a mesh.  An unknown
    TPU ``device_kind`` is an error, not a default — add its row with the
    evidence the module docstring names."""
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"known: {FAMILIES}")
    if sharded and family not in _SHARDED:
        return False
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return family in _INTERPRETED
    try:
        return family in _COMPILED[dev.device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no Pallas kernel table row for TPU device_kind "
            f"{dev.device_kind!r} (known: {sorted(_COMPILED)}); "
            f"see caps_tpu/ops/kernel_table.py") from None

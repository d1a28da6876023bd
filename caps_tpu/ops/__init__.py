"""Pallas TPU kernels for the hot relational operators.

SURVEY.md §2 "native components": the reference leans on Spark's Tungsten
(whole-stage codegen) and shuffle for its performance-critical paths; the
TPU-native equivalents are hand-written Pallas/Mosaic kernels.  Every
kernel here is a real ``pallas_call`` with a ``jax.numpy`` reference twin
(``*_ref``) used for differential testing (SURVEY.md §7 step 6).

Kernels run compiled on TPU and in interpreter mode everywhere else, so
the unit suite (CPU, 8 virtual devices) exercises the same kernel code.
Which families the engine routes to on which device is one static table
(``kernel_table.py``); a kernel that is on and fails, raises.
"""
from caps_tpu.ops.segment import (
    dense_segment_agg,
    dense_segment_agg_ref,
    dense_segment_agg_sharded,
    default_interpret,
)
from caps_tpu.ops.expand import (
    TILE as EXPAND_TILE,
    DeviceCSR,
    build_csr,
    expand_positions,
    expand_positions_ref,
    join_expand_via_positions,
)
from caps_tpu.ops.kernel_table import (
    FAMILIES,
    UnknownDeviceKind,
    pallas_usable,
)

__all__ = [
    "dense_segment_agg",
    "dense_segment_agg_ref",
    "dense_segment_agg_sharded",
    "default_interpret",
    "EXPAND_TILE",
    "FAMILIES",
    "UnknownDeviceKind",
    "DeviceCSR",
    "build_csr",
    "expand_positions",
    "expand_positions_ref",
    "join_expand_via_positions",
    "pallas_usable",
]

"""Device column representation and CypherType → dtype mapping.

A column is (data, valid): a device array padded to the table's bucketed
capacity plus a validity mask (False = Cypher null).  Row padding beyond
the table's live row count is tracked table-level, not per column.

Kinds:
    id     int32   entity ids (dense, < 2^31 — the MXU/VPU-friendly width)
    int    int64   CTInteger properties (Cypher integers are 64-bit)
    float  float64 CTFloat/CTNumber
    bool   bool_
    str    int32   dictionary codes into the session StringPool
    list   int32 2D (capacity, max_len) + lens — relationship-id lists
    object —       host-only values; forces local fallback
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from caps_tpu import native
from caps_tpu.okapi.types import (
    CTBoolean, CTDate, CTDateTime, CTFloat, CTInteger, CTNumber, CTString,
    CypherType, _CTList, _CTNode, _CTRelationship,
)

jax.config.update("jax_enable_x64", True)

_DTYPES = {
    "id": jnp.int32,
    "int": jnp.int64,
    "float": jnp.float64,
    "bool": jnp.bool_,
    "str": jnp.int32,
    "list": jnp.int32,
    # temporal: one int64 each (epoch days / epoch microseconds);
    # durations are 3-component and stay host-only ("object")
    "date": jnp.int64,
    "datetime": jnp.int64,
}


def list_elem_kind(ctype: CypherType) -> Optional[str]:
    """Element kind of a device-representable list type (values are packed
    into the int32 list matrix): rel/node ids, int (int32-range), str
    codes, bool.  None = no device representation (floats, nested lists,
    mixed/unknown element types)."""
    m = ctype.material
    if not isinstance(m, _CTList):
        return None
    inner = m.inner.material if m.inner is not None else None
    if isinstance(inner, (_CTRelationship, _CTNode)):
        return "id"
    if inner == CTInteger:
        return "int"
    if inner == CTString:
        return "str"
    if inner == CTBoolean:
        return "bool"
    return None


def kind_for(ctype: CypherType) -> str:
    m = ctype.material
    if isinstance(m, (_CTNode, _CTRelationship)):
        return "id"
    if isinstance(m, _CTList):
        if list_elem_kind(ctype) is not None:
            return "list"
        return "object"
    if m == CTInteger:
        return "int"
    if m in (CTFloat, CTNumber):
        return "float"
    if m == CTBoolean:
        return "bool"
    if m == CTString:
        return "str"
    if m == CTDate:
        return "date"
    if m == CTDateTime:
        return "datetime"
    return "object"


@dataclasses.dataclass
class Column:
    kind: str
    data: jnp.ndarray            # (capacity,) or (capacity, max_len)
    valid: jnp.ndarray           # bool (capacity,)
    ctype: CypherType
    lens: Optional[jnp.ndarray] = None  # int32 (capacity,) for kind="list"
    # Ingest-time host mirror (data_np, valid_np): scan columns keep the
    # numpy arrays they were built from, so host-side plan builders (the
    # fused count pushdown, the ring var-expand) never re-download graph
    # columns over the transport.  Derived columns drop it.
    host: Optional[tuple] = None

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def host_arrays(self):
        """(data, valid) as numpy: the ingest-time mirror when present,
        else one device read each (a transport round trip)."""
        if self.host is not None:
            return self.host
        d = np.asarray(self.data)
        v = (self.valid if isinstance(self.valid, np.ndarray)
             else np.asarray(self.valid))
        return d, v

    def astype_kind(self, kind: str) -> "Column":
        if kind == self.kind:
            return self
        return Column(kind, self.data.astype(_DTYPES[kind]), self.valid,
                      self.ctype, self.lens)


def make_column(values: List[Any], ctype: CypherType, capacity: int,
                pool) -> Column:
    """Host values → device column (padded to capacity).  ``values`` is a
    list, or for an integer column a numpy integer array (no nulls)."""
    kind = kind_for(ctype)
    n = len(values)
    valid_np = np.zeros(capacity, dtype=bool)
    if kind == "object":
        raise ValueError(f"type {ctype!r} has no device representation")
    if kind == "list":
        ek = list_elem_kind(ctype) or "id"
        max_len = max((len(v) for v in values if v is not None), default=0)
        data_np = np.zeros((capacity, max(1, max_len)), dtype=np.int32)
        lens_np = np.zeros(capacity, dtype=np.int32)
        for i, v in enumerate(values):
            if v is None:
                continue
            valid_np[i] = True
            lens_np[i] = len(v)
            for j, x in enumerate(v):
                data_np[i, j] = encode_list_elem(x, ek, pool)
        return Column(kind, jnp.asarray(data_np), jnp.asarray(valid_np),
                      ctype, jnp.asarray(lens_np))
    dtype = _DTYPES[kind]
    data_np = np.zeros(capacity, dtype=np.dtype(dtype))
    if kind == "str":
        codes = np.asarray(pool.encode_many(list(values)), dtype=np.int32)
        data_np[:n] = np.where(codes >= 0, codes, 0)
        valid_np[:n] = codes >= 0
        return Column(kind, jnp.asarray(data_np), jnp.asarray(valid_np),
                      ctype, host=(data_np, valid_np))
    if (isinstance(values, np.ndarray) and values.dtype.kind in "iu"
            and kind in ("int", "id")):
        # a column handed over as an integer array: no value is null
        if kind == "id" and n:
            _check_id(int(values.max()))
            _check_id(int(values.min()))
        data_np[:n] = values
        valid_np[:n] = True
        return Column(kind, jnp.asarray(data_np), jnp.asarray(valid_np),
                      ctype, host=(data_np, valid_np))
    fast = None if kind in ("date", "datetime") \
        else _make_column_native(values, kind, n)
    if fast is not None:
        d, v = fast
        data_np[:n] = d
        valid_np[:n] = v
        return Column(kind, jnp.asarray(data_np), jnp.asarray(valid_np),
                      ctype, host=(data_np, valid_np))
    for i, v in enumerate(values):
        if v is None:
            continue
        valid_np[i] = True
        if kind == "bool":
            data_np[i] = bool(v)
        elif kind == "id":
            data_np[i] = _check_id(int(v))
        elif kind == "float":
            data_np[i] = float(v)
        elif kind == "date":
            from caps_tpu.okapi.values import CypherDate
            data_np[i] = v.days if isinstance(v, CypherDate) else int(v)
        elif kind == "datetime":
            from caps_tpu.okapi.values import CypherDateTime
            data_np[i] = v.micros if isinstance(v, CypherDateTime) else int(v)
        else:
            data_np[i] = int(v)
    return Column(kind, jnp.asarray(data_np), jnp.asarray(valid_np), ctype,
                  host=(data_np, valid_np))


def _check_id(iv: int) -> int:
    if not (-2**31 < iv < 2**31):
        raise ValueError(f"entity id {iv} exceeds int32 (ingest "
                         "should densify ids)")
    return iv


def encode_list_elem(x: Any, elem_kind: str, pool) -> int:
    """Pack one list element into the int32 list matrix."""
    if x is None:
        raise ValueError("null list elements have no device representation")
    if elem_kind == "str":
        return pool.encode(x)
    if elem_kind == "bool":
        return int(bool(x))
    iv = int(x if not hasattr(x, "id") else x.id)
    return _check_id(iv)


def decode_list_elem(code: int, elem_kind: str, pool) -> Any:
    if elem_kind == "str":
        return pool.decode(int(code))
    if elem_kind == "bool":
        return bool(code)
    return int(code)


def _make_column_native(values, kind: str, n: int):
    """Bulk ingest via the C++ host runtime (native/csrc/host_runtime.cpp); returns
    (data, valid) numpy views of length n, or None to use the Python loop.
    str columns never reach here — make_column returns early via
    pool.encode_many (itself native-backed when available)."""
    if native.lib is None or n == 0:
        return None
    try:
        if kind in ("int", "id"):
            raw_d, raw_v = native.lib.ingest_i64(values)
            d = np.frombuffer(raw_d, np.int64)
            if kind == "id":
                if len(d):
                    _check_id(int(d.max()))
                    _check_id(int(d.min()))
                d = d.astype(np.int32)
        elif kind == "float":
            raw_d, raw_v = native.lib.ingest_f64(values)
            d = np.frombuffer(raw_d, np.float64)
        elif kind == "bool":
            raw_d, raw_v = native.lib.ingest_bool(values)
            d = np.frombuffer(raw_d, np.uint8).astype(bool)
        else:
            return None
    except (TypeError, ValueError, OverflowError):
        # values the strict C converters reject (e.g. numeric strings) —
        # fall back to the Python loop so semantics never depend on
        # whether the toolchain was present
        return None
    return d, np.frombuffer(raw_v, np.uint8).astype(bool)


def column_to_host(col: Column, n: int, pool) -> List[Any]:
    """Device column → host Python values (None for null).

    Each device→host read is a full transport round trip (on remote
    transports ~tens of ms flat), so columns whose validity is host-known
    (e.g. the fused count result) carry a numpy ``valid`` and pay exactly
    ONE device read here."""
    if isinstance(col.valid, np.ndarray):
        valid = col.valid[:n]
    else:
        valid = np.asarray(col.valid[:n])
    if col.kind == "list":
        ek = list_elem_kind(col.ctype) or "id"
        data = np.asarray(col.data[:n])
        lens = np.asarray(col.lens[:n])
        return [[decode_list_elem(x, ek, pool) for x in data[i, :lens[i]]]
                if valid[i] else None
                for i in range(n)]
    data = np.asarray(col.data[:n])
    out: List[Any] = []
    for i in range(n):
        if not valid[i]:
            out.append(None)
        elif col.kind == "str":
            out.append(pool.decode(int(data[i])))
        elif col.kind == "bool":
            out.append(bool(data[i]))
        elif col.kind == "float":
            out.append(float(data[i]))
        elif col.kind == "date":
            from caps_tpu.okapi.values import CypherDate
            out.append(CypherDate(int(data[i])))
        elif col.kind == "datetime":
            from caps_tpu.okapi.values import CypherDateTime
            out.append(CypherDateTime(int(data[i])))
        else:
            out.append(int(data[i]))
    return out


def literal_column(value: Any, ctype: CypherType, capacity: int,
                   pool) -> Column:
    kind = kind_for(ctype)
    if kind == "object":
        raise ValueError(f"type {ctype!r} has no device representation")
    if value is None:
        if kind == "list":
            return Column(kind, jnp.zeros((capacity, 1), jnp.int32),
                          jnp.zeros(capacity, bool), ctype,
                          jnp.zeros(capacity, jnp.int32))
        return Column(kind, jnp.zeros(capacity, _DTYPES[kind]),
                      jnp.zeros(capacity, bool), ctype)
    if kind == "str":
        value = pool.encode(value)
    if kind == "list":
        raise ValueError("literal list columns are not supported")
    data = jnp.full(capacity, value, _DTYPES[kind])
    return Column(kind, data, jnp.ones(capacity, bool), ctype)

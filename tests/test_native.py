"""Native host runtime (native/csrc/host_runtime.cpp): differential tests of the
C++ string pool / ingest / CSR against the pure-Python implementations
(SURVEY.md §2 native components — each native path keeps a Python twin)."""
import numpy as np
import pytest

from caps_tpu import native
from caps_tpu.backends.tpu.pool import NativeStringPool, StringPool

pytestmark = pytest.mark.skipif(not native.available(),
                                reason=f"no native lib: {native.build_error}")


VALUES = ["b", "a", None, "b", "", "ü", "a" * 100, None, "z"]


def test_pool_differential():
    py, nat = StringPool(), NativeStringPool()
    pc = py.encode_many(VALUES)
    nc = nat.encode_many(VALUES)
    np.testing.assert_array_equal(pc, nc)
    assert len(py) == len(nat)
    assert py.decode_many(pc) == nat.decode_many(nc) == [
        v for v in VALUES]
    np.testing.assert_array_equal(py.rank_array(), nat.rank_array())


def test_pool_single_encode_roundtrip():
    nat = NativeStringPool()
    a = nat.encode("x")
    assert nat.encode("x") == a
    assert nat.encode(None) == -1
    assert nat.decode(a) == "x"
    assert nat.decode(-1) is None


def test_pool_luts_match():
    py, nat = StringPool(), NativeStringPool()
    words = ["Apple", "apricot", "Banana", "avocado"]
    py.encode_many(words)
    nat.encode_many(words)
    np.testing.assert_array_equal(py.starts_with_lut("a"),
                                  nat.starts_with_lut("a"))
    np.testing.assert_array_equal(py.contains_lut("an"),
                                  nat.contains_lut("an"))
    np.testing.assert_array_equal(
        py.map_lut("upper", str.upper), nat.map_lut("upper", str.upper))
    assert py.decode_many(py.map_lut("upper", str.upper)) == \
        nat.decode_many(nat.map_lut("upper", str.upper))


def test_ingest_i64():
    d, v = native.lib.ingest_i64([1, None, -5, 2**40, True])
    np.testing.assert_array_equal(np.frombuffer(d, np.int64),
                                  [1, 0, -5, 2**40, 1])
    np.testing.assert_array_equal(np.frombuffer(v, np.uint8),
                                  [1, 0, 1, 1, 1])


def test_ingest_f64_and_bool():
    d, v = native.lib.ingest_f64([1.5, None, 2])
    np.testing.assert_allclose(np.frombuffer(d, np.float64), [1.5, 0.0, 2.0])
    d2, v2 = native.lib.ingest_bool([True, False, None, 1])
    np.testing.assert_array_equal(np.frombuffer(d2, np.uint8), [1, 0, 0, 1])
    np.testing.assert_array_equal(np.frombuffer(v2, np.uint8), [1, 1, 0, 1])


def test_ingest_rejects_bad_values():
    with pytest.raises(TypeError):
        native.lib.ingest_i64([1, "nope"])


def test_csr_build_matches_numpy():
    rng = np.random.RandomState(0)
    n_nodes, n_edges = 50, 400
    src = rng.randint(0, n_nodes, n_edges).astype(np.int64)
    dst = rng.randint(0, n_nodes, n_edges).astype(np.int64)
    off_b, perm_b = native.lib.csr_build(src.tobytes(), n_edges, n_nodes)
    off = np.frombuffer(off_b, np.int64)
    perm = np.frombuffer(perm_b, np.int64)
    # offsets = prefix histogram of sources
    np.testing.assert_array_equal(
        off, np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n_nodes))]))
    # perm groups edges by source, stable within a source
    assert sorted(perm) == list(range(n_edges))
    np.testing.assert_array_equal(src[perm], np.sort(src, kind="stable"))
    order = np.argsort(src, kind="stable")
    np.testing.assert_array_equal(perm, order)


def test_csr_build_rejects_out_of_range():
    src = np.array([0, 9], np.int64)
    with pytest.raises(ValueError):
        native.lib.csr_build(src.tobytes(), 2, 5)


def test_ingest_i64_rejects_nonfinite_floats():
    # parity with int(v): NaN/inf raise instead of storing garbage
    for bad in (float("nan"), float("inf"), float("-inf")):
        with pytest.raises((TypeError, ValueError, OverflowError)):
            native.lib.ingest_i64([1, bad])
    d, v = native.lib.ingest_i64([1, 2.0])  # finite floats still tolerated
    np.testing.assert_array_equal(np.frombuffer(d, np.int64), [1, 2])


def test_make_column_native_matches_python(make_session, monkeypatch):
    """Whole-table ingest parity: native on vs off."""
    from caps_tpu.okapi.types import CTBoolean, CTFloat, CTInteger, CTString
    data = {"i": [1, None, 3], "f": [1.5, None, -2.0],
            "b": [True, None, False], "s": ["x", None, "y"]}
    types = {"i": CTInteger, "f": CTFloat, "b": CTBoolean, "s": CTString}
    s1 = make_session("tpu")
    rows1 = s1.table_factory.from_columns(data, types).rows()
    monkeypatch.setattr(native, "lib", None)
    s2 = make_session("tpu")
    rows2 = s2.table_factory.from_columns(data, types).rows()
    assert rows1 == rows2


def test_loaded_library_is_named_by_its_source_hash():
    """What loads was built from the host_runtime.cpp beside it: the
    file name carries the source's content hash (no mtime test)."""
    import hashlib
    import os
    src = os.path.join(os.path.dirname(native.__file__), "csrc",
                       "host_runtime.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    assert native.source_hash() == digest
    assert f"_caps_host.{digest}." in os.path.basename(native.lib.__file__)

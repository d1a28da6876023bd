"""Differential tests: Pallas kernels vs their jnp reference twins
(SURVEY.md §7 step 6 — every kernel keeps a jnp twin for testing).

Run in interpreter mode on CPU; the same kernel code compiles on TPU.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from caps_tpu.ops import dense_segment_agg, dense_segment_agg_ref
from caps_tpu.backends.tpu import kernels as K

KINDS = ["count", "sum_f32", "sum_i32", "min_i32", "max_i32",
         "min_f32", "max_f32"]


def _case(rng, n, s):
    codes = rng.randint(0, s, n).astype(np.int32)
    ok = rng.rand(n) < 0.8
    return codes, ok


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n,s", [(1000, 7), (513, 130), (4096, 1),
                                 (100, 300), (1, 1),
                                 # multi-row-tile AND multi-segment-tile
                                 # (s > 1024 -> seg_tile 1024, grid j > 1)
                                 (5000, 1500)])
def test_dense_segment_agg_matches_ref(kind, n, s):
    # NB: deterministic seed — hash() is salted per process.
    rng = np.random.RandomState((len(kind) * 1009 + n * 31 + s) % 2**31)
    codes, ok = _case(rng, n, s)
    if kind.endswith("f32"):
        values = rng.randn(n).astype(np.float32)
    else:
        values = rng.randint(-1000, 1000, n).astype(np.int32)
    got = dense_segment_agg(jnp.asarray(codes), jnp.asarray(ok),
                            jnp.asarray(values), s, kind, interpret=True)
    want = dense_segment_agg_ref(jnp.asarray(codes), jnp.asarray(ok),
                                 jnp.asarray(values), s, kind)
    assert got.shape == want.shape == (s,)
    if kind.endswith("f32"):
        # f32 sums differ by reduction order; absolute tolerance scales
        # with segment population.
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3 * np.sqrt(n))
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dense_segment_agg_empty_input():
    got = dense_segment_agg(jnp.zeros(0, jnp.int32), jnp.zeros(0, bool),
                            jnp.zeros(0, jnp.int32), 5, "count",
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(5))


def test_dense_segment_agg_all_masked():
    codes = jnp.asarray(np.array([0, 1, 2], np.int32))
    ok = jnp.zeros(3, bool)
    vals = jnp.asarray(np.array([5, 6, 7], np.int32))
    got = dense_segment_agg(codes, ok, vals, 3, "count", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(3))
    got_min = dense_segment_agg(codes, ok, vals, 3, "min_i32", interpret=True)
    assert np.all(np.asarray(got_min) == np.iinfo(np.int32).max)


# -- bitonic multi-column sort (ops/sort.py) --------------------------------

def _adversarial_keys(rng, cap):
    """Key columns exercising every comparator edge: int64 beyond 2^53
    (float64 would collide them), NaN / +-0.0 / +-inf doubles, negated
    (descending) values, heavy duplicates, null sentinels."""
    k_int = rng.randint(-2**62, 2**62, cap).astype(np.int64)
    k_int[: cap // 8] = 2**53 + rng.randint(0, 3, cap // 8)
    k_int[cap // 8: cap // 4] = -(2**53) - rng.randint(0, 3, cap // 8)
    k_f = rng.rand(cap) * 100 - 50
    k_f[: cap // 16] = np.nan
    k_f[cap // 16: cap // 8] = -0.0
    k_f[cap // 8: 3 * cap // 16] = 0.0
    k_f[3 * cap // 16: cap // 5] = -np.inf
    k_f[cap // 5: cap // 4] = np.inf
    k_dup = rng.randint(0, 4, cap).astype(np.int64)
    k_null = (rng.rand(cap) < 0.3).astype(np.int64)  # null-first/last plane
    return [jnp.asarray(k_null), jnp.asarray(k_dup), jnp.asarray(-k_int),
            jnp.asarray(k_f)]


@pytest.mark.parametrize("cap", [256, 1024, 4096, 16384])
def test_bitonic_sort_perm_matches_lax(cap):
    """The bitonic network (XLA twin of the Pallas kernel body) must be
    bit-identical to the stable lax.sort path on adversarial keys."""
    from caps_tpu.ops.sort import (
        bitonic_sort_perm_twin, sort_cap_supported, split_planes,
    )
    assert sort_cap_supported(cap)
    rng = np.random.RandomState(cap)
    keys = _adversarial_keys(rng, cap)
    for nk in (1, 2, 4):
        sub = keys[:nk]
        want = np.asarray(K.sort_perm(sub, cap))
        got = np.asarray(bitonic_sort_perm_twin(tuple(split_planes(sub))))
        np.testing.assert_array_equal(got, want, err_msg=f"nk={nk}")


def test_bitonic_sort_pallas_interpret_smoke():
    """One small interpreter-mode pallas_call run to validate the kernel
    plumbing itself (the full network is exercised via the XLA twin —
    interpreter mode is far too slow for every shape)."""
    from caps_tpu.ops.sort import sort_perm_pallas
    cap = 256
    rng = np.random.RandomState(5)
    keys = [jnp.asarray(rng.randint(0, 7, cap).astype(np.int64))]
    want = np.asarray(K.sort_perm(keys, cap))
    got = np.asarray(sort_perm_pallas(keys, cap, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_bitonic_sort_unsupported_caps():
    from caps_tpu.ops.sort import sort_cap_supported
    assert not sort_cap_supported(0)
    assert not sort_cap_supported(128)        # R=1
    assert not sort_cap_supported(384)        # R=3
    assert not sort_cap_supported(32768)      # R=256
    assert sort_cap_supported(256) and sort_cap_supported(16384)


# -- ops/kernel_table.py: which families the engine routes to ---------------

def test_kernel_table_on_cpu_is_interpret_mode():
    """Off the TPU: expand and segment interpreted, sort off (its network
    is slower interpreted than lax.sort); row-sharded operands only where
    the family has a shard_map form."""
    from caps_tpu.ops import kernel_table
    assert [f for f in kernel_table.FAMILIES
            if kernel_table.pallas_usable(f)] == ["expand", "segment"]
    assert [f for f in kernel_table.FAMILIES
            if kernel_table.pallas_usable(f, sharded=True)] == ["segment"]
    with pytest.raises(ValueError, match="unknown kernel family"):
        kernel_table.pallas_usable("prefetch")


def test_kernel_table_unknown_tpu_kind_raises(monkeypatch):
    import types
    import jax
    from caps_tpu.ops import kernel_table

    def fake_devices(kind):
        return lambda *a, **k: [types.SimpleNamespace(platform="tpu",
                                                      device_kind=kind)]

    monkeypatch.setattr(jax, "devices", fake_devices("TPU v5 lite"))
    assert all(kernel_table.pallas_usable(f) for f in kernel_table.FAMILIES)
    monkeypatch.setattr(jax, "devices", fake_devices("TPU v9 future"))
    with pytest.raises(kernel_table.UnknownDeviceKind, match="TPU v9"):
        kernel_table.pallas_usable("segment")


def test_failing_kernel_raises_instead_of_falling_back(monkeypatch):
    """A kernel that is on and fails must surface: no catch-and-degrade
    to the sorted path, no latched kill flag, no fallback entry."""
    import caps_tpu
    from caps_tpu import ops as OPS
    from caps_tpu.testing.factory import create_graph
    session = caps_tpu.local_session(backend="tpu")
    graph = create_graph(session, """
        CREATE (:P {ok: true, v: 1}), (:P {ok: false, v: 2}),
               (:P {ok: true, v: 3})""")
    query = "MATCH (p:P) RETURN p.ok AS ok, count(*) AS n, max(p.v) AS hi"
    want = [{"ok": False, "n": 1, "hi": 2}, {"ok": True, "n": 2, "hi": 3}]
    rows = graph.cypher(query).records.to_maps()
    assert sorted(rows, key=lambda r: r["ok"]) == want
    assert session.backend.kernel_launches["segment"] > 0

    class PlantedMosaicError(Exception):
        pass

    def broken(*a, **k):
        raise PlantedMosaicError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(OPS, "dense_segment_agg", broken)
    with pytest.raises(PlantedMosaicError):
        graph.cypher(query + " ORDER BY ok").records.to_maps()
    assert session.fallback_count == 0
    assert session.backend.fallback_reasons == []

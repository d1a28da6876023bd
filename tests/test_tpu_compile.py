"""Compile the main path's kernels for a *described* TPU v5e — no chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached (``jax.experimental.topologies``), so what
Mosaic or XLA:TPU would refuse on the chip is refused here: a block that
breaks the tiling rule, a 64-bit value in a kernel body, a 64-bit
collective with no lowering, a kernel XLA cannot partition.  Interpret
mode and the CPU backend show none of these.  A compile that passes is
not a chip run: ``chip_smoke.py`` is what checks answers on the chip.

Everything that touches the topology lives in the fixtures below — one
process may load the TPU's library, so nothing here runs at import, and
these cases stay in this one file (one xdist worker).  The persistent
compilation cache is off around them: an entry written for a described
device cannot be read back.

The fused count program (relational/count_pattern.py) is a closure over
per-graph structures and cannot be lowered from shapes alone; it is
compiled here from a small graph's own argument shapes.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.fixture(scope="module")
def four_chips(topo, no_persistent_cache):
    mesh = Mesh(np.array(topo.devices), ("shard",))
    rows = NamedSharding(mesh, P("shard"))
    return mesh, lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                           sharding=rows)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_topology_is_the_kind_the_kernel_table_knows(topo):
    from caps_tpu.ops import kernel_table
    assert topo.devices[0].device_kind in kernel_table._COMPILED


@pytest.mark.parametrize("segs,kind", [(1500, "sum_f32"), (130, "count")])
def test_segment_kernel_compiles(one_chip, segs, kind):
    from caps_tpu.ops.segment import dense_segment_agg
    n = 1 << 20
    vals = jnp.float32 if kind.endswith("f32") else jnp.int32
    compiled = dense_segment_agg.lower(
        one_chip((n,), jnp.int32), one_chip((n,), jnp.bool_),
        one_chip((n,), vals), num_segments=segs, kind=kind,
        interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("cap", [256, 16384])
def test_sort_kernel_compiles(one_chip, cap):
    from caps_tpu.ops.sort import bitonic_sort_perm
    planes = tuple(one_chip((cap,), jnp.int32) for _ in range(3))
    compiled = bitonic_sort_perm.lower(planes, interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("cap_l,out_cap", [(65536, 8388608), (1024, 1024)])
def test_expand_kernel_compiles(one_chip, cap_l, out_cap):
    """The engine's x64 mode is on (module import): a weak i64 literal
    in the kernel body or a sub-1,024 data-dependent block would fail
    here exactly as on the chip (PR 22)."""
    from caps_tpu.ops.expand import expand_positions
    compiled = expand_positions.lower(
        one_chip((cap_l,), jnp.int64), one_chip((cap_l,), jnp.int32),
        out_cap=out_cap, interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("n,out_cap,searches", [
    (1 << 20, 256, True), (4096, 4096, False), (262144, 262144, False)])
def test_compact_indices_compiles(one_chip, n, out_cap, searches):
    """No compaction scatters one update per input row (64 ms for the
    filter's million-row mask on the v5e; 17.8 ms at 262,144 ->
    262,144): a narrow one searches, a wide one sorts one int32 key."""
    from caps_tpu.backends.tpu.kernels import compact_indices
    compiled = compact_indices.lower(one_chip((n,), jnp.bool_),
                                     out_cap=out_cap).compile()
    text = compiled.as_text()
    assert "scatter" not in text
    assert ("sort(" in text) != searches


@pytest.mark.parametrize("n_keys,cap_l", [
    (1 << 20, 262144), ((1 << 26) - 1, 4096)])
def test_csr_probe_compiles_without_a_loop(one_chip, n_keys, cap_l):
    """A join against an indexed id column is two gathers a row in one
    program; the search it stands in for (``probe_count``) is two
    ``while`` loops of ~21 rounds (140 ms each at 262,144 keys into 2^20
    ids on the v5e, PR 33).  At the sizes of ``fof-depth3-c4``'s node
    probe and of a ``KNOWS`` probe."""
    from caps_tpu.backends.tpu.kernels import probe_count
    from caps_tpu.ops.expand import csr_probe
    keys, ok = one_chip((cap_l,), jnp.int64), one_chip((cap_l,), jnp.bool_)
    indexed = csr_probe.lower(
        one_chip((n_keys + 1,), jnp.int32), keys, ok).compile()
    assert "while" not in indexed.as_text()
    searched = probe_count.lower(
        keys, ok, one_chip((n_keys,), jnp.int64)).compile()
    assert "while" in searched.as_text()


def _sorts_within(compiled, keys: int) -> bool:
    sorts = re.findall(r"= \(?[a-z0-9]+\[(\d+)\][^=]* sort\(",
                       compiled.as_text())
    return bool(sorts) and max(int(x) for x in sorts) <= keys


def _kernel_only_in_loops(compiled) -> bool:
    """The compiled program runs a Pallas kernel, and every computation
    that holds one is reached from the entry only through a ``while``'s
    body: the kernel runs inside a device loop."""
    text = compiled.as_text()
    bodies = dict(re.findall(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)\n\}$",
                             text, re.M | re.S))
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    outside, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in outside:
            continue
        outside.add(name)
        body = re.sub(r"body=%\S+", "", bodies[name])
        todo += re.findall(r"%([\w.\-]+)", " ".join(
            re.findall(r"(?:calls|to_apply|condition|branch_computations)"
                       r"=\{?([^}\s]+(?:, %[^}\s,]+)*)", body)))
    holders = [k for k, v in bodies.items() if "tpu_custom_call" in v]
    return bool(holders) and not outside.intersection(holders)


def test_bfs_level_programs_compile(one_chip):
    """The search's traced pieces (caps_tpu/ops/bfs.py) at a small
    graph's shapes, each compiled alone: a pass's row layout and a chunk
    of its stream with the expand kernel in it; the only scatter marks
    the vertices found, in a loop, and no program sorts more than a
    chunk's keys."""
    from caps_tpu.ops import bfs as B
    domain, pad, chunk = 1 << 16, B.CHUNK + 1, B.CHUNK
    i32 = lambda *shape: one_chip(shape, jnp.int32)         # noqa: E731
    dist, ips = i32(domain), (i32(domain + 1), i32(domain + 1))
    flag = one_chip((), jnp.bool_)
    rows = jax.jit(B._rows, static_argnames="pad").lower(
        dist, i32(), flag, ips, ips, i32(2), i32(), i32(), pad=pad).compile()
    assert "scatter" not in rows.as_text()
    n = 2 * domain + chunk + pad
    step = jax.jit(B._chunk, static_argnames=(
        "chunk", "kernel", "interpret")).lower(
        dist, i32(1 << 21), i32(n), i32(n), i32(n), i32(n), i32(), i32(),
        flag, chunk=chunk, kernel=True, interpret=False).compile()
    assert _has_kernel(step)
    assert step.as_text().count("scatter(") == 1
    emit = B.bfs_emit.lower(dist, cap=domain).compile()
    assert "scatter" not in emit.as_text()
    for program in (rows, step, emit):
        assert _sorts_within(program, chunk)


def test_bfs_pass_is_one_program(one_chip):
    """A level pass (``bfs_pass``) is one program at a small graph's
    shapes: the row layout, then the chunk loop on the device with the
    expand kernel inside the ``while``; one scatter (the mark), and no
    sort over a chunk's keys."""
    from caps_tpu.ops import bfs as B
    domain = 1 << 16
    i32 = lambda *shape: one_chip(shape, jnp.int32)         # noqa: E731
    ips = (i32(domain + 1), i32(domain + 1))
    one_pass = B.bfs_pass.lower(
        i32(domain), i32(1 << 21), i32(), one_chip((), jnp.bool_), ips, ips,
        i32(2), i32(), i32(), pad=B.CHUNK + 1, chunk=B.CHUNK, kernel=True,
        interpret=False).compile()
    assert _kernel_only_in_loops(one_pass)
    assert one_pass.as_text().count("scatter(") == 1
    assert _sorts_within(one_pass, B.CHUNK)


def test_fused_count_program_compiles(one_chip):
    """Config 1 through the count push-down, from a small graph."""
    from caps_tpu.backends.tpu.session import TPUCypherSession
    from caps_tpu.datasets import foaf
    from caps_tpu.okapi.config import EngineConfig
    session = TPUCypherSession(config=EngineConfig(use_cost_model=False))
    graph, src, dst, names, _ages = foaf.build_graph(
        session, 20_000, 100_000, 100, np.random.RandomState(42))
    got = graph.cypher(foaf.QUERY).records.to_maps()[0]["c"]
    assert got == foaf.expected_paths(src, dst, names, ["Alice"])["Alice"]
    entries = [e for e in session.backend.fused_count_fns.values()
               if isinstance(e, dict)]
    assert len(entries) == 1
    shapes = jax.tree_util.tree_map(
        lambda x: one_chip(x.shape, x.dtype), entries[0]["args"])
    entries[0]["run"].lower(*shapes).compile()


# -- four chips: a mesh over the described devices -------------------------


@pytest.mark.parametrize("kind", ["count", "min_i32"])
def test_sharded_segment_kernel_compiles(four_chips, kind):
    from caps_tpu.ops.segment import _sharded_agg_fn
    mesh, rows = four_chips
    n = 1 << 20
    compiled = _sharded_agg_fn(mesh, 3, kind, False).lower(
        rows((n,), jnp.int32), rows((n,), jnp.bool_),
        rows((n,), jnp.int32)).compile()
    assert _has_kernel(compiled) and "all-reduce" in compiled.as_text()


def test_unsharded_kernel_families_are_refused_on_a_mesh(four_chips):
    """The evidence behind ops/kernel_table.py ``_SHARDED``: XLA does
    not partition a Mosaic kernel, so a mesh session must not route
    row-sharded operands to expand/sort.  If this starts compiling, the
    table can grow."""
    from caps_tpu.ops import kernel_table
    from caps_tpu.ops.expand import expand_positions
    _mesh, rows = four_chips
    assert not kernel_table.pallas_usable("expand", sharded=True)
    assert not kernel_table.pallas_usable("sort", sharded=True)
    with pytest.raises(NotImplementedError, match="shard_map"):
        expand_positions.lower(
            rows((4096,), jnp.int64), rows((4096,), jnp.int32),
            out_cap=4096, interpret=False).compile()


def test_ring_two_hop_compiles_at_smoke_size(four_chips):
    """int64 path counts: needs the 64-bit-safe reduce-scatter
    (parallel/collectives.py ``sum_scatter``)."""
    from caps_tpu.parallel.ring import make_ring_khop
    mesh, rows = four_chips
    n, e = 1 << 20, 5 << 20
    khop = make_ring_khop(mesh, n, 2, axis="shard", masked=True)
    compiled = jax.jit(khop).lower(
        rows((n,), jnp.int64), rows((e,), jnp.int32), rows((e,), jnp.int32),
        rows((e,), jnp.bool_), rows((n,), jnp.int64)).compile()
    text = compiled.as_text()
    assert "collective-permute" in text and "all-to-all" in text


def test_csr_probe_compiles_on_a_mesh(four_chips):
    """On a mesh only relationship tables carry the index; their probe
    is the same one program, under GSPMD: keys row-sharded, ``indptr``
    replicated (at ``chip_smoke.py --chips 4``'s sizes)."""
    from caps_tpu.ops.expand import csr_probe
    mesh, rows = four_chips
    indptr = jax.ShapeDtypeStruct(((1 << 20) + 1,), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    compiled = csr_probe.lower(indptr, rows((65536,), jnp.int64),
                               rows((65536,), jnp.bool_)).compile()
    assert "while" not in compiled.as_text()


def test_int64_collectives_compile(four_chips):
    """``lax.pmax`` and ``lax.psum_scatter`` have no 64-bit lowering on
    TPU; the engine's replacements must."""
    from jax import shard_map
    from caps_tpu.parallel.collectives import global_max, sum_scatter
    mesh, rows = four_chips

    def body(x):
        return global_max(x.sum(), "shard"), sum_scatter(x, "shard", 4)

    jax.jit(shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                      out_specs=(P(), P("shard")))).lower(
        rows((4096,), jnp.int64)).compile()

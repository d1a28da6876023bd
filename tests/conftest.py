"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so that sharded (`shard_map`)
code paths execute exactly as they would on a v5e-8 — same program, mesh
size is config (SURVEY.md §4 carry-over (c)).  The real-TPU path is
exercised by chip_smoke.py (and tests/test_tpu_compile.py compiles its
kernels for a described v5e), not by running the unit suite on a chip.
"""
import os
import sys

# Unit tests run on the CPU whatever the machine has: JAX_PLATFORMS=cpu in
# the environment is all a CPU run needs.  The chip is exercised by
# chip_smoke.py, in a process of its own.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def make_session():
    """Session factory by backend name ('local' | 'tpu' | 'sharded')."""
    from caps_tpu.testing.sessions import make_backend_session
    return make_backend_session


@pytest.fixture(scope="module")
def fof():
    """The benchmark's friends-of-friends generator (data from a seed,
    the served query texts, their numpy reference), loaded by path:
    ``benchmarks/`` is no package."""
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_generators_fof",
        os.path.join(root, "benchmarks", "generators", "fof.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop jit/executable caches at every module boundary.

    XLA:CPU's backend_compile segfaults once a single process has
    accumulated a few hundred test files' worth of compiled programs
    (reproduced on an unmodified tree: the full suite dies
    deterministically inside jax's backend_compile at whichever
    compile crosses the threshold, while the same test passes in
    isolation).  Tests never rely on cross-module cache warmth — the
    persistent-compile-cache tests use the on-disk cache, and
    zero-compile replay assertions hold live references to their
    executables, which clear_caches() does not invalidate — so a
    boundary clear only costs per-module rewarming.
    """
    yield
    try:
        import jax
        jax.clear_caches()
    except Exception:  # pragma: no cover — cache clear is best-effort
        pass

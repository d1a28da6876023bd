"""The benchmark's own tests run on the CPU at a small size; the chip is
stood in for HERE, never by an option of the command."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def cpu_devices(cell):
    """Stands in for ``run.check_device``."""
    import jax
    return jax.devices()[:cell.chips]


#: the cut of a configuration that states none of its own
DEFAULT_TEST_SIZES = {"people": 20_000}


def small_copy(tmp_path, add_files=None) -> str:
    """A copy of BENCHMARK.json and the benchmark's directory in which
    every configuration is cut to its own ``test_sizes`` group, laid over
    ``sizes`` (the same shapes, cut in scale, whatever its scale is
    called).  ``add_files(root)`` writes what a later PR would bring (new
    files only) before the cut."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if add_files is not None:
        add_files(str(root))
    for name in os.listdir(root / "benchmarks" / "configs"):
        path = root / "benchmarks" / "configs" / name
        cfg = json.loads(path.read_text())
        cfg["sizes"].update(cfg.get("test_sizes", DEFAULT_TEST_SIZES))
        path.write_text(json.dumps(cfg))
    return str(root)


@pytest.fixture
def small_root(tmp_path):
    """``small_copy`` of the benchmark as committed (``fof-1m-50m``
    states no ``test_sizes``: 20,000 people, the same 50 friends each,
    so the same fan-out and the same buckets as on the chip)."""
    return small_copy(tmp_path)

"""Lazy loader for the native host runtime (native/csrc/host_runtime.cpp).

Compiles the CPython extension with g++ on first import, imports it, and
exposes it as ``native.lib``.  The built file's name carries a hash of the
source's content, so what loads was built from the ``host_runtime.cpp``
beside it — a binary left over from another source is never picked up.
``lib is None`` means no toolchain (``build_error`` says why) — callers
fall back to pure Python.  Opt out with ``CAPS_TPU_NO_NATIVE=1`` (useful
for differential tests).
"""
from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig

# The C++ source ships inside the package (package-data) so installed
# distributions keep the native fast path, not just repo checkouts.
_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "csrc", "host_runtime.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")

lib = None
build_error: str | None = None


def source_hash() -> str:
    """Content hash of the C++ source (12 hex digits of its SHA-256)."""
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def _so_path() -> str:
    tag = sysconfig.get_config_var("SOABI") or "none"
    return os.path.join(_BUILD_DIR,
                        f"_caps_host.{source_hash()}.{tag}.so")


def _build(so: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    include = sysconfig.get_paths()["include"]
    # build to a temp path + atomic rename: an interrupted link must not
    # leave a corrupt .so under the final name
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           f"-I{include}", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(f"native build failed: {proc.stderr[-2000:]}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global lib, build_error
    if os.environ.get("CAPS_TPU_NO_NATIVE"):
        build_error = "disabled by CAPS_TPU_NO_NATIVE"
        return
    so = _so_path()
    try:
        if not os.path.exists(so):
            _build(so)
        spec = importlib.util.spec_from_file_location("_caps_host", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        sys.modules["_caps_host"] = mod
        lib = mod
    except Exception as e:  # no toolchain / bad env — pure-Python fallback
        build_error = str(e)
        lib = None


_load()


def available() -> bool:
    return lib is not None

"""Every jax trace and backend compile of the process, counted.

``obs/compile.py`` charges the engine's four *known* compile boundaries;
a jit cache miss anywhere else (a new shape bucket, a second
``jax.default_device`` key) is seen by none of them.  jax reports every
one through ``jax.monitoring``; this module listens:

* ``xla.traces``    — ``/jax/core/compile/jaxpr_trace_duration`` events
  (a function traced to a jaxpr: a miss in jit's trace cache);
* ``xla.compiles``  — ``/jax/core/compile/backend_compile_duration``
  events (a lowered module handed to the backend; a load from the
  persistent compilation cache counts too — it is still time inside
  whatever window it lands in);
* ``xla.compile_s`` — the seconds of the latter.

The listeners are process-wide (jax has one listener list), so the
counters live in the process-global registry and every session's
``metrics_snapshot()`` reads them from there (:func:`snapshot`).  In a
served steady state all three stand still: any movement inside a
measured window is a compile the warm-up did not cover.
"""
from __future__ import annotations

from typing import Dict

from caps_tpu.obs.lockgraph import make_lock
from caps_tpu.obs.metrics import global_registry

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_install_lock = make_lock("xla_events._install_lock")
_installed = False


def _counters():
    """(traces, compiles, compile seconds), get-or-create: a test that
    clears the global registry gets fresh ones at the next event."""
    registry = global_registry()
    return (registry.counter("xla.traces"), registry.counter("xla.compiles"),
            registry.counter("xla.compile_s"))


def _on_duration(event: str, duration: float, **_kwargs) -> None:
    if event == COMPILE_EVENT:
        _traces, compiles, compile_s = _counters()
        compiles.inc()
        compile_s.inc(duration)
    elif event == TRACE_EVENT:
        _counters()[0].inc()


def install() -> None:
    """Register the listener, once per process (idempotent), and the
    three counters at 0 so a snapshot has them before the first event."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring
        _counters()
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def snapshot() -> Dict[str, float]:
    """The three counters as ``metrics_snapshot()`` keys."""
    return {c.name: c.value for c in _counters()}

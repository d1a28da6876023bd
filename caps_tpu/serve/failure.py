"""Failure taxonomy for the serving tier.

SURVEY.md §5.3: the reference engine inherited all failure handling from
Spark (lineage re-execution, executor blacklisting); single-controller
JAX has none, so the serving tier must decide *on its own* what a raised
exception means for the request and for the shared engine state.  One
function owns that decision:

    classify(exc) -> TRANSIENT | POISONED_PLAN | FATAL

* ``TRANSIENT`` — the execution environment hiccuped; the SAME
  execution path is expected to succeed on a retry.  Device-runtime
  errors with retryable status words (``RESOURCE_EXHAUSTED`` from an
  HBM allocator under pressure, ``UNAVAILABLE``/``ABORTED`` from a
  flapping transport), connection/timeout errors, and anything
  explicitly marked ``caps_transient = True``
  (the fault-injection harness and backend code use the marker).
  The worker retries these with exponential backoff
  (:mod:`caps_tpu.serve.retry`), charging the request's deadline.

* ``FATAL`` — the *request* is wrong or already resolved: syntax /
  semantic errors, missing parameters, cooperative cancellation and
  deadline expiry, and every :class:`~caps_tpu.serve.errors.ServeError`.
  Retrying cannot change the outcome; the error completes the handle
  as-is.

* ``POISONED_PLAN`` — everything else.  The deliberate default: an
  unexplained execution error while serving from shared cached state
  (a cached operator tree, a fused size memo) must be treated as
  possible corruption of that state, because a poisoned entry fails
  every future hit on its key.  The worker quarantines the plan-cache
  entry, drops the fused memos, and walks the degraded ladder (fresh
  fused re-record → per-operator unfused execution); a query that is
  simply broken deterministically costs two extra executions once and
  then trips its family's circuit breaker.

The classifier is import-light on purpose: it never imports jax —
device-runtime exceptions are recognized by MRO class *name* plus
status words in the message.
"""
from __future__ import annotations

from caps_tpu.serve.errors import CancellationError, ServeError

#: Classification outcomes (strings, not an Enum: they flow straight
#: into attempt-history dicts, metrics labels, and trace events).
TRANSIENT = "transient"
POISONED_PLAN = "poisoned_plan"
FATAL = "fatal"

#: Device-runtime exception class names treated as device errors
#: (``jax.errors.JaxRuntimeError`` and its older spelling).
_DEVICE_ERROR_NAMES = frozenset({"XlaRuntimeError", "JaxRuntimeError"})

#: Status words (gRPC / XLA canonical codes) that mark a device error
#: as retryable.  ``INTERNAL`` is included: on TPU transports it is the
#: catch-all for preempted/restarted device servers.
_RETRYABLE_STATUS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "ABORTED",
                     "CANCELLED", "INTERNAL", "DEADLINE_EXCEEDED")

#: Frontend / user-error exception class names (by name: the frontend
#: must stay importable without pulling the serving tier and vice
#: versa).
_FATAL_NAMES = frozenset({"CypherSyntaxError", "SemanticError",
                          "HeaderError", "NondeterministicResultError"})


def is_device_error(exc: BaseException) -> bool:
    """True when ``exc`` is (or wraps, via its MRO) an XLA runtime
    error — recognized by class name so no jax import is needed."""
    return any(c.__name__ in _DEVICE_ERROR_NAMES
               for c in type(exc).__mro__)


def device_fault(exc: BaseException) -> bool:
    """True when the error indicts the DEVICE rather than the query or
    the cached plan — the only failures the per-device health ladder
    (serve/devices.py) counts.  An explicit ``caps_device_fault`` marker
    wins (the device-scoped fault injectors stamp it); otherwise
    device-runtime errors by MRO name and connection failures qualify.
    A user's bad query must never take a device down."""
    marker = getattr(exc, "caps_device_fault", None)
    if marker is not None:
        return bool(marker)
    return is_device_error(exc) or isinstance(exc, ConnectionError)


def attribute_device(exc: BaseException, device_index: int) -> None:
    """Stamp the replica index an execution error was observed on —
    first-writer-wins, like ``caps_failed_op`` (relational/ops.py): the
    device CLOSEST to the failure keeps the attribution through retries
    on other devices."""
    try:
        if getattr(exc, "caps_device_index", None) is None:
            exc.caps_device_index = device_index
    except Exception:  # pragma: no cover — immutable exception types
        pass


def device_of(exc: BaseException):
    """The replica index stamped by :func:`attribute_device` (None when
    the error never crossed a device execution bracket)."""
    return getattr(exc, "caps_device_index", None)


def quarantine_plan_state(session, graph, query, params,
                          exec_lock=None) -> None:
    """Evict one family's shared cached state on ``session``: the
    plan-cache entry anchored by (graph, query, params) and, on
    backends with a fused executor, its size memos.  The ONE
    poisoned-plan eviction sequence — the server's device path and the
    shard-group path both call here, so containment semantics cannot
    drift apart.  ``exec_lock`` (the owning execution stream's lock) is
    held around the fused eviction: memo maps must not shrink under an
    in-flight fused run.  Never raises — containment must not fail."""
    import contextlib
    try:
        key_fn = getattr(session, "_plan_cache_key", None)
        if key_fn is not None:
            key = key_fn(graph, query, params)
            if key is not None:
                session.plan_cache.quarantine(key)
    except Exception:  # pragma: no cover — containment must not fail
        pass
    fused = getattr(session, "fused", None)
    if fused is not None:
        try:
            with (exec_lock if exec_lock is not None
                  else contextlib.nullcontext()):
                fused.forget(graph, query)
        except Exception:  # pragma: no cover — containment must not fail
            pass


def classify(exc: BaseException) -> str:
    """Map one raised exception to its containment treatment."""
    # explicit marker wins: the fault harness and backend code stamp
    # exceptions they KNOW are retryable / know are not
    marker = getattr(exc, "caps_transient", None)
    if marker is True:
        return TRANSIENT
    if marker is False:
        return FATAL
    # the serving tier's own errors are never retried by the serving
    # tier (cancellation, shedding, give-ups — all terminal here)
    if isinstance(exc, (CancellationError, ServeError)):
        return FATAL
    if is_device_error(exc):
        msg = str(exc)
        if any(s in msg for s in _RETRYABLE_STATUS):
            return TRANSIENT
        # device error without a retryable status (e.g. INVALID_ARGUMENT
        # out of a stale compiled program): suspect the cached state
        return POISONED_PLAN
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return TRANSIENT
    if isinstance(exc, (SyntaxError, KeyError, NotImplementedError)) \
            or type(exc).__name__ in _FATAL_NAMES:
        # user error (bad query text / missing $param / unsupported
        # feature): deterministic, never the cache's fault
        return FATAL
    return POISONED_PLAN

"""From a profiler trace (``.xplane.pb``) to device busy/idle time, time
per device operation, programs launched, and idle gaps named by what the
host was doing.

``load_xplane`` turns the profiler's file into plain tuples, and
``reduce_trace`` works on those alone, so the arithmetic is tested on a
hand-built trace (``tests/test_trace_reduce.py``) and no number depends
on anything but event names, starts and durations.

What a v5e trace looks like (one chip, jax 0.9.0, looked at by hand in
PR 26): plane ``/device:TPU:0`` carries the lines ``XLA Modules`` (one
event per program execution, named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO op or fusion inside a program; a ``while``
or ``conditional`` event covers its children, so the busy time is the
UNION of intervals, never their sum); plane ``/host:CPU`` carries one
line per host thread with the ``TraceMe`` events, among them the
engine's ``caps_tpu.<Operator>`` spans.  All planes share one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
Line = Tuple[str, List[Event]]          # name, events
Plane = Tuple[str, List[Line]]          # name, lines

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: written by the harness around the traced interval (host thread)
WINDOW_MARKER = "bench.traced_window"
HOST_SPAN_PREFIX = "caps_tpu."
NO_SPAN = "no span"
NO_PROGRAM = "no program"
#: an op's name in the trace is its whole HLO line; the breakdown keeps
#: this much of it
OP_NAME_CHARS = 120
#: idle gaps shorter than this sit between the ops of one running program;
#: they are summed under one name and not searched for a host span
SHORT_GAP_NS = 20_000
SHORT_GAPS = "gaps under 20us"


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events])
              for line in plane.lines])
            for plane in data.planes]


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: Iterable[Event], t0: int, t1: int
          ) -> List[Tuple[str, int, int]]:
    """``(name, start, end)`` of the parts of ``events`` inside
    ``[t0, t1]``; events with no part inside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e))
    return out


def _is_device(plane_name: str) -> bool:
    rest = plane_name[len(DEVICE_PLANE_PREFIX):]
    return plane_name.startswith(DEVICE_PLANE_PREFIX) and rest.isdigit()


def _window(planes: Sequence[Plane]) -> Optional[Tuple[int, int]]:
    """The harness's marker span: reads are counted against the same
    interval on the host's clock, so there is no other window to take."""
    for pname, lines in planes:
        if _is_device(pname):
            continue
        for _lname, events in lines:
            for name, start, dur in events:
                if name == WINDOW_MARKER:
                    return start, start + dur
    return None


def reduce_trace(planes: Sequence[Plane], top: int = 10) -> Optional[dict]:
    """The numbers the per-layer readers and the ``breakdown`` use.

    Returns None when the trace has no marker span or no device plane
    carries an operation inside it.  Seconds throughout:

    ``window_s``   length of the traced interval
    ``busy_s``     union of device-op intervals, mean over device planes
    ``programs``   program executions started inside the window (all planes)
    ``op_s``       {op name: summed duration inside the window} (a
                   parent op's time includes its children's)
    ``device_ops`` the ``top`` ops by duration, each named
                   ``<program that ran it>: <start of the op's name>``
    ``idle_gaps``  idle time of the first device plane summed by the
                   innermost host span (``caps_tpu.*``) that covered it,
                   the ``top`` names; ``"no span"`` where none did
    """
    win = _window(planes)
    if win is None:
        return None
    t0, t1 = win
    busy_ns: List[int] = []
    op_ns: Dict[Tuple[str, str], int] = {}     # (program, op) -> ns
    programs = 0
    gaps: List[Tuple[int, int]] = []
    for pname, lines in planes:
        if not _is_device(pname):
            continue
        ops = [c for lname, events in lines if lname == OPS_LINE
               for c in _clip(events, t0, t1)]
        if not ops:
            continue
        merged = _union((s, e) for _n, s, e in ops)
        busy_ns.append(sum(e - s for s, e in merged))
        mods = sorted((s, s + d, n) for lname, events in lines
                      if lname == MODULES_LINE for n, s, d in events)
        mod_starts = [m[0] for m in mods]
        for name, s, e in ops:
            i = bisect.bisect_right(mod_starts, s) - 1
            key = (mods[i][2] if i >= 0 and s < mods[i][1] else NO_PROGRAM,
                   name)
            op_ns[key] = op_ns.get(key, 0) + (e - s)
        programs += sum(1 for s in mod_starts if t0 <= s < t1)
        if not gaps:
            edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy_ns:
        return None
    spans = [c for pname, lines in planes if not _is_device(pname)
             for _lname, events in lines
             for c in _clip((ev for ev in events
                             if ev[0].startswith(HOST_SPAN_PREFIX)), t0, t1)]
    gap_ns = _attribute(gaps, spans)
    ranked = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    op_s: Dict[str, float] = {}
    for (_program, name), ns in op_ns.items():
        op_s[name] = op_s.get(name, 0.0) + ns / 1e9
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs": programs,
        "op_s": op_s,
        "device_ops": ranked({f"{program}: {name[:OP_NAME_CHARS]}": ns
                              for (program, name), ns in op_ns.items()}),
        "idle_gaps": ranked(gap_ns),
    }


def _attribute(gaps: Sequence[Tuple[int, int]],
               spans: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Idle time by host span.  A gap is cut where a span starts or ends
    inside it, and each piece goes to the innermost (shortest) span that
    covers it, or to ``"no span"``."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _n, s, _e in spans]
    out: Dict[str, int] = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0) + (g1 - g0)
            continue
        # spans are sorted by start: none that starts at or after g1 overlaps
        near = [sp for sp in spans[:bisect.bisect_left(starts, g1)]
                if sp[2] > g0]
        cuts = sorted({g0, g1} | {t for _n, s, e in near for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            over = [(e - s, n) for n, s, e in near if s <= a and e >= b]
            name = min(over)[1] if over else NO_SPAN
            out[name] = out.get(name, 0) + (b - a)
    return out


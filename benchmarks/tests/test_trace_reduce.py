"""The reduction from a trace to busy/idle, per-name sums, programs and
gap attribution, on a hand-built trace whose answers are worked out in
the comments (microseconds there, nanoseconds in the events)."""
import pytest

import trace_reduce as tr

US = 1000


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


# window 0..1000.  Device ops (start, end): fusion.0 [-50,10) clipped to
# [0,10); fusion.1 [100,130); while [120,150) with its child
# _expand_kernel [125,135); fusion.2 [400,500); fusion.3 [505,510).
# Union: [0,10) [100,150) [400,500) [505,510) = 10+50+100+5 = 165 busy.
# Gaps: [10,100) [150,400) [500,505) [510,1000).
# Host spans: Select [5,420) outermost; Scan [20,60), Join [140,390) inside.
PLANES = [
    ("/device:TPU:0", [
        ("Steps", [ev("0", 0, 1000)]),
        ("XLA Modules", [ev("jit_a(1)", -50, 60), ev("jit_b(2)", 100, 50),
                         ev("jit_c(3)", 400, 110)]),
        ("XLA Ops", [ev("fusion.0", -50, 60), ev("fusion.1", 100, 30),
                     ev("while", 120, 30), ev("_expand_kernel", 125, 10),
                     ev("fusion.2", 400, 100), ev("fusion.3", 505, 5)]),
    ]),
    ("/device:TPU:0 extra", [("XLA Ops", [ev("ignored", 0, 1000)])]),
    ("/host:CPU", [
        ("python", [ev("bench.traced_window", 0, 1000)]),
        ("python", [ev("caps_tpu.Select", 5, 415), ev("caps_tpu.Scan", 20, 40),
                    ev("caps_tpu.Join", 140, 250), ev("other", 600, 100)]),
    ]),
]


def test_busy_idle_programs_and_sums():
    got = tr.reduce_trace(PLANES)
    assert got["window_s"] == pytest.approx(1000e-6)
    assert got["busy_s"] == pytest.approx(165e-6)
    assert got["programs"] == 2          # jit_a started before the window
    assert got["op_s"] == pytest.approx({
        "fusion.0": 10e-6, "fusion.1": 30e-6, "while": 30e-6,
        "_expand_kernel": 10e-6, "fusion.2": 100e-6, "fusion.3": 5e-6})
    # ops are named by the program whose execution covers their start
    assert got["device_ops"][0] == ["jit_c(3): fusion.2",
                                    pytest.approx(100e-6)]
    assert ["jit_b(2): _expand_kernel", pytest.approx(10e-6)] \
        in got["device_ops"]


def test_gaps_go_to_the_innermost_host_span():
    gaps = dict(tr.reduce_trace(PLANES)["idle_gaps"])
    # [10,100): Select 10 + Scan 40 + Select 40; [150,400): Join 240 +
    # Select 10; [500,505) is short; [510,1000) has no caps_tpu span
    assert gaps == pytest.approx({
        "caps_tpu.Select": 60e-6, "caps_tpu.Scan": 40e-6,
        "caps_tpu.Join": 240e-6, tr.NO_SPAN: 490e-6, tr.SHORT_GAPS: 5e-6})
    assert sum(gaps.values()) == pytest.approx((1000 - 165) * 1e-6)


def test_without_the_marker_there_is_no_window():
    assert tr.reduce_trace([p for p in PLANES if p[0] != "/host:CPU"]) is None


def test_an_op_outside_every_program_says_so():
    device = ("/device:TPU:0", [line for line in PLANES[0][1]
                                if line[0] != tr.MODULES_LINE])
    got = tr.reduce_trace([device, PLANES[2]])
    assert got["programs"] == 0
    assert got["device_ops"][0][0] == f"{tr.NO_PROGRAM}: fusion.2"


def test_no_device_operation_reads_as_nothing():
    assert tr.reduce_trace([PLANES[2]]) is None
    assert tr.reduce_trace([]) is None


def test_top_limits_the_lists():
    got = tr.reduce_trace(PLANES, top=2)
    assert len(got["device_ops"]) == 2 and len(got["idle_gaps"]) == 2
    assert len(got["op_s"]) == 6


def test_a_real_trace_file_loads(tmp_path):
    """The profiler's own file through ``load_xplane`` (CPU: host planes
    only, so the reduction finds no device and says so)."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_MARKER):
        jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    names = {n for _p, lines in planes for _l, evs in lines
             for n, _s, _d in evs}
    assert tr.WINDOW_MARKER in names
    assert tr.reduce_trace(planes) is None
    assert "/host:CPU" in [name for name, _lines in planes]

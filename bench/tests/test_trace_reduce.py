"""trace_reduce on hand-made timelines and on a trace recorded on the chip."""
import os

import tiny  # noqa: F401
from bench import trace_reduce as tr


def test_busy_gaps_and_attribution():
    ms = 1_000_000  # ns
    window = (0, 100 * ms)
    ops = {"/device:TPU:0": [("fusion.1", -5 * ms, 10 * ms),   # clipped
                             ("fusion.2", 8 * ms, 20 * ms),    # overlaps
                             ("dot.3", 50 * ms, 60 * ms),
                             ("dot.3", 90 * ms, 120 * ms)],    # clipped
           "/device:TPU:1": [("fusion.1", 0, 50 * ms)]}
    host = [("sample", 15 * ms, 55 * ms),
            ("dispatch", 20 * ms, 45 * ms),     # innermost over 20..50
            ("retire", 60 * ms, 95 * ms)]
    out = tr.reduce_planes(ops, host, window)
    # device 0 busy: 0..20, 50..60, 90..100 = 40 ms; device 1: 50 ms.
    assert abs(out["busy_s"] - 0.045) < 1e-12
    assert abs(out["window_s"] - 0.1) < 1e-12
    assert out["n_devices"] == 2
    ops0 = dict(out["device_ops"])
    assert abs(ops0["fusion.1"] - 0.010) < 1e-12
    assert abs(ops0["fusion.2"] - 0.012) < 1e-12
    assert abs(ops0["dot.3"] - 0.020) < 1e-12
    # gaps on device 0: 20..50 (mid 35: dispatch), 60..90 (mid 75: retire)
    gaps = dict(out["idle_gaps"])
    assert abs(gaps["dispatch"] - 0.030) < 1e-12
    assert abs(gaps["retire"] - 0.030) < 1e-12
    # per-step device ms is busy over steps: 45 ms over 3 steps
    assert abs(1e3 * out["busy_s"] / 3 - 15.0) < 1e-9


def test_uncovered_gap():
    out = tr.reduce_planes({"/device:TPU:0": [("a", 0, 10)]}, [], (0, 30))
    assert dict(out["idle_gaps"]) == {tr.NO_SPAN: 20e-9}


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "cpu_trace",
                       "trace.xplane.pb")


def test_recorded_trace():
    """A profiler trace recorded on the CPU: inside one ``bench.window``,
    three jitted matmuls each under a ``dispatch`` span, each followed by
    30 ms of host sleep under ``host_wait``. The CPU has no device plane,
    so the device is taken busy exactly during the recorded dispatches."""
    ops, host, window = tr.read_xplane(FIXTURE, "bench.window",
                                       ["dispatch", "host_wait"])
    assert ops == {}
    assert window is not None
    names = [n for n, _, _ in sorted(host, key=lambda h: h[1])]
    assert names == ["dispatch", "host_wait"] * 3
    waits = [(e - s) * 1e-9 for n, s, e in host if n == "host_wait"]
    assert all(w >= 0.03 for w in waits)
    busy = [("matmul", s, e) for n, s, e in host if n == "dispatch"]
    out = tr.reduce_planes({"/device:TPU:0": busy}, host, window)
    want_busy = sum(e - s for _, s, e in busy) * 1e-9
    assert abs(out["busy_s"] - want_busy) < 1e-12
    assert abs(out["window_s"] - (window[1] - window[0]) * 1e-9) < 1e-12
    assert abs(1e3 * out["busy_s"] / 3 - 1e3 * want_busy / 3) < 1e-9
    gaps = dict(out["idle_gaps"])
    assert max(gaps, key=gaps.get) == "host_wait"
    assert gaps["host_wait"] >= sum(waits) - 1e-3
    assert dict(out["device_ops"])["matmul"] == want_busy

"""Per-layer metrics of a ``--trace 1`` run.

``read(run)`` reduces the profiler's trace (``trace_reduce``), builds one
``Context`` and asks each per-layer metric of the cell, by name, for its
value: ``bench/metrics/<name>.py`` defines ``read(ctx)``, which returns a
number or None when it finds nothing to read (the metric is then left out).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import harness, peaks, trace_reduce


class Context:
    """What a metric reader may read: the program's spans inside the
    window, the run's counters (``info``), the device trace reduced to the
    window (``device``), the operations counted for the window's work
    (``ops``) and the chip's peaks."""

    def __init__(self, run, spans: List[dict], device: Optional[Dict]):
        self.seconds = run.args.seconds
        self.info = run.window_info
        self.device = device
        self.n_chips = len(run.devices)
        self.peak_flops = peaks.peaks_for(
            run.devices[0].device_kind)["bf16_flops"]
        self._spans = spans

    def span_seconds(self, name: str) -> List[float]:
        """Durations (s) of the program's spans named ``name`` that lie
        inside the window."""
        return [ev["dur"] * 1e-6 for ev in self._spans if ev["name"] == name]

    def mean_ms(self, name: str) -> Optional[float]:
        d = self.span_seconds(name)
        return 1e3 * sum(d) / len(d) if d else None

    def mfu(self) -> Optional[float]:
        ops = self.info.get("ops")
        if not ops:
            return None
        return 100.0 * ops / self.seconds / self.n_chips / self.peak_flops


def _window_spans(run) -> List[dict]:
    from repro.obs.trace import TRACER

    info = run.window_info
    lo = (info["t0"] - TRACER.epoch) * 1e6
    hi = (info["t_end"] - TRACER.epoch) * 1e6
    return [ev for ev in run.tracer.events()
            if ev.get("ph") == "X" and ev["ts"] >= lo
            and ev["ts"] + ev["dur"] <= hi]


def read(run) -> Tuple[Dict, Optional[Dict], Dict]:
    spans = _window_spans(run)
    device = None
    path = run.tracer.xplane()
    if path is not None:
        names = {ev["name"] for ev in run.tracer.events()
                 if ev.get("ph") == "X"}
        ops, host, window = trace_reduce.read_xplane(
            path, harness.TraceSession.WINDOW, sorted(names))
        if window is not None and ops:
            device = trace_reduce.reduce_planes(ops, host, window)
    run.tracer.cleanup(keep=run.args.keep_trace)
    ctx = Context(run, spans, device)
    metrics = {}
    for m in harness.cell_metrics(run.bench, run.cell["name"], "per_layer"):
        value = harness.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if device is None:
        raise RuntimeError("the trace holds no device operation in the "
                           "window")
    breakdown = {"device_ops": device["device_ops"],
                 "idle_gaps": device["idle_gaps"]}
    busy = {"busy_s": device["busy_s"], "window_s": device["window_s"]}
    harness.log(f"bench: traced window {device['window_s']:.3f}s, device "
                f"busy {device['busy_s']:.3f}s; top device ops "
                f"{device['device_ops'][:5]}; idle by host span "
                f"{device['idle_gaps'][:5]}")
    return metrics, breakdown, busy

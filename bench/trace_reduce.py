"""From the profiler's trace to device busy time, top device operations and
idle gaps by the host span that covers them.

Reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` (nothing but JAX).
The window is the host event named ``TraceSession.WINDOW``. Device planes
are the planes named ``/device:<platform>:<n>``; their operations are the
events of the ``XLA Ops`` line (all lines but the step and module lines
where a plane has no such line). Busy time is the union of the operations'
intervals inside the window, per device; gaps are the complements.

Each gap of device 0 is charged to the host span that covers its midpoint
and began last (the innermost span running then); a gap no span covers is
charged to ``(no host span)``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_OP_LINES = ("XLA Ops",)
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code", "SparseCore")
NO_SPAN = "(no host span)"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Seconds of gap per covering host span name (see module doc)."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        name = NO_SPAN
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and starts[i] >= mid - longest:
            if spans[i][2] >= mid:          # latest-starting cover
                name = spans[i][0]
                break
            i -= 1
        out[name] += (e - s) * 1e-9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce_planes(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Interval) -> Dict:
    """``device_ops``: per device, (op name, start ns, end ns); host spans
    likewise; ``window`` in the same ns. Returns busy/window seconds, the
    top device operations by time and the idle gaps by host span."""
    lo, hi = window
    busy_s, per_dev_gaps = [], []
    op_time: Dict[str, float] = defaultdict(float)
    for i, (dev, ops) in enumerate(sorted(device_ops.items())):
        iv = clip([(s, e) for _, s, e in ops], lo, hi)
        merged = merge(iv)
        busy_s.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            per_dev_gaps = gaps(merged, lo, hi)
            for name, s, e in ops:
                c = clip([(s, e)], lo, hi)
                if c:
                    op_time[name] += (c[0][1] - c[0][0]) * 1e-9
    spans = [s for s in host_spans if s[2] > lo and s[1] < hi]
    return {
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": top(op_time),
        "idle_gaps": top(attribute(per_dev_gaps, spans)),
        "n_devices": len(busy_s),
    }


def read_xplane(path: str, window_name: str, span_names: Sequence[str]
                ) -> Tuple[Dict[str, List], List, Optional[Interval]]:
    """(device ops per device plane, host spans named in ``span_names``,
    the window interval) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List] = {}
    host: List = []
    window: Optional[Interval] = None
    names = set(span_names)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            chosen = [l for l in lines if l.name in DEVICE_OP_LINES]
            if not chosen:
                chosen = [l for l in lines if l.name not in SKIP_LINES]
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for l in chosen for e in l.events]
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in names:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return device_ops, host, window

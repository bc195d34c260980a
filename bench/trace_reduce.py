"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle time.

Two stages, so the arithmetic can be tested without a chip:

1. ``read_xplane`` pulls out what the reduction needs: the intervals of every
   operation on each device (the ``XLA Ops`` line of each ``/device:TPU:<n>``
   plane) and the harness's own host spans (``jax.profiler.TraceAnnotation``
   names in ``HOST_SPANS``, and ``WINDOW_SPAN`` around the traced window).
2. ``reduce`` works on those intervals alone: the busy union per device
   (averaged over devices), the idle share of the window, the operations that
   took most device time, and the longest idle gaps of the first device, each
   labelled by the host span that overlaps it most. ``busy_between`` then
   gives the busy time inside any part of the window, such as one engine step.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("engine_step", "submit", "await_arrival")
NO_SPAN = "between_spans"  # idle while the host was in none of HOST_SPANS
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class TraceEvents:
    device_ops: dict = field(default_factory=dict)  # device -> [(start_ns, end_ns, name)]
    host: list = field(default_factory=list)  # [(start_ns, end_ns, name)]


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = TraceEvents()
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX) :].isdigit():
            ops = out.device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        out.host.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    merged: list = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b, *_ in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class SpanIndex:
    """Host spans merged per name, for the overlap of many gaps at once."""

    def __init__(self, host: list):
        self.names = [n for n in HOST_SPANS if any(h[2] == n for h in host)]
        self.merged = {n: union([h for h in host if h[2] == n], float("-inf"), float("inf")) for n in self.names}
        self.ends = {n: [e for _, e in m] for n, m in self.merged.items()}

    def overlap(self, name: str, a: float, b: float) -> float:
        m, total = self.merged[name], 0.0
        for i in range(bisect.bisect_right(self.ends[name], a), len(m)):
            s, e = m[i]
            if s >= b:
                break
            total += min(b, e) - max(a, s)
        return total

    def label(self, a: float, b: float) -> str:
        """The host span that overlaps ``[a, b)`` most, else ``NO_SPAN``."""
        best, name = 0.0, NO_SPAN
        for n in self.names:
            ov = self.overlap(n, a, b)
            if ov > best:
                best, name = ov, n
        return name


def window_of(ev: TraceEvents) -> tuple:
    spans = [(s, e) for s, e, n in ev.host if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    every = [x for ops in ev.device_ops.values() for x in ops] + ev.host
    if not every:
        raise ValueError("trace holds no events")
    return min(x[0] for x in every), max(x[1] for x in every)


def reduce(ev: TraceEvents, top: int = 10) -> dict:
    """Busy and idle time of the traced window, in seconds.

    Returns ``busy_s`` (busy union averaged over devices), ``window_s``,
    ``idle_share`` (1 - busy/window), ``device_ops`` (the ``top`` operations
    by summed device time, over all devices, as ``[name, seconds]``),
    ``idle_gaps`` (the ``top`` longest gaps of the first device as
    ``[host span, seconds]``), ``idle_by_span`` (all of that device's idle
    time summed by host span), and for ``busy_between`` the window's bounds
    ``window_ns`` and each device's busy intervals ``busy_union`` (ns)."""
    if not ev.device_ops:
        raise ValueError("trace holds no device plane")
    lo, hi = window_of(ev)
    window = hi - lo
    if window <= 0:
        raise ValueError("empty traced window")
    busy_per_device = {}
    op_time: dict = {}
    for dev, ops in ev.device_ops.items():
        busy_per_device[dev] = union(ops, lo, hi)
        for a, b, name in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
    busy = sum(sum(b - a for a, b in u) for u in busy_per_device.values()) / len(busy_per_device)
    first = sorted(busy_per_device, key=lambda n: int(n[len(DEVICE_PREFIX) :]))[0]
    idle = gaps(busy_per_device[first], lo, hi)
    index = SpanIndex(ev.host)
    labelled = sorted(((index.label(a, b), b - a) for a, b in idle), key=lambda x: -x[1])
    by_span: dict = {}
    for name, d in labelled:
        by_span[name] = by_span.get(name, 0.0) + d / 1e9
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window,
        "devices": len(busy_per_device),
        "device_ops": [[n, t / 1e9] for n, t in sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, d / 1e9] for n, d in labelled[:top]],
        "idle_by_span": by_span,
        "window_ns": (lo, hi),
        "busy_union": busy_per_device,
    }


def busy_between(reduced: dict, a: float, b: float) -> float:
    """Seconds the devices were busy inside ``[a, b)`` (trace ns, clipped to
    the window), averaged over devices."""
    total = 0.0
    for merged in reduced["busy_union"].values():
        i = bisect.bisect_right([e for _, e in merged], a)
        for s, e in merged[i:]:
            if s >= b:
                break
            total += min(b, e) - max(a, s)
    return total / len(reduced["busy_union"]) / 1e9

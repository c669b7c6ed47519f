"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

One pass gives everything the per-layer readers and the result's
``breakdown`` use: the traced window, the device's busy time (the union
of the intervals in which an operation ran, averaged over the chips
used), device seconds per operation name and per compiled program, and
the device's idle time, each piece of a gap attributed to the innermost
benchmark span (``bench.*``) that the host had open over it.

On a TPU the device planes are ``/device:TPU:<n>``; their operations are
on the line ``XLA Ops`` and their compiled programs on ``XLA Modules``.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


def tpu_ops(line: str) -> bool:
    return line == "XLA Ops"


def tpu_modules(line: str) -> bool:
    return line == "XLA Modules"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    op_s: Dict[str, float] = field(default_factory=dict)
    module_s: Dict[str, List[float]] = field(default_factory=dict)
    gap_s: Dict[str, float] = field(default_factory=dict)
    spans: Dict[str, List[float]] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> List[List]:
        return [[k, v] for k, v in sorted(self.gap_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def module_events(self, match: Callable[[str], bool]) -> List[float]:
        return [d for k, ds in self.module_s.items() if match(k) for d in ds]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(path: str, *,
                 device_plane: Callable[[str], bool] = tpu_plane,
                 op_line: Callable[[str], bool] = tpu_ops,
                 module_line: Callable[[str], bool] = tpu_modules,
                 ) -> Optional[TraceSummary]:
    """None where the trace holds no device operation."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    per_device: List[List[Tuple[float, float]]] = []
    op_s: Dict[str, float] = {}
    module_s: Dict[str, List[float]] = {}
    for plane in pd.planes:
        is_dev = device_plane(plane.name)
        ops: List[Tuple[float, float]] = []
        for line in plane.lines:
            is_op = is_dev and op_line(line.name)
            is_mod = is_dev and module_line(line.name)
            for e in line.events:
                a, d = float(e.start_ns), float(e.duration_ns)
                if is_op and d > 0:
                    ops.append((a, a + d))
                    op_s[e.name] = op_s.get(e.name, 0.0) + d * 1e-9
                elif is_mod and d > 0:
                    module_s.setdefault(e.name, []).append(d * 1e-9)
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append((a, a + d, e.name))
        if ops:
            per_device.append(ops)
    if not per_device:
        return None
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        lo = min(a for ops in per_device for a, _ in ops)
        hi = max(b for ops in per_device for _, b in ops)
    busy = 0.0
    gap_s: Dict[str, float] = {}
    inner = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])
    for ops in per_device:
        u = _clip(union(ops), lo, hi)
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            # split the gap where a span starts or ends inside it, and give
            # each piece to the innermost span open over it
            cuts = sorted({a, b} | {x for s, t, _ in inner for x in (s, t)
                                    if a < x < b})
            for x, y in zip(cuts, cuts[1:]):
                mid = (x + y) / 2
                name = next((n for s, t, n in inner if s <= mid <= t),
                            "no benchmark span")
                gap_s[name] = gap_s.get(name, 0.0) + \
                    (y - x) * 1e-9 / len(per_device)
    span_s: Dict[str, List[float]] = {}
    for a, b, n in spans:
        span_s.setdefault(n, []).append((b - a) * 1e-9)
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy * 1e-9 / len(per_device),
                        devices=len(per_device), op_s=op_s,
                        module_s=module_s, gap_s=gap_s, spans=span_s)


class Tracer:
    """Profiler trace of a run's window, reduced when it stops."""

    def __init__(self, directory: str, **selectors):
        self.directory = directory
        self.selectors = selectors

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.directory)

    def stop(self) -> Optional[TraceSummary]:
        import jax
        jax.profiler.stop_trace()
        return reduce_trace(find_xplane(self.directory), **self.selectors)

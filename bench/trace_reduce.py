"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the device's module intervals),
device time by module, and the device's idle gaps with what the
benchmark's host spans were doing in them.

``extract`` reads the ``.xplane.pb`` the profiler wrote into plain lists,
which is also the form kept under ``tests/data/`` to check ``reduce``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."


def extract(trace_dir: str) -> dict:
    """{"device": [[line, name, start_ns, dur_ns], ...] of the "XLA
    Modules" line of the first TPU device plane (one event per jitted
    call; the op-level line holds thousands of events per chunk batch and
    is not read), "host": [[name, start_ns, dur_ns], ...] of every host
    event whose name starts with ``bench.``}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    dev_planes = sorted((p for p in pd.planes
                         if p.name.startswith("/device:TPU:")),
                        key=lambda p: p.name)
    if dev_planes:
        for line in dev_planes[0].lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                device.append([line.name, e.name, int(e.start_ns),
                               int(e.duration_ns)])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append([e.name, int(e.start_ns),
                                 int(e.duration_ns)])
    return {"device": device, "host": host}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


class Reduced:
    """The numbers of one traced window (nanoseconds inside)."""

    def __init__(self, events: dict):
        host = events["host"]
        win = [h for h in host if h[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        _, w0, wd = win[0]
        self.t0, self.t1 = w0, w0 + wd
        self.host = [h for h in host if h[0] != WINDOW_SPAN]
        self.modules = [(n, s, d) for line, n, s, d in events["device"]
                        if line == "XLA Modules"]
        self.busy = clip(union((s, s + d) for _, s, d in self.modules),
                         self.t0, self.t1)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy) / 1e9

    def module_seconds(self, names: Optional[Sequence[str]] = None
                       ) -> Dict[str, float]:
        """Device seconds inside the window by module name; with ``names``,
        only modules whose name contains one of them."""
        out: Dict[str, float] = {}
        for n, s, d in self.modules:
            if names is not None and not any(k in n for k in names):
                continue
            a, b = max(s, self.t0), min(s + d, self.t1)
            if b > a:
                out[n] = out.get(n, 0.0) + (b - a) / 1e9
        return out

    def idle_gaps(self) -> List[Tuple[int, int]]:
        gaps, pos = [], self.t0
        for a, b in self.busy:
            if a > pos:
                gaps.append((pos, a))
            pos = max(pos, b)
        if pos < self.t1:
            gaps.append((pos, self.t1))
        return gaps

    def idle_by_host_span(self) -> Dict[str, float]:
        """Idle seconds by the innermost ``bench.`` host span covering each
        gap's midpoint (``none`` where no span covers it)."""
        out: Dict[str, float] = {}
        for a, b in self.idle_gaps():
            mid = (a + b) // 2
            cover = [(d, n) for n, s, d in self.host if s <= mid < s + d]
            name = min(cover)[1] if cover else "none"
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]

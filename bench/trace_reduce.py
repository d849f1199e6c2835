"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (the union of each chip's module intervals),
device time by module, and the chips' idle gaps with what the
benchmark's host spans were doing in them.

``extract`` reads the ``.xplane.pb`` the profiler wrote into plain lists,
which is also the form kept under ``tests/data/`` to check ``reduce``.

A cell of ``k`` chips is read on every chip: its busy time is the mean of
the chips' (so the idle share is ``1 - sum_c busy_c / (k * window)``),
its idle gaps are every chip's, and each module's time is summed over the
chips.  A one-chip cell reads as it did before chips were told apart.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
TPU_PLANE = re.compile(r"/device:TPU:(\d+)$")
# The reduction reads the module line alone ("XLA Modules", one event per
# jitted call).  On a TPU v5e this mode records the same module events as
# the default, and the op line with them: no mode keeps the one without
# the other, so a traced run still grows with the chunk batches its
# window runs.
TRACE_MODE = "TRACE_ONLY_XLA"


def profile_options():
    """The profiler options of a traced run: the benchmark's and the
    program's host spans (``TraceAnnotation``, level 1), no Python
    tracer, and the TPU tracer in ``TRACE_MODE``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.advanced_configuration = {"tpu_trace_mode": TRACE_MODE}
    return opts


def _xplanes(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def xplane_bytes(trace_dir: str) -> int:
    """Bytes of the profiler's ``.xplane.pb`` files under ``trace_dir``."""
    return sum(os.path.getsize(p) for p in _xplanes(trace_dir))


def extract(trace_dir: str) -> dict:
    """{"device": [[line, name, start_ns, dur_ns, chip], ...] of the "XLA
    Modules" line of every ``/device:TPU:<chip>`` plane (one event per
    jitted call), "host": [[name, start_ns, dur_ns], ...] of every host
    event whose name starts with ``bench.``}."""
    from jax.profiler import ProfileData

    paths = _xplanes(trace_dir)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in pd.planes:
        chip = TPU_PLANE.match(plane.name)
        if chip is None:
            continue
        for line in plane.lines:
            if line.name != MODULE_LINE:
                continue
            for e in line.events:
                device.append([line.name, e.name, int(e.start_ns),
                               int(e.duration_ns), int(chip.group(1))])
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
    """The numbers of one traced window (nanoseconds inside) on the
    ``chips`` chips of a cell, numbered 0 to ``chips - 1``.  A device event
    without a chip (as ``extract`` wrote them before it read every chip)
    is chip 0's."""

    def __init__(self, events: dict, chips: int = 1):
        host = events["host"]
        win = [h for h in host if h[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        _, w0, wd = win[0]
        self.t0, self.t1 = w0, w0 + wd
        self.chips = chips
        self.host = [h for h in host if h[0] != WINDOW_SPAN]
        self.modules: Dict[int, List[Tuple[str, int, int]]] = {
            c: [] for c in range(chips)}
        for line, n, s, d, *chip in events["device"]:
            c = chip[0] if chip else 0
            if line == MODULE_LINE and c < chips:
                self.modules[c].append((n, s, d))
        self.busy = {c: clip(union((s, s + d) for _, s, d in mods),
                             self.t0, self.t1)
                     for c, mods in self.modules.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def n_modules(self) -> int:
        return sum(len(m) for m in self.modules.values())

    def chip_busy_s(self, chip: int) -> float:
        return sum(b - a for a, b in self.busy[chip]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(self.chip_busy_s(c) for c in self.busy) / self.chips

    def module_seconds(self, names: Optional[Sequence[str]] = None,
                       chip: Optional[int] = None) -> Dict[str, float]:
        """Device seconds inside the window by module name, on ``chip`` or
        summed over every chip; with ``names``, only modules whose name
        contains one of them."""
        out: Dict[str, float] = {}
        for c, mods in self.modules.items():
            if chip is not None and c != chip:
                continue
            for n, s, d in mods:
                if names is not None and not any(k in n for k in names):
                    continue
                a, b = max(s, self.t0), min(s + d, self.t1)
                if b > a:
                    out[n] = out.get(n, 0.0) + (b - a) / 1e9
        return out

    def idle_gaps(self, chip: int = 0) -> List[Tuple[int, int]]:
        gaps, pos = [], self.t0
        for a, b in self.busy[chip]:
            if a > pos:
                gaps.append((pos, a))
            pos = max(pos, b)
        if pos < self.t1:
            gaps.append((pos, self.t1))
        return gaps

    def idle_by_host_span(self) -> Dict[str, float]:
        """Idle seconds of every chip by the innermost ``bench.`` host span
        covering each gap's midpoint (``none`` where no span covers it);
        they sum to ``chips * (window_s - busy_s)``."""
        out: Dict[str, float] = {}
        for chip in self.busy:
            for a, b in self.idle_gaps(chip):
                mid = (a + b) // 2
                cover = [(d, n) for n, s, d in self.host
                         if s <= mid < s + d]
                name = min(cover)[1] if cover else "none"
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]

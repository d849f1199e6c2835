"""What the benchmark counts on the served path, from outside the program.

``MeteredExecutor`` stands between the serving fleet and its
``ReplicaSet``: every pass the fleet runs goes through it unchanged, and it
chains a counter onto the engine's batch-boundary hook.  At each boundary
the wave's scheduler has just admitted and retired its tenants, so the
live tenant columns it holds (``scheduler.active``) are the columns the
next chunk batch is multiplied against.

The host dispatches a pass's batches far ahead of the device (a whole
pass on a frozen full wave), so the time a batch is dispatched says little
about when it is done.  The meter books each batch at its completion time
on the device, worked out from what the host can time exactly: a pass
runs on the device from its first batch's dispatch until ``multiply``
returns (the engine blocks on the accumulator there), and the step costs
the same for every chunk, so the pass's batches are spread over that
interval in proportion to their chunk counts.  Inside a window this is
exact; at its two ends it interpolates within one pass.  The per-layer
readers take their window's work from those batches.

The end-to-end rate counts whole passes (``whole_passes``): the work of
the passes that ended in the window, over the time from the end of the
pass before the first of them to the end of the last.  Its span is a
whole number of pass periods, host work between passes included, so a
change to the period shows in full whatever the window's phase.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def batch_plan_sizes(store, chunk_batch: int) -> dict:
    """{first chunk of a batch: (nonzeros, chunks) of the batch} over the
    store's own batch plan (an optimized store splits batches at encoding
    runs)."""
    out = {}
    for start, count in store.batch_plan(chunk_batch):
        meta = store.read_batch_raw(start, count)[0]
        out[start] = (int(meta[:, 3].astype(np.int64).sum()), count)
    return out


class BatchMeter:
    """Per-batch records and the passes they belong to."""

    def __init__(self, sizes: dict, clock=time.perf_counter):
        self.sizes = sizes
        self.clock = clock
        # (dispatch time, batch nnz, live columns, capacity, chunks, pass)
        self.records: List[Tuple[float, int, int, int, int, int]] = []
        self.passes: Dict[int, List[Optional[float]]] = {}  # [first, end]
        self._lock = threading.Lock()

    def begin_pass(self) -> int:
        with self._lock:
            pid = len(self.passes)
            self.passes[pid] = [None, None]
            return pid

    def book(self, pid: int, chunk_start: int, live: int,
             capacity: int) -> None:
        nnz, chunks = self.sizes[chunk_start]
        t = self.clock()
        with self._lock:
            if self.passes[pid][0] is None:
                self.passes[pid][0] = t
            self.records.append((t, nnz, live, capacity, chunks, pid))

    def end_pass(self, pid: int) -> None:
        t = self.clock()
        with self._lock:
            self.passes[pid][1] = t

    def done(self) -> List[Tuple[float, int, int, int]]:
        """(estimated device completion time, nnz, live, capacity) of every
        batch of every ended pass."""
        with self._lock:
            recs, passes = list(self.records), dict(self.passes)
        by_pass: Dict[int, list] = {}
        for r in recs:
            by_pass.setdefault(r[5], []).append(r)
        out = []
        for pid, rs in by_pass.items():
            first, end = passes[pid]
            if end is None:
                continue
            total = sum(r[4] for r in rs)
            cum = 0
            for _, nnz, live, cap, chunks, _ in rs:
                cum += chunks
                out.append((first + (end - first) * cum / total, nnz, live,
                            cap))
        return out

    def pass_ends(self) -> List[float]:
        """Host times at which the ended passes returned, in order."""
        with self._lock:
            return sorted(e for _, e in self.passes.values() if e is not None)

    def whole_passes(self, t0: float, t1: float
                     ) -> Optional[Tuple[int, float, int]]:
        """(edge-column products, seconds, passes) over the passes that
        ended in ``(t0, t1]``, from the last end at or before ``t0`` to
        the last end in the window; None without a pass on both sides."""
        with self._lock:
            recs, passes = list(self.records), dict(self.passes)
        before = [e for e in self.pass_ends() if e <= t0]
        inside = {pid for pid, (_, e) in passes.items()
                  if e is not None and t0 < e <= t1}
        if not before or not inside:
            return None
        work = sum(nnz * live for _, nnz, live, _, _, pid in recs
                   if pid in inside)
        last = max(passes[pid][1] for pid in inside)
        return work, last - before[-1], len(inside)

    def in_window(self, t0: float, t1: float):
        return [r for r in self.done() if t0 <= r[0] < t1]

    def edge_cols(self, t0: float, t1: float) -> int:
        """Sum over batches done in ``[t0, t1)`` of batch nnz times the
        live tenant columns it was multiplied against."""
        return sum(nnz * live for _, nnz, live, _ in self.in_window(t0, t1))


class MeteredExecutor:
    """The ``ReplicaSet`` the fleet serves from, with the batch meter
    chained onto every boundary hook; every other attribute is the
    replica set's own.  ``annotate`` (a ``TraceAnnotation`` when the run
    is traced) names each pass and each boundary's scheduler work on the
    host timeline."""

    def __init__(self, replicas, meter: BatchMeter, annotate=None):
        self._replicas = replicas
        self._meter = meter
        self._annotate = annotate

    def __getattr__(self, name):
        return getattr(self._replicas, name)

    def multiply(self, x, *, boundary_hook=None, **kw):
        sched = getattr(boundary_hook, "__self__", None)
        span = self._annotate or _no_span
        meter = self._meter
        pid = meter.begin_pass()
        if boundary_hook is not None and hasattr(sched, "active"):
            inner, cap = boundary_hook, x.shape[1]

            def boundary_hook(b):
                with span("bench.boundary"):
                    inner(b)
                meter.book(pid, b.chunk_start,
                           sum(s.width for s in sched.active), cap)
        with span("bench.pass"):
            y = self._replicas.multiply(x, boundary_hook=boundary_hook, **kw)
        meter.end_pass(pid)
        return y


@contextlib.contextmanager
def _no_span(name):
    yield


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q`` quantile of all values by linear interpolation between
    order statistics (``statistics.quantiles``' inclusive method); None
    when there are none."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = q * (len(vals) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))

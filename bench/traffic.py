"""The one load generator.  A traffic mix is a JSON file of parameters
under ``traffic/``; this module reads every mix.

* ``"loop": "closed"`` — ``clients`` callers (``"capacity"``: as many as
  the fleet's waves hold at ``cols_per_request`` columns each), each
  resubmitting the moment its answer arrives.
* ``"loop": "open"`` — one-shot requests due on a schedule at
  ``rate_per_s``, whatever the system does.  The schedule is a ramp of
  ``ramp_s`` seconds and then the window: each holds
  ``round(rate_per_s * span)`` arrivals whose gaps are that many
  exponential quantiles, scaled so the arrivals and one mean gap more fill
  the span, and put in another order by each seed.  Every seed offers the
  same arrivals in each span, so every window owes as many requests.

Each request multiplies ``cols_per_request`` columns drawn without
replacement from a seeded pool of ``pool_cols`` operand columns.  A seeded
share ``sample_share`` of the requests (at most ``max_sampled``) keep
their answers for the comparison after the window.  One thread submits
and collects; completion times are stamped by a done-callback on the
serving thread that completes the ticket.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

MAX_REQUESTS = 1 << 15


@dataclasses.dataclass
class Request:
    k: int
    idx: np.ndarray
    due: float
    submitted: float = 0.0
    done: Optional[float] = None
    error: Optional[BaseException] = None
    result: Optional[np.ndarray] = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def plan(mix: dict, seed: int, seconds: float = 0.0):
    """Deterministic per-seed request plan: (columns of request k as a
    (K, p) int array, sample flag of request k, open-loop due offsets from
    the schedule's start or None).  An open loop's window of ``seconds``
    starts ``ramp_s`` after the schedule."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    p, pool = mix["cols_per_request"], mix["pool_cols"]
    K = MAX_REQUESTS
    idx = np.argsort(rng.random((K, pool)), axis=1)[:, :p]
    keep = rng.random(K) < mix["sample_share"]
    due = None
    if mix["loop"] == "open":
        parts, start = [], 0.0
        for span in (mix["ramp_s"], seconds):
            m = round(mix["rate_per_s"] * span)
            if m:
                gaps = -np.log1p(-(np.arange(m) + 0.5) / m)
                gaps *= span / (gaps.sum() + gaps.mean())
                parts.append(start + np.cumsum(rng.permutation(gaps)))
            start += span
        due = np.concatenate(parts) if parts else np.zeros(0)
    return idx, keep, due


class Load:
    """Drives one mix against a ``Submitter`` (the serving fleet)."""

    def __init__(self, fleet, mix: dict, pool_t: np.ndarray, seed: int,
                 clients: int, seconds: float = 0.0,
                 clock=time.perf_counter):
        self.fleet = fleet
        self.mix = mix
        self.pool_t = pool_t            # (pool_cols, n): rows are columns
        self.clock = clock
        self.clients = clients
        self.idx, self.keep, self.due = plan(mix, seed, seconds)
        self.requests: List[Request] = []
        self.lateness: List[float] = []
        self.kept = 0
        self.keep_after = float("inf")   # answers kept once the window opens
        self._stop = threading.Event()
        self._accepting = True
        self._thread: Optional[threading.Thread] = None
        self._t_sched = 0.0
        self.error: Optional[BaseException] = None

    # -- submission ---------------------------------------------------------
    def _submit(self, due: float, late: bool = False) -> None:
        from repro.runtime import SessionSpec

        k = len(self.requests)
        if k >= MAX_REQUESTS:
            raise RuntimeError("request plan exhausted")
        req = Request(k, self.idx[k], due)
        x = self.pool_t[req.idx].T          # (n, p), one contiguous gather
        req.submitted = self.clock()
        if late:
            self.lateness.append(req.submitted - due)
        ticket = self.fleet.submit(SessionSpec.multiply(x, tenant_id=str(k)))
        self.requests.append(req)
        clock = self.clock

        def stamp(t, req=req):
            if req.done is None:
                req.done = clock()
        ticket.add_done_callback(stamp)

    def _collect(self, timeout: float) -> None:
        t = self.fleet.deliver(timeout=timeout)
        while t is not None:
            req = self.requests[int(t.tenant_id)]
            if req.done is None:    # the ticket queues itself before stamp
                req.done = self.clock()
            try:
                y = t.wait(timeout=0)
                if (self.keep[req.k] and req.done >= self.keep_after
                        and self.kept < self.mix["max_sampled"]):
                    req.result = y
                    self.kept += 1
            except Exception as e:  # noqa: BLE001 — counted as failed
                req.error = e
            if self.mix["loop"] == "closed" and self._accepting:
                self._submit(self.clock())
            t = self.fleet.deliver(timeout=0)

    def _run(self) -> None:
        try:
            if self.mix["loop"] == "closed":
                for _ in range(self.clients):
                    self._submit(self.clock())
                while not self._stop.is_set():
                    self._collect(0.05)
                return
            j = 0
            while not self._stop.is_set():
                nxt = (self._t_sched + self.due[j] if self._accepting
                       and j < len(self.due) else None)
                now = self.clock()
                if nxt is not None and now >= nxt:
                    self._submit(nxt, late=True)
                    j += 1
                    continue
                wait = 0.05 if nxt is None else min(0.05, nxt - now)
                self._collect(wait)
        except BaseException as e:  # noqa: BLE001 — surfaced by the harness
            self.error = e

    # -- harness-facing -----------------------------------------------------
    @property
    def window_start(self) -> float:
        """When an open loop's window begins on the host clock: ``ramp_s``
        after ``start``."""
        return self._t_sched + self.mix["ramp_s"]

    def start(self) -> None:
        self._t_sched = self.clock()
        self._thread = threading.Thread(target=self._run, name="bench-load",
                                        daemon=True)
        self._thread.start()

    def close_submissions(self) -> None:
        """No new requests from now on (open: none due later; closed: no
        resubmission)."""
        self._accepting = False

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        # collect anything that completed after the thread's last look
        while self.fleet.deliver(timeout=0) is not None:
            pass

    def warm_up(self, n: int, timeout: float) -> None:
        """Serve ``n`` requests outside the plan and wait for them (before
        ``start``): the first pass of every wave, compiling what it
        needs."""
        from repro.runtime import SessionSpec

        p = self.mix["cols_per_request"]
        x = self.pool_t[:p].T
        tickets = [self.fleet.submit(SessionSpec.multiply(x))
                   for _ in range(n)]
        for t in tickets:
            t.wait(timeout=timeout)
        while self.fleet.deliver(timeout=0) is not None:
            pass

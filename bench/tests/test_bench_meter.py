"""Counting at the engine's batch boundary, and tails over all requests."""
import numpy as np

from bench import meter as M
from bench.traffic import Request


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_batches_are_booked_at_their_completion_on_the_device():
    """A pass dispatched all at once is spread over its device interval
    (first dispatch to the return of multiply) by chunk counts."""
    clock = _Clock()
    m = M.BatchMeter({0: (100, 4), 4: (50, 4), 8: (10, 2)}, clock=clock)
    pid = m.begin_pass()
    for start, live in [(0, 8), (4, 16), (8, 16)]:
        clock.t = 1.0                       # the host dispatches at once
        m.book(pid, start, live, 16)
    clock.t = 11.0
    m.end_pass(pid)                          # done 4/10, 8/10, 10/10 in
    assert [r[0] for r in m.done()] == [5.0, 9.0, 11.0]
    assert m.edge_cols(0.0, 6.0) == 100 * 8
    assert m.edge_cols(6.0, 12.0) == 50 * 16 + 10 * 16
    # a pass still running books nothing yet
    pid2 = m.begin_pass()
    m.book(pid2, 0, 16, 16)
    assert m.edge_cols(0.0, 100.0) == 800 + 960
    clock.t = 13.0
    m.end_pass(pid2)
    assert m.edge_cols(0.0, 100.0) == 800 + 960 + 1600
    assert len(m.in_window(10.0, 14.0)) == 2


def _passes(meter, clock, spans, work=(100, 4)):
    """Run one-batch passes over ``(start, end)`` host intervals."""
    for a, b in spans:
        pid = meter.begin_pass()
        clock.t = a
        meter.book(pid, 0, work[1], 16)
        clock.t = b
        meter.end_pass(pid)


def test_whole_passes_take_the_rate_over_pass_periods():
    """The rate counts the passes that ended in the window over the time
    since the pass before them ended: whole pass periods, host gaps
    included, whatever the window's phase."""
    clock = _Clock()
    m = M.BatchMeter({0: (100, 4)}, clock=clock)
    # device 6 s, host 11 s: a 17 s period
    _passes(m, clock, [(17 * k + 11, 17 * k + 17) for k in range(6)])
    for t0 in (17.0, 20.0, 33.9):            # any phase: 400 per 17 s
        work, secs, k = m.whole_passes(t0, t0 + 51)
        assert work / secs == 400 / 17
        assert secs == 17 * k
    # a host 5% slower shows as 5% less, though the window holds as
    # many passes as before
    slow = M.BatchMeter({0: (100, 4)}, clock=clock)
    _passes(slow, clock, [(17.85 * k + 11.85, 17.85 * k + 17.85)
                          for k in range(6)])
    work, secs, k = slow.whole_passes(17.85, 17.85 + 51)
    assert k == 2 and abs(work / secs / (400 / 17) - 1 / 1.05) < 1e-12
    # nothing before the window, or nothing in it: no reading
    assert m.whole_passes(0.0, 16.0) is None
    assert m.whole_passes(18.0, 30.0) is None


def test_metered_executor_books_live_columns_after_the_hook():
    """The meter chains onto the scheduler's hook and counts the tenants
    the scheduler holds once the hook has admitted or retired them."""

    class Sched:
        def __init__(self):
            self.active = []

        def hook(self, b):
            self.active.append(type("S", (), {"width": 4})())

    class Boundary:
        def __init__(self, cs):
            self.chunk_start = cs

    class Replicas:
        n_rows = 7

        def multiply(self, x, *, boundary_hook=None, **kw):
            for cs in (0, 2, 4):
                boundary_hook(Boundary(cs))
            return x

    m = M.BatchMeter({0: (10, 2), 2: (20, 2), 4: (30, 2)})
    ex = M.MeteredExecutor(Replicas(), m)
    sched = Sched()
    x = np.zeros((3, 16))
    assert ex.multiply(x, boundary_hook=sched.hook) is x
    assert [(nnz, live, cap) for _, nnz, live, cap in m.done()] == [
        (10, 4, 16), (20, 8, 16), (30, 12, 16)]
    assert ex.n_rows == 7                    # everything else delegates


def test_p95_counts_every_request_including_a_stall():
    """Open-loop latency runs from the due time, so a stall inside the
    window makes every request due during it late, and the tail sees
    them all."""
    reqs = []
    for k in range(100):
        due = 0.1 * k
        served = due + 1.0
        if 3.0 <= due < 5.0:                 # a 2 s stall holds these back
            served = 5.0 + 1.0
        reqs.append(Request(k, np.arange(2), due, done=served))
    lat = [r.latency for r in reqs]
    assert M.quantile(lat, 0.5) == 1.0
    assert M.quantile(lat, 0.95) > 2.5
    # the first stalled request waited the whole stall
    assert max(lat) == 3.0
    assert M.quantile([], 0.95) is None


def test_quantile_interpolates_like_statistics():
    import statistics
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    q = statistics.quantiles(vals, n=4, method="inclusive")
    assert np.allclose([M.quantile(vals, 0.25), M.quantile(vals, 0.5),
                        M.quantile(vals, 0.75)], q)

"""The load generator's plan is a function of the mix and the seed."""
import numpy as np

from bench import traffic

OPEN = {"loop": "open", "cols_per_request": 4, "pool_cols": 64,
        "rate_per_s": 5.0, "ramp_s": 10, "sample_share": 0.1}
CLOSED = {"loop": "closed", "cols_per_request": 8, "pool_cols": 64,
          "sample_share": 0.125}


def test_plan_is_deterministic_for_a_seed():
    for mix in (OPEN, CLOSED):
        a = traffic.plan(mix, 2**33 + 1, 51)
        b = traffic.plan(mix, 2**33 + 1, 51)
        for x, y in zip(a, b):
            assert (x is None and y is None) or np.array_equal(x, y)


def test_requests_draw_distinct_pool_columns():
    idx, keep, due = traffic.plan(CLOSED, 9)
    assert idx.shape == (traffic.MAX_REQUESTS, 8)
    assert all(len(set(r)) == 8 for r in idx[:500])
    assert idx.min() >= 0 and idx.max() < 64
    assert due is None
    assert 0.08 < keep.mean() < 0.17


def test_open_loop_offers_the_same_arrivals_in_another_order():
    _, _, d1 = traffic.plan(OPEN, 1, 51)
    _, _, d2 = traffic.plan(OPEN, 2, 51)
    g1, g2 = np.diff(d1, prepend=0.0), np.diff(d2, prepend=0.0)
    assert not np.array_equal(g1, g2)
    # the ramp's arrivals and the window's: the same sets in each
    assert np.allclose(np.sort(g1[:50]), np.sort(g2[:50]))
    w1, w2 = (np.diff(d[50:], prepend=10.0) for d in (d1, d2))
    assert np.allclose(np.sort(w1), np.sort(w2))
    # the window's set has the mix's mean rate
    assert abs(w1.mean() - 1 / OPEN["rate_per_s"]) < 0.01
    assert np.all(np.diff(d1) > 0) and d1[49] < 10.0


def test_every_window_owes_the_same_number_of_requests():
    """Whatever the seed, exactly round(rate * seconds) arrivals fall in
    the window ``[ramp_s, ramp_s + seconds)`` and round(rate * ramp_s)
    before it."""
    for seconds in (51, 12.3):
        for seed in (1, 2, 2**33 + 7, 3500000027):
            _, _, due = traffic.plan(OPEN, seed, seconds)
            win = (due >= 10) & (due < 10 + seconds)
            assert win.sum() == round(5.0 * seconds)
            assert (due < 10).sum() == 50
            assert len(due) == 50 + round(5.0 * seconds)

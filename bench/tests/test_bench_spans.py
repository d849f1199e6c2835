"""The program's spans in a trace (``bench/spans.py``): seconds of a span
in the window, the device's idle time split among the innermost spans,
and a traced rehearsal that reads them."""
import random

import pytest

from bench import run, spans, trace_reduce
from bench.tests.rehearsal import SIZES

MS = 1_000_000


def _reduced(host, device):
    return trace_reduce.Reduced({
        "host": [["bench.window", 0, 100 * MS]] + host,
        "device": [["XLA Modules", "jit__batch_step_binary", s, d]
                   for s, d in device]})


def _synthetic():
    """Busy [10, 20) and [50, 60); a pass that began before the window,
    a stream inside it with a step inside that, and two deliveries: one
    overlapping the pass's end, one crossing the window's end."""
    return _reduced(
        [["sem.pass", -20 * MS, 100 * MS], ["sem.stream", 5 * MS, 40 * MS],
         ["sem.step", 30 * MS, 10 * MS], ["sem.deliver", 70 * MS, 20 * MS],
         ["sem.deliver", 95 * MS, 35 * MS], ["sem.pack", 110 * MS, MS]],
        [(10 * MS, 10 * MS), (50 * MS, 10 * MS)])


def test_span_seconds_clip_at_the_window():
    r = _synthetic()
    assert spans.span_seconds(r, "sem.pass") == pytest.approx(0.08)
    assert spans.span_seconds(r, "sem.deliver") == pytest.approx(0.025)
    assert spans.span_seconds(r, "sem.pack") == 0
    assert spans.span_seconds(r, "sem.sync") == 0


def test_idle_gaps_are_split_among_the_innermost_spans():
    idle = spans.idle_by_span(_synthetic())
    assert idle == {
        "sem.pass": pytest.approx(0.020),     # [0, 5) [45, 50) [60, 70)
        "sem.stream": pytest.approx(0.020),   # [5, 10) [20, 30) [40, 45)
        "sem.step": pytest.approx(0.010),     # nested inside the stream
        "sem.deliver": pytest.approx(0.025),  # shorter than the pass
        "none": pytest.approx(0.005)}         # [90, 95): nothing covers it
    r = _synthetic()
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_a_window_without_spans_is_idle_under_none():
    r = _reduced([], [(10 * MS, 10 * MS)])
    assert spans.idle_by_span(r) == {"none": pytest.approx(0.09)}


@pytest.mark.parametrize("seed", range(6))
def test_idle_by_span_agrees_with_a_walk_over_every_instant(seed):
    """Random spans (same names and lengths included) and busy intervals on
    a grid of 1 ms: the sweep gives what looking at every instant gives."""
    rng = random.Random(seed)
    host = [[f"sem.{rng.choice('abc')}", rng.randrange(-20, 100) * MS,
             rng.randrange(1, 40) * MS] for _ in range(rng.randrange(1, 30))]
    device = [(rng.randrange(0, 100) * MS, rng.randrange(1, 15) * MS)
              for _ in range(rng.randrange(0, 8))]
    r = _reduced(host, device)
    want = {}
    busy = r.busy
    for t in range(0, 100 * MS, MS):
        if any(a <= t < b for a, b in busy):
            continue
        cover = [(d, n) for n, s, d in host if s <= t < s + d]
        name = min(cover)[1] if cover else "none"
        want[name] = want.get(name, 0.0) + 1e-3
    got = spans.idle_by_span(r)
    assert set(got) == set(want)
    for name, v in want.items():
        assert got[name] == pytest.approx(v)


@pytest.mark.parametrize("cell", ["g22-full", "g22p-full"])
def test_traced_rehearsal_reads_the_programs_spans(cell, tmp_path):
    lines, extract, data = [], trace_reduce.extract, run.RunData
    result = spans.traced_run(run.load_cell(cell), 5, 2.0, lines.append,
                              rehearsal=SIZES,
                              workdir=str(tmp_path / "data"))
    assert result["correct"], lines
    assert trace_reduce.extract is extract and run.RunData is data
    n = 1 << SIZES["graph"]["scale"]
    cap = SIZES["fleet"]["capacity"]
    # the pass's product, plus whatever mid-pass reads took
    assert (result["metrics"]["d2h_mb_per_pass.full"]["value"]
            >= 4 * n * cap / 1e6)
    sp = result["spans"]
    assert sp["passes_booked"] > 0
    for name in spans.SPANS:
        assert sp["ms_per_pass"][name] > 0, name
    assert any(name.startswith(spans.PREFIX)
               for name, _ in sp["idle_by_span"])
    assert 0 < sp["idle_named_share"] <= 1
    assert sp["program_events"] > 0 and sp["xplane_bytes"] > 0
    assert sp["args"]["sem.pack"]["bytes"] == 4 * n * cap

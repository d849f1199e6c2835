"""The reduction from a profiler trace to device busy time, module time
and idle gaps."""
import json
import os

import pytest

from bench import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")


def _synthetic():
    ms = 1_000_000
    return {
        "host": [["bench.window", 0, 100 * ms], ["bench.pass", 0, 60 * ms],
                 ["bench.boundary", 30 * ms, 15 * ms]],
        "device": [
            ["XLA Modules", "jit__batch_step_binary", 5 * ms, 20 * ms],
            ["XLA Modules", "jit_scatter", 20 * ms, 10 * ms],
            ["XLA Modules", "jit_dynamic_slice", 50 * ms, 10 * ms],
            ["XLA Modules", "jit__batch_step_binary", 90 * ms, 20 * ms]]}


def test_busy_is_the_union_of_ops_inside_the_window():
    r = TR.Reduced(_synthetic())
    assert r.window_s == pytest.approx(0.1)
    # [5, 30] + [50, 60] + [90, 100] (clipped at the window's end)
    assert r.busy_s == pytest.approx(0.045)
    steps = r.module_seconds(("_batch_step",))
    assert steps == {"jit__batch_step_binary": pytest.approx(0.03)}


def test_idle_gaps_are_named_by_the_host_span_inside_them():
    r = TR.Reduced(_synthetic())
    assert r.idle_gaps() == [(0, 5_000_000), (30_000_000, 50_000_000),
                             (60_000_000, 90_000_000)]
    idle = r.idle_by_host_span()
    assert idle["bench.pass"] == pytest.approx(0.005)
    assert idle["bench.boundary"] == pytest.approx(0.02)
    assert idle["none"] == pytest.approx(0.03)
    assert TR.top(idle, 2)[0] == ["none", pytest.approx(0.03)]


def test_union_merges_overlaps():
    assert TR.union([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def test_a_trace_without_the_window_span_is_refused():
    ev = _synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        TR.Reduced(ev)


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5 lite by a small traced run: the
    reduction gives the numbers that run reported."""
    with open(os.path.join(DATA, "v5e_scale16_trace.json")) as f:
        r = TR.Reduced(json.load(f))
    assert r.window_s == pytest.approx(3.000052847)
    assert r.busy_s == pytest.approx(0.495890744)
    steps = r.module_seconds(("_batch_step",))
    assert list(steps) == ["jit__batch_step_binary(8630926794372898878)"]
    assert sum(steps.values()) == pytest.approx(0.4806514020000005)
    idle = r.idle_by_host_span()
    assert set(idle) == {"bench.pass", "bench.boundary", "none"}
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)

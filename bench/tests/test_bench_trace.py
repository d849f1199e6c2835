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


def _four_chips():
    """Chip 0 busy [0, 40), chip 1 [10, 30) and [90, 120), chip 2 idle,
    chip 3 [0, 100); events of a fifth chip the cell does not use."""
    ms = 1_000_000
    dev = [["XLA Modules", "jit__batch_step_binary", 0, 40 * ms, 0],
           ["XLA Modules", "jit__batch_step_binary", 10 * ms, 20 * ms, 1],
           ["XLA Modules", "jit_scatter", 90 * ms, 30 * ms, 1],
           ["XLA Modules", "jit__batch_step_binary", 0, 100 * ms, 3],
           ["XLA Modules", "jit__batch_step_binary", 0, 100 * ms, 4]]
    host = [["bench.window", 0, 100 * ms], ["bench.pass", 50 * ms, 50 * ms]]
    return {"host": host, "device": dev}


def test_every_chip_of_a_cell_is_read():
    r = TR.Reduced(_four_chips(), chips=4)
    assert [r.chip_busy_s(c) for c in range(4)] == pytest.approx(
        [0.04, 0.03, 0.0, 0.1])
    # 1 - sum_c busy_c / (k * window)
    assert 1 - r.busy_s / r.window_s == pytest.approx(1 - 0.17 / 0.4)
    assert r.module_seconds(("_batch_step",), chip=3) == {
        "jit__batch_step_binary": pytest.approx(0.1)}
    assert r.module_seconds() == {
        "jit__batch_step_binary": pytest.approx(0.16),
        "jit_scatter": pytest.approx(0.01)}
    assert r.idle_gaps(1) == [(0, 10_000_000), (30_000_000, 90_000_000)]
    idle = r.idle_by_host_span()
    assert sum(idle.values()) == pytest.approx(4 * (r.window_s - r.busy_s))
    # chip 0 [40, 100) mid 70, chip 1 [30, 90) mid 60, chip 2 [0, 100)
    # mid 50: under the pass; chip 1 [0, 10): none
    assert idle == {"bench.pass": pytest.approx(0.22),
                    "none": pytest.approx(0.01)}
    assert r.n_modules == 4


def test_the_pass_is_paced_by_the_chip_with_the_most_step_time():
    from types import SimpleNamespace

    from bench import roofline

    r = TR.Reduced(_four_chips(), chips=4)
    run = SimpleNamespace(trace=r, chips=4, n=1 << 10, nnz=1 << 14,
                          peaks={"flops_per_s": 1e12,
                                 "hbm_bytes_per_s": 1e9})
    assert roofline.step_seconds(run) == pytest.approx(0.1)
    one = SimpleNamespace(**dict(vars(run), chips=1))
    E, Z = 1 << 20, 1 << 14
    assert roofline.bound_seconds(one, E, Z) == pytest.approx(
        (8 * (1 << 10) * E / (1 << 14) + Z) / 1e9)
    assert roofline.bound_seconds(run, E, Z) == pytest.approx(
        roofline.bound_seconds(one, E, Z) / 4)


class _Event:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_extract_reads_the_module_line_of_every_tpu_plane(tmp_path,
                                                          monkeypatch):
    import jax

    (tmp_path / "host.xplane.pb").write_bytes(b"")
    planes = [
        _Plane("/host:CPU", [_Line("main", [
            _Event("bench.window", 0, 100), _Event("sem.pass", 5, 10),
            _Event("bench.pass", 5, 20)])]),
        _Plane("/device:TPU:1", [
            _Line("XLA Modules", [_Event("jit_b", 3, 4)]),
            _Line("XLA Ops", [_Event("fusion", 3, 1)])]),
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Event("jit_a", 1, 2)]),
            _Line("Steps", [_Event("1", 0, 9)])]),
        _Plane("/device:TPU:0 SparseCore 0", [
            _Line("XLA Modules", [_Event("jit_c", 1, 2)])])]

    class _PD:
        @staticmethod
        def from_file(path):
            assert path.endswith(".xplane.pb")
            return type("PD", (), {"planes": planes})

    monkeypatch.setattr(jax.profiler, "ProfileData", _PD)
    ev = TR.extract(str(tmp_path))
    assert sorted(ev["device"], key=lambda e: e[4]) == [
        ["XLA Modules", "jit_a", 1, 2, 0], ["XLA Modules", "jit_b", 3, 4, 1]]
    assert ev["host"] == [["bench.window", 0, 100], ["bench.pass", 5, 20]]
    assert TR.Reduced(ev, chips=2).n_modules == 2


def test_profile_options_ask_for_the_chosen_trace_mode():
    opts = TR.profile_options()
    assert opts.advanced_configuration == {"tpu_trace_mode": TR.TRACE_MODE}
    assert opts.host_tracer_level == 1 and opts.python_tracer_level == 0

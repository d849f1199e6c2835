"""The serving path's host spans (``repro.trace``) as the profiler records
them, and the device-to-host byte counter."""
import jax
import numpy as np
import pytest

from bench.spans import PREFIX as BENCH_PREFIX
from bench.spans import program_events
from repro import trace
from repro.core.formats import to_chunked
from repro.core.sem import SEMConfig, SEMSpMM
from repro.io.storage import IOStats, TileStore
from repro.runtime import (MultiplyRequest, PowerIterationSession,
                           ServingFleet, SharedScanScheduler)
from repro.runtime.session import SessionSpec

BATCH = 16
PER_PASS = ("pass", "pack", "prepare_x", "stream", "sync", "copyback",
            "deliver")
PER_BATCH = ("stage", "step", "boundary")


@pytest.fixture(scope="module")
def store_path(small_graph, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("spans") / "g")
    TileStore.write(path, to_chunked(small_graph, T=512, C=128))
    return path


def _sem(store_path):
    return SEMSpMM(TileStore.open(store_path), SEMConfig(chunk_batch=BATCH))


def _events(trace_dir):
    """{span name without prefix: [(start, end), ...] in start order}."""
    out = {}
    for name, s, d, _ in program_events(str(trace_dir)):
        out.setdefault(name[len(trace.PREFIX):], []).append((s, s + d))
    return {k: sorted(v) for k, v in out.items()}


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_the_benchmark_reads_the_programs_prefix():
    assert trace.PREFIX == BENCH_PREFIX == "sem."


def test_elastic_passes_record_each_span_at_its_layer(store_path, tmp_path):
    sem = _sem(store_path)
    n_batches = len(sem.store.batch_plan(BATCH))
    sched = SharedScanScheduler(sem, use_cache=False, elastic=True)
    rng = np.random.default_rng(3)
    sched.submit(PowerIterationSession(
        rng.standard_normal(sem.n_cols).astype(np.float32), tol=0.0,
        max_iter=2))
    with jax.profiler.trace(str(tmp_path)):
        reports = sched.run()
    assert len(reports) == 2
    ev = _events(tmp_path)
    for name in PER_PASS:
        assert len(ev[name]) == 2, name
    for name in PER_BATCH:
        assert len(ev[name]) == 2 * n_batches, name
    # every batch's read, and the read that finds the stream's end
    assert len(ev["read_wait"]) == 2 * (n_batches + 1)
    for k, outer in enumerate(ev["pass"]):
        for name in ("pack", "prepare_x", "stream", "sync", "copyback",
                     "deliver"):
            assert _inside(ev[name][k], outer), name
        stream = ev["stream"][k]
        for name in PER_BATCH + ("read_wait",):
            mine = [e for e in ev[name] if _inside(e, outer)]
            assert mine and all(_inside(e, stream) for e in mine), name
    args = {name: a for name, _, _, a in program_events(str(tmp_path))}
    assert args["sem.pass"]["tenants"] == 1
    assert args["sem.prepare_x"]["bytes"] == 4 * sem.padded_cols * (
        sched.capacity)
    assert args["sem.copyback"]["bytes"] == 4 * sem.n_rows * sched.capacity


def test_the_fleets_wave_thread_waits_under_its_span(store_path, tmp_path):
    sem = _sem(store_path)
    x = np.random.default_rng(5).standard_normal(
        (sem.n_cols, 2)).astype(np.float32)
    with jax.profiler.trace(str(tmp_path)):
        with ServingFleet(sem, n_waves=1, use_cache=False) as fleet:
            for _ in range(2):
                fleet.submit(SessionSpec.multiply(x)).wait(timeout=120)
            fleet.drain(timeout=120)
    ev = _events(tmp_path)
    assert len(ev["pass"]) == 2
    assert ev["wave_wait"]
    for w in ev["wave_wait"]:
        assert not any(a < w[1] and w[0] < b for a, b in ev["pass"])


def test_d2h_bytes_count_the_copy_back_of_each_classic_pass(store_path):
    sem = _sem(store_path)
    sched = SharedScanScheduler(sem, use_cache=False, elastic=False)
    rng = np.random.default_rng(7)
    p = 3
    before = sem.io_stats.d2h_bytes
    for k in range(1, 3):
        sched.submit(MultiplyRequest(
            rng.standard_normal((sem.n_cols, p)).astype(np.float32)))
        sched.run()
        assert sem.io_stats.d2h_bytes - before == k * sem.n_rows * p * 4
    # the fleet's aggregate and the cross-host heartbeat carry it
    d2h = sem.io_stats.d2h_bytes
    assert IOStats.aggregate([sem.io_stats, sem.io_stats]).d2h_bytes == 2 * d2h
    assert IOStats.from_dict(sem.io_stats.to_dict()).d2h_bytes == d2h


def test_d2h_bytes_count_mid_pass_reads(store_path):
    sem = _sem(store_path)
    x = np.ones((sem.n_cols, 2), np.float32)
    read = []

    def hook(b):
        if not read and b.chunk_start > 0:
            read.append(b.read_output(1, 0, 2).nbytes)

    before = sem.io_stats.d2h_bytes
    sem.multiply(x, boundary_hook=hook)
    assert read == [sem.T * 2 * 4]
    assert sem.io_stats.d2h_bytes - before == sem.n_rows * 2 * 4 + read[0]

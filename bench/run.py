#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run makes the cell's graph and operand pool from the seed, ingests the
graph through the program's store writers, builds a ``ServingFleet`` over
a ``ReplicaSet`` of that one store, warms up, then offers the cell's
traffic mix through ``SessionSpec.multiply`` -> ``ServingFleet.submit`` ->
``Ticket`` for ``--seconds``.  Everything before the window is set-up
(``setup_s``).  After the window it waits for every answer still owed,
reads the device's peak memory, closes the fleet, and holds a seeded
sample of the answers delivered to the plain reference (``reference.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  Each metric is read by ``metrics/<name>.py``; a configuration
is ``configs/<name>.json`` and a traffic mix ``traffic/<name>.json``, all
found by the names in ``BENCHMARK.json``.  The last line of standard
output is the result; the numbers compared for ``correct`` are the last
lines of standard error.  Without a TPU (or with fewer chips than the cell
asks for) the run prints no result and exits 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
DRAIN_S = 240.0
NO_DEVICE = 3


# ---------------------------------------------------------------------------
# Finding a cell by name: configuration, traffic mix and metric readers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    bench_dir: str


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _for_cell(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve a cell of ``<root>/BENCHMARK.json``: its configuration file,
    its traffic mix ``<bench>/traffic/<traffic>.json`` (``<bench>`` is the
    first of ``paths``) and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = _by_name(spec["workloads"], name, "workload")
    conf = _by_name(spec["configs"], cell["config"], "config")
    bench_dir = os.path.join(root, spec["paths"][0])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return Cell(name, cell["chips"], config, mix,
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name), bench_dir)


def metric_reader(bench_dir: str, name: str) -> Callable:
    """``read(run)`` of ``<bench>/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# What the metric readers see
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read, after the run."""
    setup_s: float
    t0: float                      # window, host clock
    t1: float
    n: int                         # graph size
    nnz: int
    chips: int                     # the cell's chips
    meter: object                  # meter.BatchMeter
    requests: list                 # traffic.Request, in submission order
    attempted: list                # the requests the window owes
    lateness: list                 # open loop: submit time - due time
    io: dict                       # IOStats delta over every pass of the run
    passes: int                    # scan passes of the run
    batches_per_pass: int
    reports: list                  # PassReports of passes ended in window
    peaks: Optional[dict] = None   # the device's row of peaks.json
    trace: object = None           # trace_reduce.Reduced of the window


def memory_peak_bytes(devices) -> Optional[int]:
    """The largest ``peak_bytes_in_use`` over ``devices`` (the cell's
    chips), or None where no device reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# Set-up helpers
# ---------------------------------------------------------------------------
def fleet_budget(n: int, T: int, record: int, sem: dict, fleet: dict) -> int:
    """The memory budget at which the fleet's waves hold exactly
    ``n_waves * capacity`` columns: ``SEMSpMM.columns_that_fit`` pays
    ``4 * (n + padded_cols)`` bytes a column and one chunk batch per
    prefetch slot plus the one in use."""
    padded = -(-n // T) * T
    cols = fleet["n_waves"] * fleet["capacity"]
    return (cols * 4 * (n + padded)
            + record * sem["chunk_batch"] * (sem["prefetch"] + 1))


def warm_midpass_ops(n_tile_rows: int, T: int, padded_cols: int,
                     capacity: int, width: int) -> int:
    """Warm the eager device ops that mid-pass admission and delivery run,
    which compile on first use: the column write of a newcomer
    (``PassBoundary.write_columns``, one shape) and the read of the
    accumulator's first ``k`` tile rows of a tenant's columns
    (``PassBoundary.read_output``, one shape per ``k``).  Each runs here on
    arrays of the served shapes, so none compiles in the window.  Returns
    the number of shapes warmed."""
    import jax
    import jax.numpy as jnp

    x_pad = jnp.zeros((padded_cols, capacity), jnp.float32)
    cols = jax.device_put(jnp.zeros((padded_cols, width), jnp.float32))
    x_pad.at[:, 0:width].set(cols).block_until_ready()
    del x_pad, cols
    acc = jnp.zeros((n_tile_rows, T, capacity), jnp.float32)
    for k in range(1, n_tile_rows + 1):
        acc[:k, :, 0:width].block_until_ready()
    del acc
    return 1 + n_tile_rows


class CompileCounter:
    """Backend compiles (persistent-cache loads included) by host time."""

    def __init__(self):
        import jax
        self.times = []

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0, t1) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


@dataclasses.dataclass
class Served:
    """The served path of one run and the data it serves."""
    graph: object          # graph.Graph
    pool_t: object         # (pool_cols, n) float32 operand pool
    replicas: object       # the ReplicaSet
    meter: object          # meter.BatchMeter
    fleet: object          # the ServingFleet over the metered replicas


def build(cfg: dict, mix: dict, seed: int, workdir: str, log,
          annotate=None, rehearsal: bool = False) -> Served:
    """Make the graph and the operand pool from the seed, ingest the graph
    through the program's store writers into ``workdir``, and build the
    fleet over a metered ``ReplicaSet`` of that store."""
    import jax
    import numpy as np

    from bench import graph as graph_mod
    from bench import meter as meter_mod
    from repro.core.sem import SEMConfig
    from repro.io.storage import TileStore
    from repro.runtime import ReplicaSet, ServingFleet

    g_cfg, st_cfg, sem_cfg, fl_cfg = (cfg["graph"], cfg["store"], cfg["sem"],
                                      cfg["fleet"])
    shutil.rmtree(workdir, ignore_errors=True)
    t = time.perf_counter()
    g = graph_mod.from_config(g_cfg, seed, st_cfg["T"])
    pool_t = np.asarray(jax.random.normal(
        jax.random.fold_in(graph_mod.seed_key(seed), 0x9001),
        (mix["pool_cols"], g.n), jax.numpy.float32))
    log(f"set-up: graph {g.n} vertices, {g.nnz} edges and operand pool "
        f"{pool_t.shape} in {time.perf_counter() - t:.3f} s")
    store = TileStore.open(graph_mod.build_stores(cfg, g, workdir, log))
    meter = meter_mod.BatchMeter(
        meter_mod.batch_plan_sizes(store, sem_cfg["chunk_batch"]))
    sem = dict(sem_cfg)
    if rehearsal:
        sem["memory_budget_bytes"] = fleet_budget(
            g.n, st_cfg["T"], store.header["record"], sem_cfg, fl_cfg)
    rs = ReplicaSet([store], SEMConfig(**sem))
    fit = rs.columns_that_fit(1 << 30)
    want = fl_cfg["n_waves"] * fl_cfg["capacity"]
    if fit != want:
        raise ValueError(f"memory_budget_bytes fits {fit} columns, the "
                         f"fleet's waves hold {want}")
    fleet = ServingFleet(meter_mod.MeteredExecutor(rs, meter, annotate),
                         n_waves=fl_cfg["n_waves"],
                         capacity=fl_cfg["capacity"])
    return Served(g, pool_t, rs, meter, fleet)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        rehearsal: Optional[dict] = None, workdir: Optional[str] = None,
        log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``rehearsal`` (tests only) overrides sizes and skips the TPU check."""
    import jax

    cfg = json.loads(json.dumps(cell.config))
    mix = dict(cell.mix)
    if rehearsal:
        for group, values in rehearsal.items():
            (mix if group == "mix" else cfg[group]).update(values)
    else:
        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            raise NoDevice(f"cell {cell.name} needs {cell.chips} TPU chip(s);"
                           f" JAX found {len(devices)} {devices[0].platform}")
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CACHE, "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    chips = jax.devices()[:cell.chips]
    device = chips[0]
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import meter as meter_mod
    from bench import reference
    from bench import trace_reduce as trace_mod
    from bench import traffic
    from repro.io.storage import IOStats

    counter = CompileCounter()
    st_cfg, fl_cfg = cfg["store"], cfg["fleet"]
    workdir = workdir or os.path.join(CACHE, "data")

    served = build(cfg, mix, seed, workdir, log, annotate=(
        jax.profiler.TraceAnnotation if trace else None),
        rehearsal=bool(rehearsal))
    g, pool_t, rs, meter, fleet = (served.graph, served.pool_t,
                                   served.replicas, served.meter,
                                   served.fleet)
    t = time.perf_counter()
    try:
        warmed = warm_midpass_ops(-(-g.n // st_cfg["T"]), st_cfg["T"],
                                  rs.padded_cols, fl_cfg["capacity"],
                                  mix["cols_per_request"])
        log(f"set-up: {warmed} mid-pass op shapes warmed by "
            f"{time.perf_counter() - t:.3f} s")
        clients = (fl_cfg["n_waves"] * fl_cfg["capacity"]
                   // mix["cols_per_request"] if mix["loop"] == "closed"
                   else 0)
        load = traffic.Load(fleet, mix, pool_t, seed, clients, seconds)
        io0 = IOStats.aggregate([fleet.io_stats])
        trace_dir = os.path.join(os.path.dirname(workdir), "trace")
        if trace:
            # from before the first pass: the host dispatches a pass's
            # batches long before the device runs them, and the device
            # tracer records only what is launched while it is on
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_mod.profile_options())
        if mix["loop"] == "closed":
            load.start()
            deadline = time.perf_counter() + DRAIN_S
            while not all(w.scheduler.reports for w in fleet.waves):
                for err in [load.error] + [w.error for w in fleet.waves]:
                    if err is not None:
                        raise err
                if time.perf_counter() > deadline:
                    raise TimeoutError("a wave served no pass in warm-up")
                time.sleep(0.01)
        else:
            load.warm_up(fl_cfg["n_waves"], timeout=DRAIN_S)
            load.start()
            time.sleep(max(0.0, load.window_start - time.perf_counter()))
        log(f"set-up: fleet of {fl_cfg['n_waves']} wave(s) x "
            f"{fl_cfg['capacity']} columns, warm-up served by "
            f"{time.perf_counter() - t:.3f} s; device peak so far "
            f"{memory_peak_bytes(chips)}; host memory {host_memory()}")

        # -- the window -----------------------------------------------------
        # an open loop's window is its schedule's: it owes exactly the
        # arrivals planned for it
        t0 = (load.window_start if mix["loop"] == "open"
              else time.perf_counter())
        setup_s = t0 - T_PROCESS
        load.keep_after = t0
        seen0 = [len(w.scheduler.reports) for w in fleet.waves]
        with (jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN) if trace
              else contextlib.nullcontext()):
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t1 = t0 + seconds
        load.close_submissions()
        reports = [r for w, k in zip(fleet.waves, seen0)
                   for r in w.scheduler.reports[k:]]
        if trace:
            log(f"trace: host memory {host_memory()} at the window's end")
            jax.profiler.stop_trace()
            log(f"trace: stopped, {trace_mod.xplane_bytes(trace_dir)} B "
                f"written; host memory {host_memory()}")
        compiles = counter.between(t0, t1)

        # -- drain: every answer the window owes ----------------------------
        drain_err = None
        try:
            fleet.drain(timeout=DRAIN_S)
        except (TimeoutError, RuntimeError) as e:
            drain_err = e
        load.stop()
        if load.error is not None:
            raise load.error
        peak = memory_peak_bytes(chips)
        io1 = IOStats.aggregate([fleet.io_stats])
        passes = fleet.total_scan_passes()
    finally:
        fleet.close()
    log(f"window: {t1 - t0:.3f} s, {compiles} compiles inside it; drained "
        f"in {time.perf_counter() - t1:.3f} s; host memory {host_memory()}"
        + (f" with error {drain_err!r}" if drain_err else ""))

    # -- which requests the window owes --------------------------------------
    if mix["loop"] == "open":
        owed = [r for r in load.requests if t0 <= r.due < t1]
        late = load.lateness
        log(f"generator: {len(owed)} requests due in the window; lateness "
            f"median {meter_mod.quantile(late, 0.5)!r} s, max "
            f"{float(max(late, default=0.0))!r} s over {len(late)} "
            "submissions")
    else:
        owed = [r for r in load.requests
                if r.submitted < t1 and (r.done is None or r.done >= t0)]
        log(f"generator: {len(owed)} requests in flight in the window from "
            f"{clients} closed-loop clients")
    failed = [r for r in owed if r.done is None or r.error is not None]
    requests, lateness = load.requests, load.lateness

    # the fleet (through the load and the executor) and its tickets go
    # before the trace is read and the reference's CSR arrives in host
    # memory
    del fleet, rs, served, load
    gc.collect()
    log(f"host memory with the fleet released: {host_memory()}")

    # -- metrics ------------------------------------------------------------
    peaks = None
    reduced = None
    if trace:
        with open(os.path.join(cell.bench_dir, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if not rehearsal:
            if device.device_kind not in table:
                raise KeyError(f"no peaks for device {device.device_kind!r}"
                               " in peaks.json")
            peaks = table[device.device_kind]
        t = time.perf_counter()
        reduced = trace_mod.Reduced(trace_mod.extract(trace_dir),
                                    chips=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {reduced.window_s:.3f} s traced, {reduced.n_modules} "
            f"device modules on {reduced.chips} chip(s), busy "
            f"{[reduced.chip_busy_s(c) for c in range(reduced.chips)]} s, "
            f"read in {time.perf_counter() - t:.3f} s; host memory "
            f"{host_memory()}")
    data = RunData(
        setup_s=setup_s, t0=t0, t1=t1, n=g.n, nnz=g.nnz, chips=cell.chips,
        meter=meter, requests=requests, attempted=owed, lateness=lateness,
        io={k: v - getattr(io0, k) for k, v in io1.to_dict().items()},
        passes=passes, batches_per_pass=len(meter.sizes),
        reports=reports,
        peaks=peaks, trace=reduced)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        value = metric_reader(cell.bench_dir, m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"work: {meter.edge_cols(t0, t1)} edge-columns done in the window "
        f"over {len(meter.in_window(t0, t1))} batches; whole passes ended "
        f"in it (edge-columns, seconds, passes): "
        f"{meter.whole_passes(t0, t1)}; {passes} passes in the run, ending "
        f"at {[round(e - t0, 3) for e in meter.pass_ends()]} s from t0")

    # -- correct: the sampled answers against the reference ------------------
    del data
    gc.collect()
    log(f"host memory before the reference: {host_memory()}")
    t = time.perf_counter()
    sampled = [(r.idx, r.result) for r in owed if r.result is not None]
    A = reference.csr(g.n, g.T, g.hi, g.lo)
    err = reference.max_rel_err(A, pool_t.T, sampled) if sampled else math.inf
    limit = cfg["correct"]["max_rel_err"]
    log(f"reference: {len(sampled)} sampled answers compared in "
        f"{time.perf_counter() - t:.3f} s; host memory {host_memory()}")
    shutil.rmtree(workdir, ignore_errors=True)

    checks = {
        "max_rel_err": {"value": _finite(err), "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "sampled_answers": {"value": len(sampled), "limit": 1},
    }
    correct = (err <= limit and not failed and len(sampled) >= 1
               and drain_err is None)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(owed),
              "failed": len(failed), "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": trace_mod.top(reduced.module_seconds()),
            "idle_gaps": trace_mod.top(reduced.idle_by_host_span())}
    result["checks"] = checks
    return result


def host_memory() -> str:
    """This process's peak resident host memory so far, and its resident
    memory now."""
    import resource
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    now = "?"
    with contextlib.suppress(OSError):
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    now = f"{int(line.split()[1]) / 2**20:.2f}"
    return f"peak {peak_kb / 2**20:.2f} GiB, now {now} GiB"


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e308


class NoDevice(RuntimeError):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(line):
        print(f"[{time.perf_counter() - T_PROCESS:8.3f}] {line}",
              file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     log=log)
    except NoDevice as e:
        log(f"bench: {e}")
        return NO_DEVICE
    except ImportError as e:
        log(f"bench: cannot import the system under test: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

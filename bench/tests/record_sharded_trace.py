#!/usr/bin/env python3
"""Record ``data/v5e_4chip_trace.json``: the reduced events of one traced
window in which ``SharedScanScheduler(sharded=4)`` multiplies a small
graph through four row shards, one per chip, with the benchmark's own
profiler options.  The file is what ``trace_reduce.extract`` returned,
and ``test_bench_trace.py`` pins what the reduction reads from it.

  python3 bench/tests/record_sharded_trace.py [--chips 4] [--scale 16]

Run on a host with that many TPU chips (it exits 3 elsewhere).  It also
prints the host's memory limits: ``MemTotal`` of ``/proc/meminfo`` and the
cgroup's ``memory.max`` where there is one.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def memory_limits() -> dict:
    """What the host offers this process, read and left as it is."""
    out = {}
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                out["MemTotal_bytes"] = int(line.split()[1]) * 1024
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            out["cgroup_memory_max"] = f.read().strip()
    except OSError:
        out["cgroup_memory_max"] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    ap.add_argument("--out", default=os.path.join(HERE, "data",
                                                  "v5e_4chip_trace.json"))
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax
    import numpy as np

    from bench import graph, trace_reduce
    from repro.core.sem import SEMConfig, SEMSpMM
    from repro.io.storage import TileStore
    from repro.runtime import SessionSpec, SharedScanScheduler

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"record: needs {args.chips} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 3
    print(f"host memory: {json.dumps(memory_limits())}", flush=True)
    T = 4096
    g = graph.rmat_device(args.scale, 16, 0.57, 0.19, 0.19, args.seed, T,
                          scramble=True, symmetric=True)
    work = tempfile.mkdtemp(prefix="record_")
    trace_dir = os.path.join(work, "trace")
    try:
        path = graph.build_stores(
            {"store": {"layout": "raw", "binary": True, "T": T, "C": 2048}},
            g, os.path.join(work, "data"), print)
        sem = SEMSpMM(TileStore.open(path), SEMConfig(chunk_batch=16))
        x = np.random.default_rng(args.seed).standard_normal(
            (g.n, 32)).astype(np.float32)
        with SharedScanScheduler(sem, sharded=args.chips) as sched:
            def serve():
                tickets = [sched.submit(SessionSpec.multiply(x[:, k:k + 8]))
                           for k in range(0, 32, 8)]
                sched.run()
                return [t.wait(timeout=0) for t in tickets]

            serve()                                  # compiles
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                for _ in range(3):
                    got = serve()
            secs = time.perf_counter() - t
            jax.profiler.stop_trace()
        want = sem.multiply(x)
        same = all(np.array_equal(a, want[:, 8 * k:8 * k + 8])
                   for k, a in enumerate(got))
        events = trace_reduce.extract(trace_dir)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    with open(args.out, "w") as f:
        json.dump(events, f)
    r = trace_reduce.Reduced(events, chips=args.chips)
    print(json.dumps({
        "window_s": r.window_s, "host_s": secs, "sharded==single": same,
        "chip_busy_s": [r.chip_busy_s(c) for c in range(args.chips)],
        "busy_s": r.busy_s, "n_modules": r.n_modules,
        "step_s_by_chip": [sum(r.module_seconds(("_batch_step",), c)
                               .values()) for c in range(args.chips)],
        "idle_by_host_span": r.idle_by_host_span(),
        "device": {"kind": devices[0].device_kind, "count": args.chips}}),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

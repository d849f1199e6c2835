#!/usr/bin/env python3
"""Chip smoke test: serve a Graph500-scale graph on a TPU through the
system's normal entry points, and check every answer against a plain
NumPy/SciPy reference.

  python chip_smoke.py [--scale 22] [--seed 0]   # one chip
  python chip_smoke.py --chips 4 [--scale 22]    # the sharded scan, 4 chips

One chip: an R-MAT graph (Graph500 parameters A, B, C = 0.57, 0.19, 0.19,
edge factor 16; scale 22 = 4.2 M vertices) becomes the PageRank operator,
chunked at the engine's real tile size (T = 16384, C = 2048, 256-chunk
batches) and written as a raw ``TileStore`` and a packed one
(``TileStore.optimize``).  On each store a ``ServingFleet`` serves, through
``SessionSpec`` tickets, a multiply (p = 8), two PageRank tenants, a BFS
from 8 roots and an SSSP from 4 roots (min-plus ring) on the default scan
engine; then the multiply and PageRank tenants again on the compiled Pallas
wave kernel (its gather variant, the one ``T = 16384`` picks), and on a
scale-16 graph chunked at ``T = 2048`` through its MXU variant.
``--chips 4`` instead runs only a multiply and a PageRank
tenant through ``SharedScanScheduler(sharded=4)``, one shard per chip,
against the single-device ``SEMSpMM`` result and the reference.

Generating the graph and the stores is set-up: its time is printed and kept
out of every phase.  The per-phase lines are smoke timings of one cold run,
not benchmark results.  The script exits non-zero, printing no result,
when JAX finds no TPU; any failed phase or check exits non-zero too.  The
last line of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np
import scipy.sparse as sp

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
LABEL = "smoke timing of one cold run, not a benchmark result"


# ---------------------------------------------------------------------------
# Plain reference: NumPy/SciPy on the operator's COO, nothing from repro
# ---------------------------------------------------------------------------
def ref_multiply(P: sp.csr_matrix, x: np.ndarray):
    """(A @ X in float64, |A| @ |X| — the scale of float32 summation
    error for each entry)."""
    x64 = x.astype(np.float64)
    return P @ x64, abs(P) @ np.abs(x64)


def ref_pagerank(P, dangling, max_iter, damping=0.85):
    """The served PageRank update, iterated ``max_iter`` times (tol = 0)."""
    n = P.shape[0]
    x = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        x = damping * (P @ x + x[dangling].sum() / n) + (1.0 - damping) / n
    return x


def ref_bfs(P, sources):
    """Multi-source BFS hop counts (-1 unreachable), following P[v, u]."""
    n = P.shape[0]
    depth = np.full(n, -1, np.int32)
    depth[sources] = 0
    visited = depth >= 0
    frontier = visited.astype(np.float64)
    d = 0
    while True:
        d += 1
        reached = (P @ frontier > 0) & ~visited
        if not reached.any():
            return depth
        depth[reached] = d
        visited |= reached
        frontier = reached.astype(np.float64)


def ref_sssp(P, sources, max_iters):
    """Float32 Bellman-Ford over min-plus: dist' = min(dist, min_u(P[v, u]
    + dist[u])), at most ``max_iters`` relaxation waves."""
    n = P.shape[0]
    w = P.data.astype(np.float32)
    nonempty = np.flatnonzero(np.diff(P.indptr))
    dist = np.full(n, np.inf, np.float32)
    dist[sources] = 0.0
    for _ in range(max_iters):
        cand = w + dist[P.indices]
        best = np.full(n, np.inf, np.float32)
        best[nonempty] = np.minimum.reduceat(cand, P.indptr[nonempty])
        new = np.minimum(dist, best)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


# ---------------------------------------------------------------------------
# Set-up: graph, operator, stores, tenants, reference answers
# ---------------------------------------------------------------------------
class Data:
    """Everything a phase needs, built once from ``seed``."""

    def __init__(self, scale, seed, root, *, T, C, packed=True, log=print):
        from repro.apps.pagerank import build_operator, dangling_vertices
        from repro.core.formats import to_chunked
        from repro.io.storage import TileStore
        from repro.sparse.generate import rmat

        t0 = time.perf_counter()
        adj = rmat(scale, 16, a=0.57, b=0.19, c=0.19, seed=seed)
        op = build_operator(adj)
        n = self.n = op.n_rows
        self.raw = os.path.join(root, "raw")
        TileStore.write(self.raw, to_chunked(op, T=T, C=C))
        stores = f"raw store {TileStore.open(self.raw).nbytes} B"
        self.packed = None
        if packed:
            self.packed = os.path.join(root, "packed")
            opt = TileStore.open(self.raw).optimize(self.packed)
            stores += f", packed store {opt.nbytes} B"
        self.P = sp.csr_matrix(
            (op.vals.astype(np.float64), (op.rows, op.cols)), shape=(n, n))
        self.dangling = dangling_vertices(adj)
        gen_s = time.perf_counter() - t0
        log(f"set-up: scale {scale} R-MAT, {n} vertices, {op.nnz} nnz, "
            f"{stores}, generated in {gen_s:.1f} s (kept out of every "
            "phase)")

        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((n, 8)).astype(np.float32)
        has_out = np.flatnonzero(np.diff(self.P.tocsc().indptr))
        self.bfs_roots = rng.choice(has_out, 8, replace=False)
        self.sssp_roots = rng.choice(has_out, 4, replace=False)
        t0 = time.perf_counter()
        self.ref = {"multiply": ref_multiply(self.P, self.x)}
        for it in PAGERANK_ITERS:
            self.ref[f"pagerank-{it}"] = ref_pagerank(self.P, self.dangling,
                                                      it)
        self.ref["bfs"] = ref_bfs(self.P, self.bfs_roots)
        self.ref["sssp"] = ref_sssp(self.P, self.sssp_roots, SSSP_ITERS)
        log(f"set-up: reference answers in {time.perf_counter() - t0:.1f} s")

    def specs(self, kinds):
        from repro.runtime import SessionSpec
        out = []
        if "multiply" in kinds:
            out.append(SessionSpec.multiply(self.x, tenant_id="multiply"))
        if "pagerank" in kinds:
            out += [SessionSpec.pagerank(self.n, self.dangling, tol=0.0,
                                         max_iter=it,
                                         tenant_id=f"pagerank-{it}")
                    for it in PAGERANK_ITERS]
        if "bfs" in kinds:
            out.append(SessionSpec.bfs(self.bfs_roots, self.n,
                                       tenant_id="bfs"))
        if "sssp" in kinds:
            out.append(SessionSpec.sssp(self.sssp_roots, self.n,
                                        max_iters=SSSP_ITERS,
                                        tenant_id="sssp"))
        return out


PAGERANK_ITERS = (4, 8)
SSSP_ITERS = 8
# the MXU variant's own graph: the engine picks that variant for T <= 2048
MXU_SCALE, MXU_T = 16, 2048


def check_result(data, tenant, got, want=None) -> str:
    """'' if ``got`` agrees with ``want`` (default: the reference answer)
    within the tenant's tolerance, else what is wrong.  Float32 sums get a
    tolerance scaled to their summation error, BFS depths must be equal,
    and SSSP distances are the same float32 sums in the same order."""
    ref = data.ref[tenant]
    if tenant == "multiply":
        ref, scale = ref
    want = ref if want is None else want
    if got.shape != ref.shape:
        return f"shape {got.shape}, expected {ref.shape}"
    if tenant == "multiply":
        bad = ~(np.abs(got - want) <= 1e-4 * scale)
    elif tenant.startswith("pagerank"):
        bad = ~(np.abs(got - want) <= 1e-4 * np.abs(ref))
    elif tenant == "bfs":
        bad = got != want
    else:
        bad = ~np.isclose(got, want, rtol=1e-6, atol=0.0)
    if bad.any():
        return f"{int(bad.sum())} of {bad.size} entries off"
    return ""


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
class CompileClock:
    """Wall time during which JAX was compiling: the union of the backend
    compile intervals (a persistent-cache hit counts only its load), so
    compiles on concurrent shard threads are not counted twice."""

    def __init__(self):
        import jax
        self.intervals = []

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                now = time.perf_counter()
                self.intervals.append((now - duration, now))
        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0: float, t1: float):
        """(seconds compiling, compiles) in the window ``[t0, t1]``."""
        spans = sorted((max(a, t0), min(b, t1)) for a, b in self.intervals
                       if t0 < b <= t1)
        total, end = 0.0, t0
        for a, b in spans:
            total += max(0.0, b - max(a, end))
            end = max(end, b)
        return total, len(spans)


def peak_bytes() -> object:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def report(phase, clock, t0, t1, io0, io1, passes, checks, log):
    """Print the line of the phase served in ``[t0, t1]``; raise if any
    check failed."""
    wall = t1 - t0
    compile_s, compiles = clock.between(t0, t1)
    row = {"phase": phase, "label": LABEL,
           "compile_s": round(compile_s, 3), "compiles": compiles,
           "pass_s": round(wall - compile_s, 3), "wall_s": round(wall, 3),
           "passes": passes,
           "bytes_streamed": (io1.bytes_read - io0.bytes_read
                              + io1.cache_hit_bytes - io0.cache_hit_bytes),
           "bytes_read": io1.bytes_read - io0.bytes_read,
           "h2d_bytes": io1.h2d_bytes - io0.h2d_bytes,
           "peak_bytes_in_use": peak_bytes(),
           "checks": {k: (v or "ok") for k, v in checks.items()}}
    log("phase " + json.dumps(row))
    failed = {k: v for k, v in checks.items() if v}
    if failed:
        raise RuntimeError(f"phase {phase}: checks failed: {failed}")


def io_snapshot(stats):
    from repro.io.storage import IOStats
    return IOStats.aggregate([stats])


def serve_phase(data, store, *, use_pallas, kinds, clock, chunk_batch, log,
                against=None, variant=None):
    """Serve ``kinds`` tenants together through a one-wave elastic
    ``ServingFleet`` over ``store``; check each against the reference (and
    against ``against``, a previous phase's results, when given).
    ``variant`` pins the Pallas kernel variant (default: the engine's
    choice for the store's tile size)."""
    from repro.core.sem import SEMConfig
    from repro.io.storage import TileStore
    from repro.runtime import ReplicaSet, ServingFleet

    engine = "scan" if not use_pallas else "-".join(
        ["pallas"] + ([variant] if variant else []))
    name = f"{engine}-{'packed' if store == data.packed else 'raw'}"
    cfg = SEMConfig(chunk_batch=chunk_batch, use_pallas=use_pallas,
                    pallas_variant=variant)
    with ServingFleet(ReplicaSet([TileStore.open(store)], cfg),
                      n_waves=1) as fleet:
        io0 = fleet.io_stats
        t0 = time.perf_counter()
        tickets = [fleet.submit(s) for s in data.specs(kinds)]
        fleet.drain(timeout=1800)
        results = {t.tenant_id: np.asarray(t.wait(timeout=0))
                   for t in tickets}
        t1 = time.perf_counter()
        io1 = fleet.io_stats
        passes = fleet.total_scan_passes()
    checks = {tid: check_result(data, tid, got)
              for tid, got in results.items()}
    for tid, got in results.items():
        if against is not None:
            checks[f"{tid} vs scan"] = check_result(data, tid, got,
                                                    against[tid])
            log(f"{name}: {tid} max |pallas - scan| = "
                f"{float(np.max(np.abs(got - against[tid])))!r}")
    report(name, clock, t0, t1, io0, io1, passes, checks, log)
    return results


def run_phases(scale, seed=0, *, T=16384, C=2048, chunk_batch=256,
               log=print) -> None:
    """The one-chip smoke: set-up, then the scan engine on the raw and the
    packed store with every tenant kind, then the Pallas engine with the
    multiply and PageRank tenants, then those tenants on the Pallas MXU
    variant over a graph of scale ``min(scale, MXU_SCALE)`` chunked at
    ``MXU_T``.  Raises on any failed check."""
    clock = CompileClock()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        data = Data(scale, seed, root, T=T, C=C, log=log)
        everything = ("multiply", "pagerank", "bfs", "sssp")
        scan = {}
        for store in (data.raw, data.packed):
            scan[store] = serve_phase(
                data, store, use_pallas=False, kinds=everything, clock=clock,
                chunk_batch=chunk_batch, log=log)
        for store in (data.raw, data.packed):
            serve_phase(data, store, use_pallas=True,
                        kinds=("multiply", "pagerank"), clock=clock,
                        chunk_batch=chunk_batch, log=log,
                        against=scan[store])
        small = Data(min(scale, MXU_SCALE), seed, os.path.join(root, "mxu"),
                     T=MXU_T, C=C, packed=False, log=log)
        serve_phase(small, small.raw, use_pallas=True, variant="mxu",
                    kinds=("multiply", "pagerank"), clock=clock,
                    chunk_batch=chunk_batch, log=log)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_sharded(scale, seed=0, *, chips=4, T=16384, C=2048, chunk_batch=256,
                log=print) -> None:
    """The multi-chip smoke: a multiply and a PageRank tenant through
    ``SharedScanScheduler(sharded=chips)`` (one row shard per device),
    against the single-device ``SEMSpMM`` multiply and the reference."""
    import jax
    from repro.core.sem import SEMConfig, SEMSpMM
    from repro.io.storage import TileStore
    from repro.runtime import SharedScanScheduler

    devices = jax.devices()
    if len(devices) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices, JAX "
                           f"shows {len(devices)}")
    clock = CompileClock()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        data = Data(scale, seed, root, T=T, C=C, packed=False, log=log)
        cfg = SEMConfig(chunk_batch=chunk_batch)
        single = SEMSpMM(TileStore.open(data.raw), cfg, device=devices[0])
        io0 = io_snapshot(single.io_stats)
        t0 = time.perf_counter()
        y_single = single.multiply(data.x)
        t1 = time.perf_counter()
        checks = {"multiply": check_result(data, "multiply", y_single)}
        report("single-device", clock, t0, t1, io0, single.io_stats,
               single.passes, checks, log)

        sem = SEMSpMM(TileStore.open(data.raw), cfg)
        with SharedScanScheduler(sem, sharded=chips) as sched:
            sharded = sched.sharded
            io0 = sharded.io_stats
            t0 = time.perf_counter()
            tickets = [sched.submit(s)
                       for s in data.specs(("multiply", "pagerank"))]
            sched.run()
            results = {t.tenant_id: np.asarray(t.wait(timeout=0))
                       for t in tickets}
            t1 = time.perf_counter()
            io1 = sharded.io_stats
            passes = sched.total_scan_passes()
            # each shard's accumulator (and operand) on its own device
            placed = []
            for ex in sharded.execs:
                seen = set()
                ex.multiply(data.x[:, :1], boundary_hook=lambda b, s=seen: (
                    s.update(b.out.devices() | b.x_pad.devices())))
                placed.append(sorted(d.id for d in seen))
        checks = {tid: check_result(data, tid, got)
                  for tid, got in results.items()}
        want = [[d.id] for d in devices[:chips]]
        checks["one-device-per-shard"] = (
            "" if placed == want else f"shards on devices {placed}")
        checks["sharded==single"] = (
            "" if np.array_equal(results["multiply"], y_single) else
            f"max |diff| {np.max(np.abs(results['multiply'] - y_single))!r}")
        log(f"sharded: shard devices {placed}")
        report(f"sharded-{chips}", clock, t0, t1, io0, io1, passes, checks,
               log)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=22,
                    help="log2 of the vertex count (default 22)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-scan path on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{json.dumps(found)}", file=sys.stderr)
        return 1
    # the chips this mode runs on: one, or the sharded path's four
    device = dict(found, count=args.chips)
    print(f"device: {json.dumps(found)}", flush=True)

    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    def log(line):
        print(line, flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        run_phases(args.scale, args.seed, log=log)
    else:
        run_sharded(args.scale, args.seed, chips=args.chips, log=log)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

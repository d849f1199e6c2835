"""Example: elastic multi-tenant graph-query serving over replicated
on-"SSD" copies of one graph — optionally as a concurrent-wave fleet.

  PYTHONPATH=src python examples/serve_graph.py [--scale 12] [--tenants 6]
                                                [--replicas 2] [--waves 1]

Usage note: the serving runtime turns the paper's Fig-5 crossover into a
scheduler.  Build the sparse operator once (``TileStore.write``), copy it
to one path per spindle/NUMA node, wrap the copies in a ``ReplicaSet``
(waves are routed to the healthiest, fastest copy; a failed copy is routed
around), and hand that to ``SharedScanScheduler(elastic=True)``.  Then
submit any mix of tenants — one-shot ``scheduler.query(x)`` multiplies,
iterative ``PageRankSession`` / ``PowerIterationSession`` /
``LabelPropagationSession`` workloads — and call ``scheduler.run()``.
Every pass streams the sparse matrix ONCE for the whole wave, and elastic
mode admits late arrivals at chunk-batch boundaries *inside* a running
pass: a request that shows up mid-pass starts accumulating tile rows
immediately and is delivered from a stitched partial pass roughly half a
pass earlier than between-pass admission — with bit-identical results.
Leftover memory budget still pins hot chunk batches.

With ``--waves N`` (N >= 2) the same tenants are served by a
``ServingFleet`` instead: N elastic schedulers run concurrently over the
shared ``ReplicaSet``, the front door routes each session to the wave with
the least estimated backlog (live columns x measured pass time), and the
global column/hot-chunk budget is arbitrated across waves.  On a
deployment with as many replica spindles as waves, aggregate throughput
scales with the wave count (see ``benchmarks/bench_runtime.py``).

With ``--hosts N`` (N >= 2) the demo goes cross-host: it spawns N local
``python -m repro.net.host`` processes — each one a full HostServer
wrapping its own ServingFleet over its own store copy — and serves the
tenant mix through a ``ClusterFrontDoor`` speaking the length-prefixed
wire protocol over localhost sockets.  The front door routes each tenant
to the least-estimated-backlog host (fed by heartbeat gauges), arbitrates
the global memory budget across hosts, and — because sessions are
deterministic replays — would resubmit a dead host's tenants to the
survivors bit-identically (see ``tests/test_net.py`` and
``benchmarks/bench_net.py`` for the kill-host drill).

Adding ``--partition`` (with ``--hosts >= 2``) additionally serves one
wide iterative query submitted with ``door.submit(spec,
partitioned=True)``: instead of routing the whole tenant to one host,
every pass spans *all* live hosts, each scanning only its nnz-balanced
contiguous tile-row slab of its own store copy, and the front door
stitches the returned row blocks in tile-row order — bit-identical to a
single-host serve, with the per-pass scan time divided across spindles.
The demo prints the slab -> host assignment the partition plan chose.

With ``--optimize-store`` the operator is re-encoded offline
(``TileStore.optimize``: degree-descending column reorder + uint8 delta
packing) before the replicas are copied out, and the demo reports the
slow-tier bytes actually saved, measured from ``IOStats``.  The serving
stack is oblivious: the permutation sidecar rides along with each replica
copy and the engine relabels operands at staging time.

The single-wave demo drips one-shot queries in mid-pass (via the
scheduler's boundary probe, so the run is deterministic) and prints each
pass's mid-pass admissions/completions plus every late query's
time-to-first-result in chunk-batch boundaries.
"""
import argparse
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.apps.pagerank import (build_operator, dangling_vertices,
                                 pagerank_session)
from repro.compile_cache import enable_compile_cache
from repro.core.formats import to_chunked
from repro.core.sem import SEMConfig
from repro.io.storage import TileStore
from repro.net import ClusterFrontDoor
from repro.net.host import check_local_hosts_allowed
from repro.runtime import (PowerIterationSession, ReplicaSet, ServingFleet,
                           SessionSpec, SharedScanScheduler)
from repro.sparse.generate import rmat


def build_replicas(args):
    adj = rmat(args.scale, 16, seed=1)
    print(f"graph: {adj.n_rows} vertices, {adj.nnz} edges")
    ct = to_chunked(build_operator(adj), T=1024, C=256)
    root = tempfile.mkdtemp(prefix="serve_graph_")
    path = os.path.join(root, "replica0")
    store = TileStore.write(path, ct)
    raw_nbytes = store.nbytes
    exts = (".bin", ".json")
    if args.optimize_store:
        # offline re-encode (degree reorder + delta packing), then serve
        # the packed store: every replica copies the same optimized bytes
        # plus the persisted column permutation
        raw = os.path.join(root, "raw")
        for ext in exts:
            os.rename(path + ext, raw + ext)
        store = TileStore.open(raw).optimize(path)
        exts += (".perm.npy",)
        print(f"optimize(): {raw_nbytes / 1e6:.1f} MB raw -> "
              f"{store.nbytes / 1e6:.1f} MB reordered+packed "
              f"({1 - store.nbytes / raw_nbytes:.0%} smaller, perm sidecar "
              f"{os.path.getsize(path + '.perm.npy') / 1e6:.2f} MB)")
    paths = [path]
    for i in range(1, max(1, args.replicas)):
        p = os.path.join(root, f"replica{i}")
        for ext in exts:
            shutil.copy(path + ext, p + ext)
        paths.append(p)
    print(f"operator on slow tier: {store.nbytes / 1e6:.1f} MB "
          f"x {len(paths)} replica(s)")
    # small chunk batches -> many boundaries per pass: more mid-pass
    # admission points for the demo's late arrivals
    return adj, ReplicaSet(TileStore.open_replicas(paths),
                           SEMConfig(memory_budget_bytes=256 << 20,
                                     chunk_batch=32)), raw_nbytes


def print_stream_savings(replicas, total, raw_nbytes):
    """What the pass actually streamed (IOStats) vs what the raw store
    would have: every pass streams the whole store, so the ratio is exact."""
    if raw_nbytes <= replicas.store.nbytes:
        return
    raw_total = total * raw_nbytes / replicas.store.nbytes
    print(f"optimized store streamed {total / 1e6:.2f} MB where raw would "
          f"have streamed {raw_total / 1e6:.2f} MB "
          f"({1 - total / raw_total:.0%} fewer slow-tier bytes)")


def submit_tenants(target, adj, n_tenants, rng):
    tenants = [target.submit(pagerank_session(
        adj, max_iter=10 + 3 * i, tenant_id=f"pagerank-{i}"))
        for i in range(n_tenants)]
    tenants.append(target.submit(PowerIterationSession(
        rng.standard_normal(adj.n_rows).astype(np.float32), max_iter=25,
        tenant_id="spectral")))
    return tenants


def print_replica_states(replicas):
    for st in replicas.router.states:
        print(f"replica {st.replica_id}: {st.scans} scans, "
              f"{st.ewma_bps / 1e6:.0f} MB/s, "
              f"{'healthy' if st.healthy else 'DOWN'}")


def serve_single_wave(adj, replicas, args, raw_nbytes) -> int:
    """The elastic single-scheduler demo: late arrivals admitted mid-pass."""
    rng = np.random.default_rng(0)
    n = adj.n_rows
    late = {"queries": [], "xs": [rng.standard_normal(n).astype(np.float32)
                                  for _ in range(4)]}

    def drip(sched, boundary):
        i = len(late["queries"])
        if i < len(late["xs"]) and sched.boundary_clock >= 9 * (i + 1):
            late["queries"].append(
                sched.query(late["xs"][i], tenant_id=f"late-{i}"))

    read0 = replicas.io_stats.bytes_read
    with SharedScanScheduler(replicas, elastic=True, reserve_cols=2,
                             boundary_probe=drip) as sched:
        tenants = submit_tenants(sched, adj, args.tenants, rng)
        for i, rep in enumerate(sched.run(), 1):
            print(f"pass {i:3d}: cols={rep.wave_cols:3d}/{rep.capacity} "
                  f"tenants={rep.tenants} retired={rep.retired} "
                  f"mid-pass +{rep.admitted_midpass}/-{rep.completed_midpass} "
                  f"read={rep.bytes_read / 1e6:7.2f}MB "
                  f"cache_hit={rep.cache_hit_bytes / 1e6:7.2f}MB")

        n_batches = replicas.n_batches
        print("\nlate arrivals (admitted inside a running pass):")
        for q in late["queries"]:
            waited = q.first_result_clock - q.submit_clock
            print(f"  {q.tenant_id}: result after {waited} boundaries "
                  f"= {waited / n_batches:.2f} passes "
                  f"({(q.t_first_result - q.t_submit) * 1e3:.0f} ms)")

        total = replicas.io_stats.bytes_read - read0
        served = sum(t.iterations for t in tenants) + len(late["queries"])
        naive = served * replicas.store.nbytes
        print(f"\nserved {len(tenants)} iterative tenants "
              f"({sum(t.iterations for t in tenants)} operator applications) "
              f"+ {len(late['queries'])} mid-pass one-shot queries")
        print(f"slow-tier reads: {total / 1e6:.1f} MB "
              f"(naive per-request serving: {naive / 1e6:.1f} MB, "
              f"amortization {naive / max(1, total):.1f}x)")
        print_stream_savings(replicas, total, raw_nbytes)
        if sched.cache is not None:
            print(f"hot-chunk cache: hit rate "
                  f"{sched.cache.stats.hit_rate:.0%}")
        print_replica_states(replicas)
    return 0


def serve_fleet(adj, replicas, args, raw_nbytes) -> int:
    """Concurrent-wave serving: the same tenant mix dispatched across
    ``--waves`` elastic schedulers over the shared replica set."""
    rng = np.random.default_rng(0)
    n = adj.n_rows
    read0 = replicas.io_stats.bytes_read
    with ServingFleet(replicas, n_waves=args.waves) as fleet:
        t0 = time.perf_counter()
        tenants = submit_tenants(fleet, adj, args.tenants, rng)
        bursts = [fleet.query(rng.standard_normal(n).astype(np.float32),
                              tenant_id=f"burst-{i}") for i in range(8)]
        fleet.drain()
        wall = time.perf_counter() - t0

    sessions = tenants + bursts
    ops = sum(t.iterations for t in sessions)
    print(f"\nfleet of {args.waves} waves served {len(sessions)} tenants "
          f"({ops} operator applications) in {wall:.2f}s")
    for w in fleet.waves:
        mine = [s.tenant_id for s in sessions if s.wave_id == w.wave_id]
        print(f"  wave {w.wave_id}: {w.passes_served} passes, "
              f"ewma pass {w.ewma_pass_s * 1e3:.0f} ms, "
              f"{len(mine)} tenants: {', '.join(mine)}")
    total = fleet.io_stats.bytes_read - read0
    agg = fleet.io_stats
    print(f"slow-tier reads: {total / 1e6:.1f} MB; peak concurrent reads "
          f"on one replica: {agg.max_reads_inflight}")
    print_stream_savings(replicas, total, raw_nbytes)
    print_replica_states(replicas)
    return 0


def serve_cluster(args) -> int:
    """Cross-host serving: N spawned HostServer processes behind one
    ClusterFrontDoor speaking the wire protocol over localhost."""
    check_local_hosts_allowed()
    adj = rmat(args.scale, 16, seed=1)
    print(f"graph: {adj.n_rows} vertices, {adj.nnz} edges")
    ct = to_chunked(build_operator(adj), T=1024, C=256)
    root = tempfile.mkdtemp(prefix="serve_cluster_")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(repo, "src"),
                    env.get("PYTHONPATH", "")] if p)
    procs = []
    try:
        paths = [os.path.join(root, f"host{i}") for i in range(args.hosts)]
        store = TileStore.write(paths[0], ct)
        for p in paths[1:]:
            shutil.copy(paths[0] + ".bin", p + ".bin")
            shutil.copy(paths[0] + ".json", p + ".json")
        print(f"operator on slow tier: {store.nbytes / 1e6:.1f} MB "
              f"x {args.hosts} host(s), one store copy each")
        procs = [subprocess.Popen(
            [sys.executable, "-m", "repro.net.host", "--store", p,
             "--waves", str(max(1, args.waves))],
            stdout=subprocess.PIPE, env=env, text=True) for p in paths]
        ports = []
        for pr in procs:
            line = pr.stdout.readline()
            assert line.startswith("LISTENING "), line
            ports.append(int(line.split()[1]))
        print(f"hosts listening on ports {ports}")

        rng = np.random.default_rng(0)
        n = adj.n_rows
        with ClusterFrontDoor(memory_budget_bytes=512 << 20) as door:
            for port in ports:
                door.add_host("127.0.0.1", port)
            if args.partition:
                t0 = time.perf_counter()
                wide = door.submit(SessionSpec.power_iteration(
                    rng.standard_normal(n).astype(np.float32), tol=0.0,
                    max_iter=20, tenant_id="wide-spectral"),
                    partitioned=True)
                wide.wait(600)
                wall = time.perf_counter() - t0
                plan = wide.plan
                print(f"\npartitioned query '{wide.tenant_id}': "
                      f"{wide.iterations} passes in {wall:.2f}s, each pass "
                      f"spanning {plan.n_slabs} tile-row slab(s):")
                for slab in range(plan.n_slabs):
                    print(f"  slab {slab} -> {plan.assignment[slab].key}")
            t0 = time.perf_counter()
            tickets = [door.submit(SessionSpec.pagerank(
                n, dangling_vertices(adj).astype(np.uint8),
                max_iter=10 + 3 * i, tenant_id=f"pagerank-{i}"))
                for i in range(args.tenants)]
            tickets += [door.submit(SessionSpec.multiply(
                rng.standard_normal(n).astype(np.float32),
                tenant_id=f"burst-{i}")) for i in range(4)]
            tickets.append(door.submit(SessionSpec.bfs(
                np.array([0]), n, tenant_id="bfs-0")))
            door.drain(tickets, timeout=600)
            wall = time.perf_counter() - t0
            print(f"\ncluster of {args.hosts} hosts served {len(tickets)} "
                  f"tenants in {wall:.2f}s")
            for t in tickets:
                print(f"  {t.tenant_id}: host={t.host_key} "
                      f"iters={t.iterations} resubmits={t.resubmits}")
            agg = door.cluster_io_stats()
            print(f"cluster slow-tier reads: {agg.bytes_read / 1e6:.1f} MB")
            door.shutdown_hosts()
        return 0
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--waves", type=int, default=1,
                    help=">= 2 serves through a concurrent-wave "
                         "ServingFleet instead of one scheduler")
    ap.add_argument("--hosts", type=int, default=1,
                    help=">= 2 spawns that many local HostServer "
                         "processes and serves through the cross-host "
                         "ClusterFrontDoor instead")
    ap.add_argument("--partition", action="store_true",
                    help="with --hosts >= 2: also serve one wide iterative "
                         "query partitioned across every host (each host "
                         "scans only its nnz-balanced tile-row slab; the "
                         "front door stitches the row blocks per pass)")
    ap.add_argument("--optimize-store", action="store_true",
                    help="re-encode the store offline (degree-descending "
                         "column reorder + uint8 delta packing) and serve "
                         "the compressed replicas; prints the slow-tier "
                         "byte savings measured from IOStats")
    args = ap.parse_args()
    if args.hosts >= 2:
        return serve_cluster(args)
    adj, replicas, raw_nbytes = build_replicas(args)
    with replicas:
        if args.waves >= 2:
            return serve_fleet(adj, replicas, args, raw_nbytes)
        return serve_single_wave(adj, replicas, args, raw_nbytes)


if __name__ == "__main__":
    raise SystemExit(main())

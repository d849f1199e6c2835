"""Cross-host serving tier benchmark: the two-process localhost cluster.

Spawns real ``python -m repro.net.host`` processes — each with its OWN
copy of the operator store wrapped in the spindle-emulating throttle (one
lock + proportional sleep per store path), so every host owns one
emulated SSD spindle — and drives them through a
:class:`~repro.net.frontdoor.ClusterFrontDoor` over the wire protocol.

Two claims, mirroring the fleet section of ``bench_runtime`` one level up:

* **Scale-out across hosts.**  One host serializes a backlog of mixed
  tenants (multiply / power-iteration / PageRank / BFS, all riding the
  same column-stochastic operator) on its single spindle; two hosts with
  disjoint spindles clear the same backlog roughly twice as fast, because
  the front door's least-estimated-backlog routing keeps both streaming.
  The CI gate (``check_regression.py --runtime``) holds the 2-host/1-host
  speedup trajectory and an absolute >= 1.5x floor.
* **Host-level failover.**  Killing one host process mid-serve (SIGKILL,
  no goodbye) must not lose a tenant: the front door evicts the host on
  heartbeat/connection loss and resubmits its in-flight specs to the
  survivor, and — sessions being deterministic replays — every result is
  still bit-identical to a lone in-process ``ServingFleet``.  Asserted
  here and gated in CI.

* **Partitioned scale-out for one wide query.**  A single iterative query
  cannot be split by the tenant router — it is one tenant.  Submitted
  with ``partitioned=True``, each of its passes instead spans every live
  host, each scanning only its nnz-balanced tile-row slab of its own
  spindle, and the front door stitches the row blocks; 2 hosts must beat
  1 by >= 1.4x (gated in CI), and killing a slab host mid-query must
  reassign only the lost slab to the survivor, still bit-identically.

``REPRO_BENCH_QUICK=1`` shrinks the graph, iteration counts, and spindle
throttle to a seconds-long run.  All ten host processes (five for the
tenant-routing phases, five for the partitioned phases — each phase
shuts its hosts down when it finishes) are spawned up front so their
interpreter/jax import costs overlap instead of serializing across
phases.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.common import print_csv, save
from repro.apps.pagerank import build_operator, dangling_vertices
from repro.core.formats import to_chunked
from repro.io.storage import TileStore
from repro.net import ClusterFrontDoor
from repro.net.host import check_local_hosts_allowed
from repro.runtime import ReplicaSet, ServingFleet, SessionSpec
from repro.sparse.generate import rmat

QUICK = os.environ.get("REPRO_BENCH_QUICK") == "1"

# (rmat scale, power/pagerank iterations, spindle seconds per full pass,
#  per-wave column capacity, one-shot multiply tenants)
SCALE = 11 if QUICK else 13
ITERS = 8 if QUICK else 12
PASS_SECONDS = 0.1 if QUICK else 0.25
# The partitioned phases measure spindle ownership of ONE query's scan:
# a heavier throttle keeps the per-pass RPC/stitch overhead small against
# the slab scan time, and a finer tile grid (T=512 vs the tenant phases'
# 1024) gives the nnz-balanced tile-row split enough granularity to
# actually halve a skewed rmat store.
PART_PASS_SECONDS = 0.3 if QUICK else 0.75
PART_T = 512
CAPACITY = 4
N_MULTIPLY = 2 if QUICK else 4

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mixed_specs(adj, n: int) -> Tuple[List[SessionSpec], int]:
    """The mixed tenant backlog (every kind rides the one PageRank-operator
    store) and its total column-pass cost — the unit of served work."""
    rng = np.random.default_rng(41)
    specs: List[SessionSpec] = []
    col_passes = 0
    for i in range(N_MULTIPLY):
        x = rng.standard_normal(n).astype(np.float32)
        specs.append(SessionSpec.multiply(x, tenant_id=f"mul-{i}"))
        col_passes += 1
    for i in range(ITERS // 2):
        x0 = rng.standard_normal(n).astype(np.float32)
        specs.append(SessionSpec.power_iteration(
            x0, tol=0.0, max_iter=ITERS, tenant_id=f"pow-{i}"))
        col_passes += ITERS
    specs.append(SessionSpec.pagerank(
        n, dangling_vertices(adj).astype(np.uint8), tol=0.0, max_iter=ITERS,
        tenant_id="pr-0"))
    col_passes += ITERS
    specs.append(SessionSpec.bfs(
        np.array([0], dtype=np.int64), n, tenant_id="bfs-0"))
    col_passes += 1  # lower bound; BFS retires on frontier convergence
    return specs, col_passes


def _reference_results(path: str, specs: Sequence[SessionSpec]
                       ) -> Dict[str, np.ndarray]:
    """The lone in-process ServingFleet every cluster phase must match
    bit-for-bit (unthrottled — correctness, not timing)."""
    fleet = ServingFleet(ReplicaSet([TileStore.open(path)]), n_waves=1,
                         capacity=CAPACITY)
    try:
        sessions = [s.build() for s in specs]
        for s in sessions:
            fleet.submit(s)
        fleet.drain(300)
        return {s.tenant_id: np.asarray(s.result) for s in sessions}
    finally:
        fleet.close()


def _spawn_host(store_path: str,
                pass_seconds: float = PASS_SECONDS) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(REPO_ROOT, "src"),
                    env.get("PYTHONPATH", "")] if p)
    return subprocess.Popen(
        [sys.executable, "-m", "repro.net.host", "--store", store_path,
         "--waves", "1", "--capacity", str(CAPACITY), "--no-cache",
         "--throttle-pass-seconds", str(pass_seconds)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)


def _scrape_port(proc: subprocess.Popen, deadline_s: float = 120.0) -> int:
    t0 = time.time()
    while True:
        line = proc.stdout.readline()
        if line.startswith("LISTENING "):
            return int(line.split()[1])
        if proc.poll() is not None or time.time() - t0 > deadline_s:
            raise RuntimeError("host process died before LISTENING "
                               f"(rc={proc.returncode})")


def _warmup(ports: Sequence[int], n: int) -> None:
    """One throwaway multiply per host so every process pays its jit
    compile before the timed phases (all hosts in parallel)."""
    from concurrent.futures import ThreadPoolExecutor

    def one(port: int) -> None:
        door = ClusterFrontDoor(heartbeat_interval=0.2)
        try:
            door.add_host("127.0.0.1", port)
            door.submit(SessionSpec.multiply(
                np.ones(n, np.float32), tenant_id="warmup")).wait(300)
        finally:
            door.close()

    with ThreadPoolExecutor(len(ports)) as ex:
        list(ex.map(one, ports))


def _serve(ports: Sequence[int], specs: Sequence[SessionSpec],
           reference: Dict[str, np.ndarray],
           kill: Optional[subprocess.Popen] = None) -> dict:
    """Serve the backlog through a front door over ``ports``; returns wall
    seconds, host spread, and failover counters.  ``kill`` SIGKILLs that
    host process mid-serve (the failover phase)."""
    door = ClusterFrontDoor(heartbeat_interval=0.1, miss_limit=3,
                            deliver_poll_s=0.5)
    try:
        for p in ports:
            door.add_host("127.0.0.1", p)
        t0 = time.perf_counter()
        tickets = [door.submit(s) for s in specs]
        if kill is not None:
            time.sleep(2.5 * PASS_SECONDS)  # mid-pass, work still in flight
            kill.kill()
        door.drain(tickets, timeout=600)
        seconds = time.perf_counter() - t0
        for t in tickets:
            np.testing.assert_array_equal(t.result, reference[t.tenant_id])
        return {
            "seconds": seconds,
            "hosts_used": len({t.host_key for t in tickets}),
            "completed": sum(t.done for t in tickets),
            "resubmits": sum(t.resubmits for t in tickets),
            "evicted": len(door.evicted),
        }
    finally:
        door.shutdown_hosts()
        door.close()


def _serve_partitioned(ports: Sequence[int], n: int, spec: SessionSpec,
                       reference: Dict[str, np.ndarray],
                       kill: Optional[subprocess.Popen] = None) -> dict:
    """One wide query partitioned across ``ports``: every pass broadcasts
    the iterate and each host scans only its tile-row slab.  ``kill``
    SIGKILLs that host mid-query — only its slab should move."""
    door = ClusterFrontDoor(heartbeat_interval=0.1, miss_limit=3,
                            deliver_poll_s=0.5)
    try:
        for p in ports:
            door.add_host("127.0.0.1", p)
        # throwaway partitioned multiply: builds every host's lazy slab
        # executors and pays the slab-shaped jit compiles before timing
        door.submit(SessionSpec.multiply(np.ones(n, np.float32),
                                         tenant_id="pwarm"),
                    partitioned=True).wait(300)
        t0 = time.perf_counter()
        ticket = door.submit(spec, partitioned=True)
        if kill is not None:
            time.sleep(1.5 * PART_PASS_SECONDS)  # mid-query, slabs in flight
            kill.kill()
        result = ticket.wait(600)
        seconds = time.perf_counter() - t0
        np.testing.assert_array_equal(result, reference[spec.tenant_id])
        return {
            "seconds": seconds,
            "slabs": ticket.plan.n_slabs,
            "resubmits": ticket.resubmits,
            "reassignments": ticket.plan.reassignments,
            "evicted": len(door.evicted),
        }
    finally:
        door.shutdown_hosts()
        door.close()


def main() -> List[dict]:
    check_local_hosts_allowed()
    adj = rmat(SCALE, 8, seed=5)
    op = build_operator(adj)
    ct = to_chunked(op, T=1024, C=128)
    tmp = tempfile.mkdtemp(prefix="bench_net_")
    procs: List[subprocess.Popen] = []
    try:
        # one store copy per host process = one emulated spindle each,
        # plus an unthrottled copy for the in-process reference fleet
        paths = [os.path.join(tmp, f"store{i}") for i in range(6)]
        TileStore.write(paths[0], ct)
        for p in paths[1:]:
            shutil.copy(paths[0] + ".bin", p + ".bin")
            shutil.copy(paths[0] + ".json", p + ".json")
        # the partitioned phases get their own copies: same matrix, finer
        # tile grid (PART_T), heavier per-spindle throttle.  Bit-identity
        # is judged against a same-grid unthrottled reference — tile size
        # changes row grouping, so cross-grid bits are not comparable.
        ct_p = to_chunked(op, T=PART_T, C=128)
        ppaths = [os.path.join(tmp, f"pstore{i}") for i in range(6)]
        TileStore.write(ppaths[0], ct_p)
        for p in ppaths[1:]:
            shutil.copy(ppaths[0] + ".bin", p + ".bin")
            shutil.copy(ppaths[0] + ".json", p + ".json")

        # spawn all ten hosts up front: interpreter+jax imports overlap
        procs = [_spawn_host(p) for p in paths[1:]] + \
                [_spawn_host(p, PART_PASS_SECONDS) for p in ppaths[1:]]
        ports = [_scrape_port(pr) for pr in procs]

        n = op.shape[1]
        specs, col_passes = _mixed_specs(adj, n)
        rng = np.random.default_rng(43)
        pspec = SessionSpec.power_iteration(
            rng.standard_normal(n).astype(np.float32), tol=0.0,
            max_iter=ITERS, tenant_id="part-0")
        reference = _reference_results(paths[0], specs)
        preference = _reference_results(ppaths[0], [pspec])
        _warmup(ports[:5], n)

        one = _serve(ports[:1], specs, reference)
        two = _serve(ports[1:3], specs, reference)
        speedup = one["seconds"] / two["seconds"]
        fo = _serve(ports[3:5], specs, reference, kill=procs[3])
        print(f"  1 host: {one}\n  2 hosts: {two}\n  failover: {fo}")

        part1 = _serve_partitioned(ports[5:6], n, pspec, preference)
        part2 = _serve_partitioned(ports[6:8], n, pspec, preference)
        pspeedup = part1["seconds"] / part2["seconds"]
        pfo = _serve_partitioned(ports[8:10], n, pspec, preference,
                                 kill=procs[8])
        print(f"  partitioned 1 host: {part1}\n"
              f"  partitioned 2 hosts: {part2}\n"
              f"  partitioned failover: {pfo}")

        assert two["hosts_used"] == 2, \
            "front door left a registered host idle"
        assert speedup > 1.0, \
            f"2-host cluster slower than one host ({speedup:.2f}x)"
        assert fo["evicted"] == 1 and fo["resubmits"] >= 1, \
            f"kill-host phase saw no failover ({fo})"
        assert fo["completed"] == len(specs), \
            f"failover lost tenants ({fo['completed']}/{len(specs)})"
        assert part2["slabs"] == 2, \
            "partitioned query did not span both hosts"
        assert pspeedup > 1.0, \
            f"partitioned 2-host query slower than 1 host ({pspeedup:.2f}x)"
        assert pfo["evicted"] == 1 and pfo["resubmits"] >= 1 \
            and pfo["reassignments"] >= 1, \
            f"kill-slab-host phase saw no slab failover ({pfo})"

        rows = [
            {"workload": "cluster_throughput", "mode": "hosts-1",
             "hosts": 1, "tenants": len(specs), "seconds": one["seconds"],
             "col_passes_per_s": col_passes / one["seconds"]},
            {"workload": "cluster_throughput", "mode": "hosts-2",
             "hosts": 2, "tenants": len(specs), "seconds": two["seconds"],
             "col_passes_per_s": col_passes / two["seconds"]},
            {"workload": "cluster_failover", "mode": "hosts-2-kill-1",
             "hosts": 2, "tenants": len(specs), "seconds": fo["seconds"],
             "completed": fo["completed"], "resubmits": fo["resubmits"],
             "evicted": fo["evicted"], "bit_identical": 1},
            {"workload": "cluster_partitioned", "mode": "slabs-1",
             "hosts": 1, "passes": ITERS, "seconds": part1["seconds"]},
            {"workload": "cluster_partitioned", "mode": "slabs-2",
             "hosts": 2, "passes": ITERS, "seconds": part2["seconds"]},
            {"workload": "cluster_partitioned_failover",
             "mode": "slabs-2-kill-1", "hosts": 2, "passes": ITERS,
             "seconds": pfo["seconds"], "resubmits": pfo["resubmits"],
             "reassignments": pfo["reassignments"],
             "evicted": pfo["evicted"], "bit_identical": 1},
        ]
        print_csv("net_cluster_throughput", rows[:2])
        print_csv("net_cluster_failover", rows[2:3])
        print_csv("net_cluster_partitioned", rows[3:])
        print(f"  2-host speedup vs 1 host: {speedup:.2f}x "
              f"(failover resubmits: {fo['resubmits']}); partitioned "
              f"2-host speedup: {pspeedup:.2f}x "
              f"(slab reassignments: {pfo['reassignments']})")
        save("net_cluster", rows)
        return rows
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()

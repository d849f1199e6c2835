"""Benchmark orchestrator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,fig12] [--json]
                                          [--quick] [--json-out PATH]

Writes results/bench/<name>.json per bench and prints CSVs.  Asserts inside
each bench validate the paper's claims (byte formulas, balance bounds,
convergence) — a failed claim fails the run.

``--json`` additionally writes the machine-readable perf trajectories
tracked across PRs: repo-root ``BENCH_engine.json`` when the engine bench
runs (rows/s, bytes streamed, overlap %, pass counts per engine variant)
and repo-root ``BENCH_runtime.json`` when the serving-runtime bench runs
(boundaries/seconds to first result of elastic admission, fleet aggregate
throughput vs one wide wave, replica scan speedup).  Each file holds one
summary per mode (``full`` and ``quick``); a run updates its own mode's
block and leaves the other untouched.

``--quick`` exports ``REPRO_BENCH_QUICK=1`` before the benches import:
emulated-SSD sizes shrink to a seconds-long run (the CI regression gate's
mode — see ``benchmarks/check_regression.py``).  ``--json-out`` redirects
the summary (CI writes a scratch file and diffs it against the committed
trajectory instead of overwriting it); it names one output file, so use it
with a single trajectory bench selected via ``--only``."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = [
    ("fig2_format_size", "benchmarks.bench_format_size"),
    ("fig5_sem_vs_im", "benchmarks.bench_sem_vs_im"),
    ("fig6_sbm", "benchmarks.bench_sbm"),
    ("fig7_vs_baseline", "benchmarks.bench_vs_baseline"),
    ("fig8_memory", "benchmarks.bench_memory"),
    ("fig10_vertical", "benchmarks.bench_vertical"),
    ("fig12_opt_ablation", "benchmarks.bench_opt_ablation"),
    ("fig13_io_opts", "benchmarks.bench_io_opts"),
    ("table2_convert", "benchmarks.bench_convert"),
    ("fig14_16_apps", "benchmarks.bench_apps"),
    ("runtime_serving", "benchmarks.bench_runtime"),
    ("net_cluster", "benchmarks.bench_net"),
    ("engine", "benchmarks.bench_engine"),
    # after "engine": write_engine_json replaces its mode block wholesale,
    # while write_spgemm_json merges into it — this order keeps both
    ("spgemm", "benchmarks.bench_spgemm"),
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _merge_mode_json(summary, path, quick) -> str:
    """Write ``summary`` under the running mode's key — a quick run never
    clobbers the full-size trajectory and vice versa."""
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
        if "full" not in merged and "quick" not in merged:
            merged = {"full": merged}  # legacy flat schema
    merged["quick" if quick else "full"] = summary
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    return path


def write_engine_json(rows, out_path=None, quick=False) -> str:
    """Distill the engine ablation into BENCH_engine.json (the cross-PR perf
    trajectory file)."""
    summary = {
        "p": rows[0]["p"],
        "engines": [
            {k: r[k] for k in ("tier", "engine", "t_pass_ms", "rows_per_s",
                               "mb_streamed_per_pass", "h2d_mb_per_pass",
                               "overlap_pct", "passes")}
            for r in rows],
        "overlap_speedup_emulated": rows[0]["overlap_speedup_emulated"],
        "h2d_index_saving_mb": rows[0]["h2d_index_saving_mb"],
        "opt_store_shrink_pct": rows[0].get("opt_store_shrink_pct"),
    }
    path = out_path or os.path.join(REPO_ROOT, "BENCH_engine.json")
    return _merge_mode_json(summary, path, quick)


def write_runtime_json(rows, out_path=None, quick=False) -> str:
    """Distill the serving-runtime bench into BENCH_runtime.json: the
    elastic-admission time-to-first-result and the fleet's aggregate
    throughput vs one wide wave — the serving trajectory the CI gate
    (``check_regression.py --runtime``) holds across PRs."""
    ttfr = {r["mode"]: r for r in rows
            if r["workload"] == "ttfr_late_arrival"}
    fleet = {r["mode"]: r for r in rows
             if r["workload"] == "fleet_aggregate"}
    rep = {r["mode"]: r["seconds_to_result"] for r in rows
           if r["workload"] == "replica_scan"}
    wide = fleet["wide-1-wave"]["cols_per_s"]
    summary = {
        "boundaries_to_first_result": {
            m: ttfr[m]["boundaries_to_result"] for m in ttfr},
        "seconds_to_first_result": {
            m: ttfr[m]["seconds_to_result"] for m in ttfr},
        "fleet": {
            "spindles": 2,
            "capacity": fleet["wide-1-wave"]["capacity"],
            "wide_cols_per_s": wide,
            "fleet2_cols_per_s": fleet["fleet-2-waves"]["cols_per_s"],
            "fleet4_cols_per_s": fleet["fleet-4-waves"]["cols_per_s"],
            "fleet2_speedup_vs_wide":
                fleet["fleet-2-waves"]["cols_per_s"] / wide,
            "fleet4_speedup_vs_wide":
                fleet["fleet-4-waves"]["cols_per_s"] / wide,
        },
        "replica_scan_speedup":
            rep["sharded-1-spindle"] / rep["sharded-2-replicas"],
    }
    churn = {r["mode"]: r for r in rows
             if r["workload"] == "serve_under_churn"}
    if churn:
        overlay, compact = churn["churn-overlay"], churn["churn-compact"]
        summary["churn"] = {
            "churn_frac": overlay["churn_frac"],
            "frozen_s_per_pass": churn["frozen"]["seconds_per_pass"],
            "overlay_s_per_pass": overlay["seconds_per_pass"],
            "overhead_frac": overlay["overhead_frac"],
            "delta_nnz_peak": overlay["delta_nnz_peak"],
            "compaction_converged": bool(compact["compaction_converged"]),
            "generation": compact["generation"],
        }
    path = out_path or os.path.join(REPO_ROOT, "BENCH_runtime.json")
    return _merge_mode_json(summary, path, quick)


def write_net_json(rows, out_path=None, quick=False) -> str:
    """Distill the cross-host cluster bench into the ``cluster`` section of
    BENCH_runtime.json's mode block — merged *into* the block (the
    runtime_serving bench writes the rest of it, possibly in the same run
    via a shared ``--json-out``), never clobbering it."""
    thr = {r["mode"]: r for r in rows
           if r["workload"] == "cluster_throughput"}
    fo = next(r for r in rows if r["workload"] == "cluster_failover")
    one = thr["hosts-1"]["col_passes_per_s"]
    two = thr["hosts-2"]["col_passes_per_s"]
    summary = {
        "tenants": thr["hosts-1"]["tenants"],
        "hosts1_col_passes_per_s": one,
        "hosts2_col_passes_per_s": two,
        "hosts2_speedup_vs_1": two / one,
        "failover": {
            "tenants": fo["tenants"],
            "completed": fo["completed"],
            "resubmits": fo["resubmits"],
            "evicted": fo["evicted"],
            "bit_identical": bool(fo["bit_identical"]),
        },
    }
    part = {r["mode"]: r for r in rows
            if r["workload"] == "cluster_partitioned"}
    pfo = next((r for r in rows
                if r["workload"] == "cluster_partitioned_failover"), None)
    if part and pfo is not None:
        p1, p2 = part["slabs-1"]["seconds"], part["slabs-2"]["seconds"]
        summary["partitioned"] = {
            "passes": part["slabs-1"]["passes"],
            "hosts1_seconds": p1,
            "hosts2_seconds": p2,
            "hosts2_speedup_vs_1": p1 / p2,
            "failover": {
                "resubmits": pfo["resubmits"],
                "reassignments": pfo["reassignments"],
                "evicted": pfo["evicted"],
                "bit_identical": bool(pfo["bit_identical"]),
            },
        }
    path = out_path or os.path.join(REPO_ROOT, "BENCH_runtime.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
        if "full" not in merged and "quick" not in merged:
            merged = {"full": merged}
    block = merged.setdefault("quick" if quick else "full", {})
    block["cluster"] = summary
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    return path


def write_spgemm_json(rows, out_path=None, quick=False) -> str:
    """Distill the SpGEMM budget-vs-spill bench into the ``spgemm`` section
    of BENCH_engine.json's mode block — merged *into* the block (the engine
    bench writes the rest of it, possibly in the same run via a shared
    ``--json-out``), never clobbering it."""
    r = rows[0]
    summary = {k: r[k] for k in (
        "n", "nnz_a", "product_nnz", "partial_budget_bytes",
        "peak_partial_bytes", "spill_cycles", "merge_rounds",
        "products_per_s", "bit_identical")}
    path = out_path or os.path.join(REPO_ROOT, "BENCH_engine.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
        if "full" not in merged and "quick" not in merged:
            merged = {"full": merged}
    block = merged.setdefault("quick" if quick else "full", {})
    block["spgemm"] = summary
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    return path


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of name prefixes to run")
    ap.add_argument("--json", action="store_true",
                    help="also write the BENCH_engine.json summary")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="where --json writes (default: repo-root "
                         "BENCH_engine.json)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny emulated-SSD sizes (seconds; the CI gate)")
    args = ap.parse_args(argv)
    prefixes = args.only.split(",") if args.only else None
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"

    failures = []
    for name, module in BENCHES:
        if prefixes and not any(name.startswith(p) for p in prefixes):
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["main"])
            rows = mod.main()
            if args.json and name == "engine" and rows:
                out = write_engine_json(rows, args.json_out, args.quick)
                print(f"[bench] wrote {out}")
            if args.json and name == "runtime_serving" and rows:
                out = write_runtime_json(rows, args.json_out, args.quick)
                print(f"[bench] wrote {out}")
            if args.json and name == "net_cluster" and rows:
                out = write_net_json(rows, args.json_out, args.quick)
                print(f"[bench] wrote {out}")
            if args.json and name == "spgemm" and rows:
                out = write_spgemm_json(rows, args.json_out, args.quick)
                print(f"[bench] wrote {out}")
            print(f"[bench] {name}: ok ({time.time() - t0:.1f}s)\n")
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
            print(f"[bench] {name}: FAILED {e}\n")
    if failures:
        print("FAILURES:", failures)
        return 1
    print("all benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

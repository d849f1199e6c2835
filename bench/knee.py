#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest arrival rate the served path
sustains without a growing backlog, found once by a sweep on the chip.

  python3 bench/knee.py --workload <open-loop cell> --seed <n> \
      --seconds <s> --rates <r> [<r> ...]

One process builds the cell's data and fleet once, then offers each rate
for ``--seconds`` after a ramp and prints, per rate, the requests due,
those still owed at the window's close, and due-to-answer quantiles of
each third of the window: a backlog that grows shows as a later third
slower than the first.  The cell's rate is set in its traffic file at
four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    import jax

    from bench import meter as M
    from bench import run as R
    from bench import traffic

    cell = R.load_cell(args.workload)
    cfg, mix = cell.config, dict(cell.mix)
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(R.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    st, fl = cfg["store"], cfg["fleet"]
    served = R.build(cfg, mix, args.seed, os.path.join(R.CACHE, "knee"),
                     print)
    g, pool_t, meter, fleet = (served.graph, served.pool_t, served.meter,
                               served.fleet)
    try:
        R.warm_midpass_ops(-(-g.n // st["T"]), st["T"],
                           fleet.replicas.padded_cols, fl["capacity"],
                           mix["cols_per_request"])
        first = True
        for rate in args.rates:
            load = traffic.Load(fleet, dict(mix, rate_per_s=rate), pool_t,
                                args.seed, 0, args.seconds)
            if first:
                load.warm_up(fl["n_waves"], timeout=R.DRAIN_S)
                first = False
            load.start()
            t0 = load.window_start
            t1 = t0 + args.seconds
            time.sleep(max(0.0, t1 - time.perf_counter()))
            load.close_submissions()
            owed = [r for r in load.requests if t0 <= r.due < t1]
            still = sum(1 for r in owed if r.done is None)
            fleet.drain(timeout=R.DRAIN_S)
            load.stop()
            thirds = []
            for k in range(3):
                a = t0 + k * (t1 - t0) / 3
                b = t0 + (k + 1) * (t1 - t0) / 3
                lat = [r.done - r.due for r in owed if a <= r.due < b]
                thirds.append([M.quantile(lat, 0.5), M.quantile(lat, 0.95)])
            lat = [r.done - r.due for r in owed]
            print(json.dumps({
                "rate_per_s": rate, "due": len(owed),
                "owed_at_close": still,
                "p50_s": M.quantile(lat, 0.5), "p95_s": M.quantile(lat, 0.95),
                "thirds_p50_p95_s": thirds,
                "edge_cols_per_s": meter.edge_cols(t0, t1) / (t1 - t0),
                "lateness_max_s": float(max(load.lateness, default=0.0))}),
                flush=True)
    finally:
        fleet.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

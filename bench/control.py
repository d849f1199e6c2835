#!/usr/bin/env python3
"""The control of ``correct`` at a cell's own size: the plain reference
put in the program's place one precision down (``reference.
control_answers``), held to the same comparison as a run's sampled
answers.  It has to read above the configuration's limit on every seed.

  python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

Prints one JSON line per seed with the control's ``max_rel_err`` and the
limit.  The benchmark's own runs do not run it.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from bench import graph as G
    from bench import reference, traffic
    from bench.run import load_cell

    cell = load_cell(args.workload)
    g_cfg, T = cell.config["graph"], cell.config["store"]["T"]
    limit = cell.config["correct"]["max_rel_err"]
    for seed in args.seeds:
        t = time.perf_counter()
        g = G.from_config(g_cfg, seed, T)
        pool = np.asarray(jax.random.normal(
            jax.random.fold_in(G.seed_key(seed), 0x9001),
            (cell.mix["pool_cols"], g.n), jax.numpy.float32)).T
        A = reference.csr(g.n, g.T, g.hi, g.lo)
        idx, keep, _ = traffic.plan(cell.mix, seed)
        reqs = list(idx[np.flatnonzero(keep)[:cell.mix["max_sampled"]]])
        err = reference.max_rel_err(
            A, pool, reference.control_answers(A, pool, reqs))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_max_rel_err": err, "limit": limit,
                          "fails_limit": err > limit,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

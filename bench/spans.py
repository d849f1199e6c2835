#!/usr/bin/env python3
"""The program's own host spans (``repro.trace``, named ``sem.*``) in a
traced run, and where they put the device's idle time.

``run.py``'s reduction keeps only the benchmark's spans (``bench.*``).
This module reads the program's as well, on the trace's one clock:

- ``program_events``: the program's spans in a trace directory, with the
  arguments they carry (bytes, tenants);
- ``span_seconds``: host seconds of one span inside the window;
- ``idle_by_span``: each idle gap split among the innermost span covering
  each instant (the shortest), ``none`` where none covers it;
- ``summary``: milliseconds a pass of every span, the idle split and the
  share of idle time under a program span, and how much of each period
  between pass ends the wave thread's spans cover.

Run as a script it makes one traced run of a cell through ``run.run`` and
prints its result line with a ``spans`` entry added (its result is the
traced run's; the end-to-end numbers come from untraced runs):

  python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import glob
import heapq
import json
import os
import statistics
import sys
import time
from typing import Dict, List, Tuple

PREFIX = "sem."
# per pass: what the wave thread does from one pass end to the next
WAVE_THREAD = ("wave_wait", "pack", "prepare_x", "stream", "sync",
               "copyback", "deliver")
SPANS = ("wave_wait", "pass", "pack", "prepare_x", "stream", "read_wait",
         "stage", "step", "boundary", "sync", "copyback", "deliver")


def program_events(trace_dir: str) -> List[list]:
    """[[name, start_ns, dur_ns, {arg: value}], ...] of every host event
    named with the program's prefix, from the newest ``.xplane.pb`` under
    ``trace_dir`` (the one ``trace_reduce.extract`` reads)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns),
                                {k: v for k, v in e.stats}])
    return out


def span_seconds(reduced, name: str) -> float:
    """Host seconds of the events named ``name``, clipped to the window."""
    return sum(max(0, min(s + d, reduced.t1) - max(s, reduced.t0))
               for n, s, d in reduced.host if n == name) / 1e9


def idle_by_span(reduced) -> Dict[str, float]:
    """Idle seconds of the window, every chip's, by the innermost host span
    covering each idle instant (``none`` where no span covers it).
    Innermost is the shortest span covering the instant, so a gap that
    several spans cover in turn is split among them; the values sum to the
    idle seconds."""
    out: Dict[str, float] = {}
    for chip in range(reduced.chips):
        for name, secs in _split_gaps(reduced,
                                      reduced.idle_gaps(chip)).items():
            out[name] = out.get(name, 0.0) + secs
    return out


def _split_gaps(reduced, gaps) -> Dict[str, float]:
    t0, t1 = reduced.t0, reduced.t1
    marks = []  # (time, 0 = a span ends / 1 = starts, duration, name)
    for n, s, d in reduced.host:
        if d > 0 and s < t1 and s + d > t0:
            marks += [(s, 1, d, n), (s + d, 0, d, n)]
    marks.sort()
    marks.append((t1, 0, 0, None))
    out: Dict[str, float] = {}
    live: List[Tuple[int, str]] = []    # heap of (duration, name)
    ended: Dict[Tuple[int, str], int] = {}
    prev, g = t0, 0
    for t, starts, d, n in marks:
        t = min(max(t, t0), t1)
        if t > prev:
            while g < len(gaps) and gaps[g][1] <= prev:
                g += 1
            idle, h = 0, g
            while h < len(gaps) and gaps[h][0] < t:
                idle += min(t, gaps[h][1]) - max(prev, gaps[h][0])
                h += 1
            if idle:
                while live and ended.get(live[0], 0):
                    ended[live[0]] -= 1
                    heapq.heappop(live)
                name = live[0][1] if live else "none"
                out[name] = out.get(name, 0.0) + idle / 1e9
            prev = t
        if starts:
            heapq.heappush(live, (d, n))
        elif n is not None:
            ended[(d, n)] = ended.get((d, n), 0) + 1
    return out


def wave_thread_cover(reduced) -> List[Tuple[float, float]]:
    """(period s, share of it under the wave thread's spans) for each pair
    of consecutive ``sem.pass`` ends inside the window."""
    ends = sorted(s + d for n, s, d in reduced.host
                  if n == PREFIX + "pass" and reduced.t0 <= s + d
                  <= reduced.t1)
    names = {PREFIX + w for w in WAVE_THREAD}
    out = []
    for a, b in zip(ends, ends[1:]):
        cov = sum(max(0, min(s + d, b) - max(s, a))
                  for n, s, d in reduced.host if n in names)
        out.append(((b - a) / 1e9, cov / (b - a)))
    return out


def summary(run, args: Dict[str, dict]) -> dict:
    """What the program's spans say about one traced run (``run`` is
    ``run.RunData``; ``args`` the arguments of each span's last event)."""
    from bench import trace_reduce
    from bench.roofline import window_work

    tr = run.trace
    _, Z = window_work(run)
    passes = Z / run.nnz
    idle = idle_by_span(tr)
    total = sum(idle.values())
    cover = wave_thread_cover(tr)
    count = {w: sum(1 for n, s, d in tr.host
                    if n == PREFIX + w and tr.t0 <= s < tr.t1)
             for w in SPANS}
    return {
        "passes_booked": passes,
        "ms_per_pass": {w: (1e3 * span_seconds(tr, PREFIX + w) / passes
                            if passes else None) for w in SPANS},
        "events_in_window": count,
        "idle_by_span": trace_reduce.top(idle, 16),
        "idle_named_share": (sum(v for n, v in idle.items()
                                 if n.startswith(PREFIX)) / total
                             if total else None),
        "periods_s": [p for p, _ in cover],
        "wave_thread_cover": [c for _, c in cover],
        "median_cover": (statistics.median(c for _, c in cover)
                         if cover else None),
        "args": args,
    }


def traced_run(cell, seed: int, seconds: float, log, **kw) -> dict:
    """One traced run of ``cell`` through ``run.run``, its reduction also
    keeping the program's spans; the result line gains ``spans``."""
    from bench import run as run_mod
    from bench import trace_reduce

    seen: dict = {}
    base_extract, base_data = trace_reduce.extract, run_mod.RunData

    def extract(trace_dir):
        events = base_extract(trace_dir)
        prog = program_events(trace_dir)
        events["host"] += [e[:3] for e in prog]
        seen["args"] = {e[0]: e[3] for e in prog if e[3]}
        seen["n_events"] = len(prog)
        seen["xplane_bytes"] = trace_reduce.xplane_bytes(trace_dir)
        return events

    def data(**fields):
        seen["run"] = base_data(**fields)
        return seen["run"]

    trace_reduce.extract, run_mod.RunData = extract, data
    try:
        result = run_mod.run(cell, seed, seconds, True, log=log, **kw)
    finally:
        trace_reduce.extract, run_mod.RunData = base_extract, base_data
    spans = summary(seen["run"], seen["args"])
    spans["program_events"] = seen["n_events"]
    spans["xplane_bytes"] = seen["xplane_bytes"]
    result["spans"] = spans
    return result


def main(argv=None) -> int:
    import argparse

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as run_mod

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    def log(line):
        print(f"[{time.perf_counter() - t_start:8.3f}] {line}",
              file=sys.stderr, flush=True)

    try:
        result = traced_run(run_mod.load_cell(args.workload), args.seed,
                            args.seconds, log)
    except run_mod.NoDevice as e:
        log(f"spans: {e}")
        return run_mod.NO_DEVICE
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""The rehearsal mode: a run of a cell at a tiny size on the CPU backend,
through every layer the chip run drives.  Reachable from the tests only.

A cell kept for a later benchmark change (``later/<cell>.json``: the
workload, its configuration entry where no cell there uses it, and its
own metrics as ``BENCHMARK.json`` would hold them, and under ``joins``
the metrics already there that it reports too) rehearses
from a checkout whose ``BENCHMARK.json`` holds it as well."""
import glob
import json
import os

from bench import run

SIZES = {"graph": {"scale": 12}, "store": {"T": 512, "C": 128},
         "sem": {"chunk_batch": 4}, "fleet": {"capacity": 16},
         "mix": {"ramp_s": 1.0, "sample_share": 0.5, "rate_per_s": 8.0}}


def spec_with_later() -> dict:
    """``BENCHMARK.json`` with every kept-for-later cell added, its file
    paths made absolute."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for path in sorted(glob.glob(os.path.join(run.BENCH, "later", "*.json"))):
        with open(path) as f:
            later = json.load(f)
        cell = later["workload"]["name"]
        if later["config"] is not None:
            spec["configs"].append(later["config"])
        spec["workloads"].append(later["workload"])
        spec["end_to_end"] += later["end_to_end"]
        spec["per_layer"] += later["per_layer"]
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in later["joins"]:
                m["workloads"].append(cell)
    spec["paths"] = [os.path.join(run.ROOT, p) for p in spec["paths"]]
    for c in spec["configs"]:
        c["file"] = os.path.join(run.ROOT, c["file"])
    return spec


def later_cells() -> list:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        now = {w["name"] for w in json.load(f)["workloads"]}
    return [w["name"] for w in spec_with_later()["workloads"]
            if w["name"] not in now]


def rehearse(cell_name, seed, tmp_path, seconds=2.0, trace=False,
             root=None):
    if root is None:
        root = run.ROOT
        if cell_name in later_cells():
            root = os.path.join(str(tmp_path), "with-later")
            os.makedirs(root, exist_ok=True)
            with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
                json.dump(spec_with_later(), f)
    cell = run.load_cell(cell_name, root=root)
    lines = []
    result = run.run(cell, seed, seconds, trace, rehearsal=SIZES,
                     workdir=os.path.join(str(tmp_path), "data"),
                     log=lines.append)
    return result, lines

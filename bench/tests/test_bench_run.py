"""bench/run.py end to end on the CPU: refusal without a TPU, and a
rehearsal of every cell that comes out correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.rehearsal import later_cells, rehearse, spec_with_later

RUN = os.path.join(run.BENCH, "run.py")


def _spec():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "g22-full", "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(run.ROOT, RUN)
    assert p.returncode == run.NO_DEVICE
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_refuses_without_the_system_under_test(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits non-zero and prints no result."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _cli(str(tmp_path), str(tmp_path / "bench" / "run.py"))
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]]
                         + later_cells())
def test_rehearsal_is_correct(cell, tmp_path):
    result, lines = rehearse(cell, 2**31 + 17, tmp_path)
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in spec_with_later()["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert checks["max_rel_err"]["value"] < checks["max_rel_err"]["limit"]
    assert any("0 compiles inside it" in ln for ln in lines), lines


def test_traced_rehearsal_reports_the_counters(tmp_path):
    result, lines = rehearse("g22-full", 5, tmp_path, trace=True)
    assert result["correct"], lines
    m = result["metrics"]
    # counters only: the CPU backend has no device plane to read
    for name in ("stream_mb_per_pass.full", "h2d_mb_per_pass.full",
                 "overlap_share.full", "wave_fill_share.full"):
        assert m[name]["value"] > 0
    assert "step_roofline.full" not in m
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}

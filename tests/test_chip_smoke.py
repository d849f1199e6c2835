"""CPU rehearsals of ``chip_smoke.py``: its phase functions run end to end
at a tiny scale on the CPU backend (the Pallas kernel interpreted, because
the backend is the CPU), with every reference check on; its entry point
refuses to run without a TPU; and the multi-process launchers refuse to
spawn host processes from a process on an accelerator."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(REPO, "src"), env.get("PYTHONPATH", "")]
        if p)
    env.update(extra)
    return env


@pytest.mark.parametrize("T,C,batch", [
    (16384, 2048, 256),   # the engine's real tiles: gather kernel
    (512, 128, 16),       # many tile rows and batches: MXU kernel
])
def test_phases_rehearse_on_cpu(chip_smoke, T, C, batch):
    """Every phase of the one-chip smoke — scan engine on the raw and the
    packed store with multiply/PageRank/BFS/SSSP tenants, then the Pallas
    engine, then its MXU variant at T = 2048 — agrees with the NumPy/SciPy
    reference (``run_phases`` raises on any failed check)."""
    lines = []
    chip_smoke.run_phases(10, seed=1, T=T, C=C, chunk_batch=batch,
                          log=lines.append)
    phases = [json.loads(line[len("phase "):]) for line in lines
              if line.startswith("phase ")]
    assert [p["phase"] for p in phases] == [
        "scan-raw", "scan-packed", "pallas-raw", "pallas-packed",
        "pallas-mxu-raw"]
    for p in phases:
        assert p["passes"] >= 1 and p["bytes_streamed"] > 0
        assert set(p["checks"].values()) == {"ok"}
    assert set(phases[0]["checks"]) == {
        "multiply", "pagerank-4", "pagerank-8", "bfs", "sssp"}
    assert "multiply vs scan" in phases[2]["checks"]
    assert set(phases[4]["checks"]) == {
        "multiply", "pagerank-4", "pagerank-8"}


def test_sharded_phase_rehearses_on_four_cpu_devices():
    """``--chips 4``'s path on four virtual CPU devices: one shard per
    device, sharded multiply == single-device multiply, PageRank against
    the reference."""
    code = ("import chip_smoke; chip_smoke.run_sharded("
            "10, seed=2, chips=4, T=256, C=64, chunk_batch=16)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded: shard devices [[0], [1], [2], [3]]" in out.stdout
    assert '"sharded==single": "ok"' in out.stdout


def test_smoke_refuses_a_cpu_backend():
    """Without a TPU the script exits non-zero before any phase, and prints
    no result line."""
    out = subprocess.run([sys.executable, SMOKE, "--scale", "8"], cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "phase" not in out.stdout
    assert "needs 1 TPU chip" in out.stderr


def test_local_host_processes_refused_off_cpu(monkeypatch):
    """A launcher that has touched JAX on an accelerator holds the chip, so
    spawning ``repro.net.host`` children is refused before they start."""
    import jax
    from repro.net.host import check_local_hosts_allowed
    check_local_hosts_allowed()   # the CPU backend: any number of hosts
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one chip per host process"):
        check_local_hosts_allowed()

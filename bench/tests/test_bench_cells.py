"""A later change adds a cell as files alone: a configuration, a traffic
mix and a metric reader, found by the names in BENCHMARK.json."""
import json
import os
import shutil

from bench import run
from bench.tests.rehearsal import rehearse


def _new_cell_checkout(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    with open(os.path.join(run.BENCH, "configs", "graph500-22.json")) as f:
        config = json.load(f)
    config["name"] = "sparser-22"
    config["graph"]["edge_factor"] = 8
    (bench / "configs" / "sparser-22.json").write_text(json.dumps(config))
    (bench / "traffic" / "pairs.json").write_text(json.dumps({
        "loop": "closed", "cols_per_request": 2, "pool_cols": 16,
        "sample_share": 0.5, "max_sampled": 8}))
    (bench / "metrics" / "answers_per_s.py").write_text(
        "def read(run):\n"
        "    done = [r for r in run.requests\n"
        "            if r.done is not None and run.t0 <= r.done < run.t1]\n"
        "    return len(done) / (run.t1 - run.t0)\n")
    shutil.copy(os.path.join(run.BENCH, "metrics", "setup_s.py"),
                bench / "metrics" / "setup_s.py")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "sparser-22", "source": "https://example.org",
                     "file": "bench/configs/sparser-22.json",
                     "reduced": ["edge_factor"], "why": "test"}],
        "workloads": [{"name": "s22-pairs", "config": "sparser-22",
                       "traffic": "pairs", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "answers_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def test_new_cell_is_found_by_name(tmp_path):
    root = _new_cell_checkout(tmp_path)
    cell = run.load_cell("s22-pairs", root=root)
    assert cell.config["graph"]["edge_factor"] == 8
    assert cell.mix["cols_per_request"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["answers_per_s",
                                                    "setup_s"]
    assert callable(run.metric_reader(cell.bench_dir, "answers_per_s"))


def test_new_cell_runs_from_its_files(tmp_path):
    root = _new_cell_checkout(tmp_path)
    result, lines = rehearse("s22-pairs", 3, tmp_path, root=root)
    assert result["correct"], lines
    assert result["metrics"]["answers_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["unit"] == "s"

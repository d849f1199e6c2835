"""BENCHMARK.json and the files it names hold together."""
import json
import os
import re

import pytest

from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_and_unit_is_well_formed(spec):
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
             + [w["traffic"] for w in spec["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(0.01 <= m["bound"] <= 0.25 for m in spec["end_to_end"])


def test_every_file_a_name_points_at_exists(spec):
    for c in spec["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.mix["loop"] in ("open", "closed")
        assert cell.per_layer and any(m["name"] == "setup_s"
                                      for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.metric_reader(run.BENCH, m["name"]))
        for w in m.get("workloads", []):
            assert any(x["name"] == w for x in spec["workloads"])
    for m in spec["per_layer"]:
        assert any(e["name"] == m["moves"] for e in spec["end_to_end"])


def test_each_budget_holds_the_fleets_waves(spec):
    for c in spec["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            cfg = json.load(f)
        n = 1 << cfg["graph"]["scale"]
        C = cfg["store"]["C"]
        record = 16 + 4 * C + (8 if cfg["store"]["layout"] == "packed"
                               else 0)
        assert cfg["sem"]["memory_budget_bytes"] == run.fleet_budget(
            n, cfg["store"]["T"], record, cfg["sem"], cfg["fleet"])

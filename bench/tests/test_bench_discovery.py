"""Every cell, configuration, traffic mix and per-layer metric that
``BENCHMARK.json`` names is found by its name, and the file keeps the
benchmark's shape."""
import importlib
import json
import re

import pytest

from bench import harness
from bench.tests.rehearse import benchmark_with_staged

BM = harness.load_json(harness.ROOT / "BENCHMARK.json")
WITH_STAGED = benchmark_with_staged(harness)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"][1] == "bench/run.py"
    assert len(json.dumps(BM)) < 64 * 1024


def test_names_are_unique_and_well_formed():
    names = ([c["name"] for c in BM["configs"]] + CELLS
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for group in (BM["configs"], BM["workloads"],
                  BM["end_to_end"] + BM["per_layer"]):
        assert len({g["name"] for g in group}) == len(group)


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", [w["name"] for w in WITH_STAGED["workloads"]])
def test_cell_resolves(name):
    spec = harness.cell_spec(WITH_STAGED, name)
    importlib.import_module(f"bench.kinds.{spec.config['kind']}")
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer
    assert spec.limits


@pytest.mark.parametrize("metric",
                         [m["name"] for m in WITH_STAGED["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in WITH_STAGED["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x["name"]
                   for x in harness.cell_spec(WITH_STAGED, cell).end_to_end}
            assert m["moves"] in e2e, (m["name"], cell)


def test_unknown_workload_exits():
    with pytest.raises(SystemExit):
        harness.cell_spec(BM, "no-such-cell")

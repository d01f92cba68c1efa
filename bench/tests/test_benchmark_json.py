"""``BENCHMARK.json`` against the files the harness finds by name, and a
cell's untraced result line at its CPU size."""
from __future__ import annotations

import pytest

from bench import harness
from bench.tests.small import run_small

BENCH = harness.benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist(cell):
    w = CELLS[cell]
    config, mix = harness.cell_files(w["config"], w["traffic"])
    assert (harness.BENCH / "systems" / f"{config['system']}.py").is_file()
    assert mix["kind"]
    assert any(c["name"] == w["config"] for c in BENCH["configs"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_workloads_are_cells(metric):
    m = next(m for m in METRICS if m["name"] == metric)
    assert set(m.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    assert callable(reader.read)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_an_end_to_end_metric_besides_setup(cell):
    names = {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])}
    assert "setup_s" in names and names - {"setup_s"}
    assert any(cell in m.get("workloads", [cell]) for m in BENCH["per_layer"])


def test_at_most_half_the_cells_take_four_chips():
    four = sum(1 for w in CELLS.values() if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in CELLS.values())
    assert four <= max(len(CELLS) // 2, 1)


def test_direct_reports_its_end_to_end_metrics_only():
    result = run_small("wsi_4k.direct")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"tiles_per_s", "setup_s"}
    assert result["metrics"]["tiles_per_s"]["value"] > 0

"""The ``wsi_4k_ws`` cell at a CPU size: a sound run is correct and reports
the split's loops, and the bfloat16 control and faults in the split are
not correct.  Run with ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``."""
from __future__ import annotations

import time

import pytest

from bench import control_ws, faults_ws, harness
from bench.tests.small import CPU

CELL = "wsi_4k_ws.direct"


def small_ws() -> tuple[dict, dict, dict]:
    """The cell's files with its tiles cut to 256 pixels and one base tile,
    and the check's tiles drawn from the first four."""
    config, mix = harness.cell_files("wsi_4k_ws", "direct")
    config["tile"] = 256
    config["pool_base_tiles"] = 1
    mix["check_from_first"] = 4
    return harness.benchmark(), config, mix


def run_small_ws(seed: int = 2**33 + 5, trace: bool = False, seconds: float = 3) -> dict:
    bench, config, mix = small_ws()
    return harness.run_loaded(bench, CELL, config, mix, seed, seconds, trace,
                              time.perf_counter(), dict(CPU))


def failed(result: dict) -> list[str]:
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct_and_counts_the_split():
    result = run_small_ws()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tiles_per_s", "setup_s"}
    traced = run_small_ws(seed=11, trace=True)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["ws_iters_per_tile"]["value"] >= 6  # one call per loop at least


def test_control_in_bfloat16_fails():
    _, config, mix = small_ws()
    checks = {n: (v, lim) for n, v, lim in control_ws.control(config, mix, seed=7)}
    assert checks["hema_gap"][0] > checks["hema_gap"][1]
    assert checks["feat_gap"][0] > checks["feat_gap"][1] or checks["objects_off"][0] > 0


@pytest.mark.parametrize("fault", sorted(faults_ws.FAULTS))
def test_split_fault_is_caught(monkeypatch, fault):
    faults_ws.FAULTS[fault](monkeypatch)
    result = run_small_ws(seconds=10)  # a fault may compile in the window
    assert not result["correct"], result["checks"]
    assert {"label_px", "objects_off"} & set(failed(result)), result["checks"]

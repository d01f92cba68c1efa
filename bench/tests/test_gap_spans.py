"""Idle gaps put down to the program's spans (``bench/spans.py``), on a
hand-built trace whose numbers are known, on a trace the profiler writes
here, and on a ``wsi_4k.rt`` traced run recorded on a TPU v5e (the events
``bench/spans.py`` keeps); and the result line's ``breakdown`` built from
them."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from bench import harness, spans, trace
from bench.harness import Run

DATA = Path(__file__).parent / "data"
MS = 1_000_000  # ns


def hand_built() -> dict:
    # window 0..100 ms; the device busy 10..40 and 70..80
    ops = [["fusion.1", 10 * MS, 30 * MS], ["fusion.7", 70 * MS, 10 * MS]]
    bench_spans = [["bench.window", 0, 100 * MS], ["bench.stage_in", 0, 10 * MS],
                   ["bench.runtime", 40 * MS, 30 * MS], ["bench.read_back", 80 * MS, 18 * MS]]
    return {
        "devices": {"/device:TPU:0": {"XLA Ops": ops}},
        "host": bench_spans,
        "spans": [[*b, 0] for b in bench_spans] + [
            ["rt.tier.put/DMS", 1 * MS, 3 * MS, 0],
            ["rt.stage/Segmentation", 41 * MS, 28 * MS, 1],
            ["rt.stage.inputs", 42 * MS, 20 * MS, 1],
            ["rt.tier.get/DMS", 43 * MS, 18 * MS, 1],
            ["rt.tier.promote", 45 * MS, 5 * MS, 1],
            ["rt.task/ObjectROIs", 20 * MS, 4 * MS, 2],
            ["rt.task/ObjectROIs", 50 * MS, 10 * MS, 2],
            ["rt.tier.put/MEM", 85 * MS, 10 * MS, 3],
        ],
    }


def test_gaps_go_to_the_innermost_program_spans():
    r = spans.reduce(hand_built())
    # 0..10: the put on thread 0 takes 1..4, the benchmark's span the rest;
    # 40..70: program spans take all but the edges outside them, and 50..60
    # is split between threads 1 and 2; 80..100: the put on thread 3, the
    # benchmark's span, and 98..100 under no span but the window
    assert r["gaps"] == {
        "bench.stage_in": pytest.approx(0.007), "rt.tier.put/DMS": pytest.approx(0.003),
        "bench.runtime": pytest.approx(0.002), "rt.stage/Segmentation": pytest.approx(0.008),
        "rt.stage.inputs": pytest.approx(0.002), "rt.tier.get/DMS": pytest.approx(0.008),
        "rt.tier.promote": pytest.approx(0.005), "rt.task/ObjectROIs": pytest.approx(0.005),
        "bench.read_back": pytest.approx(0.008), "rt.tier.put/MEM": pytest.approx(0.01),
        "none": pytest.approx(0.002),
    }
    assert r["spans"]["rt.task/ObjectROIs"] == {"seconds": pytest.approx(0.014), "calls": 2}
    assert r["spans"]["bench.runtime"] == {"seconds": pytest.approx(0.03), "calls": 1}
    # a span inside another of its group (the promotion in the get) counts once
    assert r["groups"]["rt.tier"] == pytest.approx(0.003 + 0.018 + 0.010)
    assert r["groups"]["rt.stage"] == pytest.approx(0.028)


def test_span_readers(monkeypatch):
    tiles = {"before": {"tiles": 3}, "after": {"tiles": 5}}
    tier_io = harness.load_module(harness.BENCH / "metrics" / "tier_io_s_per_tile.py")
    roi = harness.load_module(harness.BENCH / "metrics" / "roi_host_ms.py")
    run = Run("wsi_4k.rt", {}, {}, tiles, {}, {}, {})
    monkeypatch.setattr(spans, "read", lambda run: spans.reduce(hand_built()))
    assert tier_io.read(run) == pytest.approx(0.031 / 2)
    assert roi.read(run) == pytest.approx(7.0)
    # the trace of a program without spans, as the parent of the spans gives
    bare = dict(hand_built())
    bare["spans"] = [[*b, 0] for b in bare["host"]]
    monkeypatch.setattr(spans, "read", lambda run: spans.reduce(bare))
    assert tier_io.read(run) is None and roi.read(run) is None
    monkeypatch.setattr(spans, "read", lambda run: None)  # not traced
    assert tier_io.read(run) is None and roi.read(run) is None


def test_read_finds_the_runs_trace(tmp_path, monkeypatch):
    from repro.core.spans import span

    monkeypatch.setattr(spans, "OUT", tmp_path)
    run = Run("c.t", {}, {}, {}, {}, {"devices": 0}, {})
    assert spans.read(run) is None  # traced, but no trace left
    jax.profiler.start_trace(str(tmp_path / "trace" / "c.t"))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("rt.tier.get/MEM", bytes=1):
                pass
    finally:
        jax.profiler.stop_trace()
    r = spans.read(run)
    assert r["spans"]["rt.tier.get/MEM"]["calls"] == 1
    assert r["groups"]["rt.tier"] > 0
    assert spans.read(Run("c.t", {}, {}, {}, {}, None, {})) is None


def _inside(events: dict, name: str) -> dict[str, float]:
    """Idle seconds inside the benchmark's spans ``name``, by span."""
    out: dict[str, float] = {}
    t0, t1 = trace.window_bounds(events)
    for n, s, d in events["host"]:
        if n == name and min(s + d, t1) > max(s, t0):
            window = [["bench.window", max(s, t0), min(s + d, t1) - max(s, t0)]]
            others = [h for h in events["host"] if h[0] != trace.WINDOW]
            clipped = dict(events, host=window + others)
            for k, v in spans.reduce(clipped)["gaps"].items():
                out[k] = out.get(k, 0.0) + v
    return out


def test_recorded_tpu_trace_puts_idle_time_under_program_spans():
    events = trace.read_saved(DATA / "wsi_4k.rt.spans.json.gz")
    r = spans.reduce(events)
    before = trace.reduce(events)
    assert sum(r["gaps"].values()) == pytest.approx(before["window_s"] - before["busy_s"])
    assert {"rt.tier.promote", "rt.tier.get/DMS", "rt.tier.put/DMS",
            "rt.task/ObjectROIs"} <= set(r["gaps"])
    runtime = _inside(events, "bench.runtime")
    staging = _inside(events, "bench.stage_in")
    program = [sum(v for k, v in part.items() if k.startswith("rt."))
               for part in (runtime, staging)]
    assert program[0] >= 0.8 * sum(runtime.values())
    assert sum(program) >= 0.8 * (sum(runtime.values()) + sum(staging.values()))
    assert r["spans"]["rt.task/ObjectROIs"]["calls"] == 16
    assert r["groups"]["rt.tier"] > 0


def test_result_breakdown_puts_idle_gaps_under_program_spans(monkeypatch):
    events = trace.read_saved(DATA / "wsi_4k.rt.spans.json.gz")
    program = spans.reduce(events)
    run = Run("wsi_4k.rt", {}, {}, {}, {}, trace.reduce(events), {})
    monkeypatch.setattr(spans, "read", lambda run: program)
    b = harness.breakdown(run)
    top = sorted(program["gaps"].items(), key=lambda kv: -kv[1])[:10]
    assert b["idle_gaps"] == [[k, v] for k, v in top] and len(top) == 10
    assert {"rt.tier.get/DMS", "rt.tier.put/DMS", "rt.tier.promote"} <= {k for k, _ in top}
    assert b["device_ops"] == trace.breakdown(run.trace)["device_ops"]
    # a program that opened no span of its own: the benchmark's spans alone
    bare = dict(events, spans=[[*h, 0] for h in events["host"]])
    monkeypatch.setattr(spans, "read", lambda run: spans.reduce(bare))
    b = harness.breakdown(run)
    assert b == trace.breakdown(run.trace)
    assert all(k.startswith("bench.") or k == "none" for k, _ in b["idle_gaps"])

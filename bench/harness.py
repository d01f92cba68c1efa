"""One run of one benchmark cell: set-up, measured window, check, result line.

Everything that belongs to one cell is found by name:

* the cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
  traffic mix;
* the configuration ``bench/configs/<config>.json`` names the ``system``
  module (``bench/systems/<system>.py``) that builds the deployment, and
  holds the limits its check compares against;
* the mix ``bench/mixes/<traffic>.json`` holds the traffic's parameters;
* each per-layer metric ``bench/metrics/<name>.py`` has a ``read(run)`` that
  returns its value, or None where it finds nothing to read.

A system module has ``build(config, mix, seed, spans)``, returning a
deployment with ``run(seconds)`` -> end-to-end values, ``counters()``,
``collect()`` (gathers what the check reads once the window and its
counters are closed), ``close()``, ``check()`` -> [(name, value, limit)] and
``notes()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"
CACHE = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_files(config: str, traffic: str) -> tuple[dict, dict]:
    """A configuration's and a mix's files, by name."""
    return (json.loads((BENCH / "configs" / f"{config}.json").read_text()),
            json.loads((BENCH / "mixes" / f"{traffic}.json").read_text()))


def find_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    return (cell, *cell_files(cell["config"], cell["traffic"]))


def require_chips(n: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_cache() -> None:
    """The persistent compile cache at a fixed path inside the checkout (the
    path is part of the cache key), whatever the environment says, for every
    program however short its compile, and never evicted: the cell's
    programs are what it holds."""
    import jax

    CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Spans:
    """Host spans of the benchmark's own: durations by name, and each one a
    ``TraceAnnotation`` so that a traced run sees it on the device's clock."""

    def __init__(self) -> None:
        self.seconds: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader sees."""

    cell: str
    config: dict
    mix: dict
    counters: dict
    spans: dict
    trace: dict | None
    peaks: dict


def memory_peak_bytes(count: int) -> int:
    import jax

    peaks = []
    for d in jax.local_devices()[:count]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCount:
    """Programs compiled, or loaded from the persistent cache, while open:
    none should be in the measured window."""

    def __init__(self) -> None:
        import jax
        from jax._src import dispatch

        self.n = 0
        self.on = False
        self._event = dispatch.BACKEND_COMPILE_EVENT

        def listen(event, duration, **kwargs):
            if self.on and event == self._event:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def traced(seconds_fn, trace_dir: Path):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return seconds_fn()
    finally:
        jax.profiler.stop_trace()


def breakdown(run: Run) -> dict:
    """The traced window's costliest device ops and longest idle gaps.  The
    gaps go by the program's spans (``bench/spans.py``) where the program
    opened any, else by the benchmark's own (``bench/trace.py``)."""
    from bench import spans
    from bench import trace as tr

    program = spans.read(run)
    reduced = run.trace
    if program and any(n.startswith(spans.PROGRAM) for n in program["spans"]):
        reduced = dict(reduced, gaps=program["gaps"])
    return tr.breakdown(reduced)


def run_cell(name: str, seed: int, seconds: int, trace: bool, t_start: float) -> dict:
    """Returns the result line's object; raises NoChip before any work."""
    bench = benchmark()
    cell, config, mix = find_cell(bench, name)
    device = require_chips(cell["chips"])
    return run_loaded(bench, name, config, mix, seed, seconds, trace, t_start, device)


def run_loaded(bench: dict, name: str, config: dict, mix: dict, seed: int, seconds: float,
               trace: bool, t_start: float, device: dict) -> dict:
    """One run of cell ``name`` with its configuration and mix given."""
    from bench import peaks as peak_table

    peaks = peak_table.peaks(device["kind"]) if device["platform"] == "tpu" else {}
    enable_cache()
    compiles = CompileCount()
    spans = Spans()
    system = load_module(BENCH / "systems" / f"{config['system']}.py")
    dep = system.build(config, mix, seed, spans)
    try:
        setup_s = time.perf_counter() - t_start
        parts = ", ".join(f"{k} {sum(v):.3f}" for k, v in spans.seconds.items())
        print(f"bench: set-up {setup_s:.3f} s ({parts})", file=sys.stderr, flush=True)
        before = dep.counters()
        opened = {k: len(v) for k, v in spans.seconds.items()}

        def window():
            compiles.on = True
            try:
                with spans.span("bench.window"):
                    return dep.run(seconds)
            finally:
                compiles.on = False

        if trace:
            trace_dir = OUT / "trace" / name
            e2e = traced(window, trace_dir)
        else:
            e2e = window()
        counters = {"before": before, "after": dep.counters()}
        in_window = {k: v[opened.get(k, 0):] for k, v in spans.seconds.items()}
        device = dict(device, memory_peak_bytes=memory_peak_bytes(device["count"]))
        dep.collect()
    finally:
        dep.close()
    print(f"bench: counters after the window {json.dumps(counters['after'])}", flush=True)
    print(f"bench: programs compiled or loaded in the window: {compiles.n}", flush=True)
    reduced = None
    if trace:
        from bench import trace as tr

        xplanes = sorted(trace_dir.rglob("*.xplane.pb"))
        if xplanes:
            events = tr.load(xplanes[-1])
            tr.save(events, OUT / f"{name}.events.json.gz")
            reduced = tr.reduce(events)
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    t_check = time.perf_counter()
    checks = dep.check()
    print(f"bench: check {time.perf_counter() - t_check:.3f} s", flush=True)
    for line in dep.notes():
        print(f"bench: {line}", flush=True)
    correct = all(value <= limit for _, value, limit in checks) and bool(checks)

    metrics = {}
    run = Run(name, config, mix, counters, in_window, reduced, peaks)
    if not trace:
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": e2e.get("attempted", 0),
        "failed": e2e.get("failed", 0),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = breakdown(run)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result

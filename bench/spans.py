"""The device's idle time put down to what the host was doing, from the
benchmark's spans and the program's.

A traced run's profiler trace holds, beside the device's ops, the host spans
that the benchmark opened around its calls into the program (``bench.*``)
and those the program opened at its own layer boundaries (``rt.*``,
``repro.core.spans``).  ``bench/trace.py`` keeps the benchmark's alone;
``load`` adds every span of both kinds as ``[name, start_ns, duration_ns,
thread]`` under ``"spans"``, ``thread`` numbering the host threads.
``reduce`` then gives, over the window span ``bench.window``:

* ``gaps``: the device's idle seconds by span.  Each idle stretch is cut at
  every span's start and end, so that each piece lies under one set of open
  spans.  Take the innermost span open on each thread there
  (``bench.window`` left out).  Where any of them is a program span, the
  piece is split evenly over the threads' innermost program spans;
  otherwise it goes to the latest-starting benchmark span open there, or to
  ``none``.  Time under ``rt.stage/<stage>`` itself is that stage's thread
  waiting for its tasks;
* ``spans``: for each span name, its seconds inside the window and its calls;
* ``groups``: for each group of spans (a name's first two dotted parts,
  before any ``/``: ``rt.tier`` holds ``rt.tier.get/DMS``), the window's
  seconds inside the group's spans, as a union on each thread (a span inside
  another of its group counts once), summed over threads.

``read(run)`` gives these for a traced run from the trace the harness left
under ``.bench_out/trace/<cell>``, and None for a run that was not traced.

    python3 bench/spans.py <trace.xplane.pb>

prints them.
"""
from __future__ import annotations

import bisect
import functools
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import trace  # noqa: E402
from bench.harness import OUT  # noqa: E402

PREFIXES = ("bench.", "rt.")
PROGRAM = "rt."


def load(path: str | Path) -> dict:
    from jax.profiler import ProfileData

    events = trace.load(path)
    spans = []
    thread = 0
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:  # one line per host thread
                spans.extend([e.name, e.start_ns, e.duration_ns, thread]
                             for e in line.events if e.name.startswith(PREFIXES))
                thread += 1
    events["spans"] = spans
    return events


def _by_thread(spans: list) -> dict[int, list[tuple[float, float, str]]]:
    """Each thread's spans but the window, as (start, end, name), sorted so
    that of two spans starting together the enclosing one comes first."""
    threads: dict[int, list[tuple[float, float, str]]] = {}
    for name, s, d, thread in spans:
        if name != trace.WINDOW:
            threads.setdefault(thread, []).append((s, s + d, name))
    for line in threads.values():
        line.sort(key=lambda x: (x[0], -x[1]))
    return threads


def _innermost(threads: dict, times: list[float]) -> list[list[tuple[float, float, str]]]:
    """For each of ``times`` (increasing), the innermost span open on each
    thread: the latest-starting one with ``start <= t < end``."""
    out: list[list] = [[] for _ in times]
    for line in threads.values():
        stack: list = []  # every span open at t, in start order, and some closed ones
        i = 0
        for k, t in enumerate(times):
            while i < len(line) and line[i][0] <= t:
                while stack and stack[-1][1] <= line[i][0]:
                    stack.pop()
                stack.append(line[i])
                i += 1
            while stack and stack[-1][1] <= t:
                stack.pop()
            if stack:
                out[k].append(stack[-1])
    return out


def _group(name: str) -> str:
    return ".".join(name.split("/", 1)[0].split(".")[:2])


def reduce(events: dict) -> dict:
    t0, t1 = trace.window_bounds(events)
    threads = _by_thread(events["spans"])
    cuts = sorted({x for line in threads.values() for s, e, _ in line for x in (s, e)})
    gaps: dict[str, float] = {}
    devices = events["devices"]
    for lines in devices.values():
        busy = []
        for _, s, d in lines.get("XLA Ops") or lines.get("XLA Modules", []):
            s, e = max(s, t0), min(s + d, t1)
            if e > s:
                busy.append((s, e))
        edges = [t0] + [x for iv in trace._union(busy) for x in iv] + [t1]
        pieces = []
        for gs, ge in zip(edges[::2], edges[1::2]):
            inner = cuts[bisect.bisect_right(cuts, gs):bisect.bisect_left(cuts, ge)]
            bounds = [gs, *inner, ge]
            pieces.extend(zip(bounds, bounds[1:]))
        pieces = [(s, e) for s, e in pieces if e > s]
        for (s, e), open_ in zip(pieces, _innermost(threads, [(s + e) / 2 for s, e in pieces])):
            program = [n for _, _, n in open_ if n.startswith(PROGRAM)]
            if program:
                for n in program:
                    gaps[n] = gaps.get(n, 0.0) + (e - s) * 1e-9 / len(program)
                continue
            owner = max(open_, key=lambda x: (x[0], -x[1]))[2] if open_ else "none"
            gaps[owner] = gaps.get(owner, 0.0) + (e - s) * 1e-9
    spans: dict[str, dict] = {}
    for name, s, d, _ in events["spans"]:
        s, e = max(s, t0), min(s + d, t1)
        if e > s:
            row = spans.setdefault(name, {"seconds": 0.0, "calls": 0})
            row["seconds"] += (e - s) * 1e-9
            row["calls"] += 1
    groups: dict[str, float] = {}
    for line in threads.values():
        by_group: dict[str, list] = {}
        for s, e, name in line:
            s, e = max(s, t0), min(e, t1)
            if e > s:
                by_group.setdefault(_group(name), []).append((s, e))
        for g, intervals in by_group.items():
            covered = sum(e - s for s, e in trace._union(intervals)) * 1e-9
            groups[g] = groups.get(g, 0.0) + covered
    n = max(len(devices), 1)
    return {"gaps": {k: v / n for k, v in gaps.items()}, "spans": spans, "groups": groups}


@functools.lru_cache(maxsize=1)
def _reduced(path: str) -> dict:
    return reduce(load(path))


def read(run) -> dict | None:
    if run.trace is None:
        return None
    xplanes = sorted((OUT / "trace" / run.cell).rglob("*.xplane.pb"))
    return _reduced(str(xplanes[-1])) if xplanes else None


if __name__ == "__main__":
    r = reduce(load(sys.argv[1]))
    r["gaps"] = dict(sorted(r["gaps"].items(), key=lambda kv: -kv[1]))
    print(json.dumps(r, indent=1))

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; ``--json out.json`` additionally
writes a machine-readable report (consumed by ``scripts/bench_gate.py``
and uploaded as a CI artifact).  Mapping:
  Fig. 11 -> bench_overhead       (RT abstraction overhead, paper ~3%)
  Tab. 1  -> bench_scaling        (multi-core / multi-GPU scalability)
  Fig. 12 -> bench_disk_groups    (I/O group sizes vs stock ADIOS, 1.13x)
  Fig. 13/14 -> bench_dms_vs_disk (DMS vs DISK exchange, ~200 GB/s)
  Fig. 15 -> bench_scheduler      (FCFS/PATS/DL/Pref cooperative configs)
  Fig. 16 -> bench_op_speedups    (per-op cost profile)
  Fig. 17 -> bench_pats_error     (estimate-error sensitivity)
  kernels -> bench_kernels        (pallas-interpret vs jnp reference)
  roofline-> bench_roofline       (dry-run artifacts -> 3-term table)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (
    bench_compute,
    bench_disk_groups,
    bench_dms_vs_disk,
    bench_gateway,
    bench_gateway_fleet,
    bench_kernels,
    bench_op_speedups,
    bench_overhead,
    bench_pats_error,
    bench_rebalance,
    bench_repair,
    bench_replication,
    bench_roofline,
    bench_scaling,
    bench_scheduler,
    bench_stcache,
    bench_tiers,
    bench_transport,
)
from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("fig11", bench_overhead),
    ("tab1", bench_scaling),
    ("fig12", bench_disk_groups),
    ("fig13_14", bench_dms_vs_disk),
    ("fig15", bench_scheduler),
    ("fig16", bench_op_speedups),
    ("fig17", bench_pats_error),
    ("kernels", bench_kernels),
    ("roofline", bench_roofline),
    ("sec7_stcache", bench_stcache),
    ("tiered_staging", bench_tiers),
    ("transport", bench_transport),
    ("gateway", bench_gateway),
    ("gateway_fleet", bench_gateway_fleet),
    ("compute", bench_compute),
    ("replication", bench_replication),
    ("repair", bench_repair),
    ("rebalance", bench_rebalance),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--json",
        metavar="OUT",
        default=None,
        help="also write results as JSON (rows + failures + wall seconds)",
    )
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated module tags to run (default: all); "
        f"tags: {','.join(tag for tag, _ in MODULES)}",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()
    selected = MODULES
    if args.only:
        want = {t.strip() for t in args.only.split(",") if t.strip()}
        unknown = want - {tag for tag, _ in MODULES}
        if unknown:
            raise SystemExit(f"unknown benchmark tag(s): {sorted(unknown)}")
        selected = [(tag, mod) for tag, mod in MODULES if tag in want]

    print("name,us_per_call,derived")
    report = {"started": time.time(), "rows": [], "failed_modules": []}
    failures = 0
    for tag, mod in selected:
        t0 = time.time()
        try:
            rows = mod.run()
            emit(rows)
            report["rows"] += [
                {"name": n, "us_per_call": us, "derived": d, "module": tag}
                for n, us, d in rows
            ]
            print(f"# {tag} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            report["failed_modules"].append(tag)
            print(f"{tag}_FAILED,0.0,exception", flush=True)
            traceback.print_exc()
    report["wall_s"] = time.time() - report["started"]
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()

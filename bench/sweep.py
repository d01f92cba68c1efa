"""Find a serving cell's knee: the offered rates at which its gateway keeps
up.  Builds the cell's deployment once and runs one window per rate.

    python3 bench/sweep.py --workload <config>.<traffic> --seed <n> --seconds <s> --rates 10,20,40

Prints one JSON line per rate: p50 and p95 latency and answered requests per
second of each operation.  The requests of the k-th rate are drawn from
``seed + k``.  The knee is the highest rate whose answered rate
keeps up with the offered one and whose p95 has not started to climb; the
cell's mix file takes about four fifths of it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    from bench import harness

    config, mix = harness.cell_files(*args.workload.split(".", 1))
    try:
        harness.require_chips(1)
    except harness.NoChip as e:
        print(f"sweep: refusing to run: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    system = harness.load_module(harness.BENCH / "systems" / f"{config['system']}.py")
    dep = system.build(config, mix, args.seed, harness.Spans())
    print(f"sweep: set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    try:
        # each rate's requests from a seed of their own: with one seed the
        # rates' schedules share origins, which the derived cache then hits
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            out = dep.run(args.seconds, rate=rate, seed=args.seed + k)
            print(json.dumps(dict(out, rate=rate, lateness_ms=dep.lateness)), flush=True)
    finally:
        dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

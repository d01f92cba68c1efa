"""The control of the ``wsi_4k_ws`` check: ``bench/reference_ws.py``, the
reference with the nucleus split, computed in bfloat16, the precision below
the float32 the configuration states, put in the program's place.  Its
answers go through the same check as a run's, and have to come out as not
correct.

    python3 bench/control_ws.py --workload wsi_4k_ws.direct --seeds 1,2,3

prints, for each seed, every number the check compares beside its limit.
It needs no chip: the reference runs on the host, at the cell's own sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import harness, reference, reference_ws, synth  # noqa: E402


def control(config: dict, mix: dict, seed: int, tiles: int = 3) -> list:
    from bench.systems import wsi_ws

    dep = wsi_ws.WSIWS.__new__(wsi_ws.WSIWS)
    dep.config, dep.mix, dep.missing, dep.capped = config, mix, 0, 0
    dep.pool = synth.TilePool(seed, config["tile"], config["pool_base_tiles"],
                              config["nuclei_per_512"], tuple(config["nucleus_radius"]))
    rng = np.random.default_rng([seed, 3])
    dep.samples = []
    for i in rng.choice(len(dep.pool), tiles, replace=False):
        out = reference_ws.analyse_tile(dep.pool.tile(int(i)), config, reference.BF16)
        dep.samples.append({"tile": int(i), **{k: np.asarray(v) for k, v in out.items()}})
    return dep.check()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(harness.ROOT / "src"))
    config, mix = harness.cell_files(*args.workload.split(".", 1))
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control(config, mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks},
                          "fails": [n for n, v, lim in checks if v > lim]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The control of each cell's check: the plain reference computed in
bfloat16, the precision below the float32 the configurations state, put in
the program's place.  Its answers go through the same check as a run's, and
have to come out as not correct.

    python3 bench/control.py --workload <config>.<traffic> --seeds 1,2,3

prints, for each seed, every number the check compares beside its limit.
It needs no chip: the reference runs on the host, at the cell's own sizes.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import harness, reference, synth, traffic  # noqa: E402


def wsi_control(config: dict, mix: dict, seed: int, tiles: int = 3) -> list:
    from bench.systems import wsi

    dep = wsi.WSI.__new__(wsi.WSI)
    dep.config, dep.mix, dep.missing = config, mix, 0
    dep.pool = synth.TilePool(seed, config["tile"], config["pool_base_tiles"],
                              config["nuclei_per_512"], tuple(config["nucleus_radius"]))
    rng = np.random.default_rng([seed, 3])
    dep.samples = []
    for i in rng.choice(len(dep.pool), tiles, replace=False):
        out = reference.analyse_tile(dep.pool.tile(int(i)), config, reference.BF16)
        dep.samples.append({"tile": int(i), **{k: np.asarray(v) for k, v in out.items()}})
    return dep.check()


def bf16_answer(cls: dict, window: np.ndarray, threshold: float):
    if cls["op"] == "read":
        return window.astype(reference.BF16).astype(np.float32)
    if cls["chain"] == "deconv":
        return reference.hematoxylin(window, reference.BF16).astype(np.float32)
    return np.array([reference.chain_count(window, threshold, reference.BF16)], np.int32)


def serve_control(config: dict, mix: dict, seed: int) -> list:
    """The answers a run of the seed would compare (the same slide, the same
    window's schedule and the same sample), computed in bfloat16."""
    from bench.systems import serve

    dep = serve.Serve.__new__(serve.Serve)
    dep.config, dep.mix = config, mix
    dep.slide = slide = serve.make_slide(config, seed)
    reqs = traffic.schedule(mix, slide.shape[1:], harness.benchmark()["run_seconds"], seed)
    picked = serve.sample(mix, reqs, seed)
    dep.requests, dep.kept = [], {}
    for r in reqs:
        if r.index not in picked:
            continue
        cls = mix["classes"][r.cls]
        y, x = r.origin
        e = cls["edge"]
        fut = concurrent.futures.Future()
        fut.set_result(bf16_answer(cls, slide[:, y : y + e, x : x + e], config["chain_threshold"]))
        dep.requests.append(r)
        dep.kept[r.index] = fut
    return dep.check()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(harness.ROOT / "src"))
    config, mix = harness.cell_files(*args.workload.split(".", 1))
    control = wsi_control if config["system"] == "wsi" else serve_control
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control(config, mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks},
                          "fails": [n for n, v, lim in checks if v > lim]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

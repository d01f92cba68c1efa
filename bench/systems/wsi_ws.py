"""The whole-slide deployment with the paper's nucleus split (``wsi_4k_ws``):
``bench/systems/wsi.py``'s ``sequential`` mix, the tile stream through
``analyze_tile`` one tile after another, with the configuration's
``split_h`` and ``split_min_area`` set, so that segmentation ends in the
distance transform, h-maxima markers, watershed and area threshold.

The program reports each tile's fixed-point loops (``analyze_tile``'s
"split"): their kernel calls are counted (``split_calls``), and a tile on
which a loop stopped at its cap counts as failed (``tiles_capped``).  The
check holds the kept tiles to ``bench/reference_ws.py``.

Set-up runs every tile of the pool once: ties between basins are broken by
raster order, so a dihedral variant may hold a different number of
objects, and so need a feature program of another shape.  A program
without the split fails at once, before any tile is drawn.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import compare_ws, synth
from bench.systems import wsi


def build(config: dict, mix: dict, seed: int, spans):
    return WSIWS(config, mix, seed, spans)


class WSIWS(wsi.WSI):
    def __init__(self, config: dict, mix: dict, seed: int, spans) -> None:
        from repro.configs.wsi import WSIConfig

        if mix["kind"] != "sequential":
            raise ValueError(f"the wsi_ws system runs no {mix['kind']!r} mix")
        if "split_h" not in {f.name for f in dataclasses.fields(WSIConfig)}:
            raise RuntimeError("this program has no nucleus split (WSIConfig.split_h)")
        self.split_calls = self.capped = 0
        super().__init__(config, mix, seed, spans)
        try:
            with spans.span("bench.setup.warmup"):
                for idx in range(len(self.pool)):
                    if idx % synth.DIHEDRAL:
                        self._tile(idx, keep=False)
        except BaseException:
            self.close()
            raise
        self.tiles = self.missing = self.split_calls = self.capped = 0

    def _tile(self, idx: int, keep: bool) -> None:
        import jax.numpy as jnp
        from repro.pipeline import analyze_tile

        cfg = dataclasses.replace(self.wcfg, split_h=self.config["split_h"],
                                  split_min_area=self.config["split_min_area"])
        out = analyze_tile(jnp.asarray(self.pool.tile(idx)), cfg, impl=self.config["impl"])
        report = out.get("split", {})
        self.split_calls += sum(report.get("calls", {}).values())
        feats = out.get("features")
        if not report or report["capped"]:
            self.capped += 1
        elif not (isinstance(feats, np.ndarray) and feats.ndim == 2 and feats.shape[1] == 9):
            self.missing += 1
        elif keep:
            self.samples.append({
                "tile": idx, "hema": np.asarray(out["hematoxylin"]),
                "labels": np.asarray(out["labels"]), "mask": np.asarray(out["mask"]),
                "boxes": np.asarray(out["boxes"]), "features": feats,
            })
        self.tiles += 1

    def run(self, seconds: float) -> dict:
        out = super().run(seconds)
        out["failed"] += self.capped
        return out

    def counters(self) -> dict:
        return {**super().counters(), "split_calls": self.split_calls}

    def check(self) -> list[tuple[str, float, float]]:
        """Each kept tile against the reference with the split: the worst of
        each number over the tiles, beside its limit."""
        c = self.config
        got = compare_ws.tiles([(self.pool.tile(s["tile"]), s, c) for s in self.samples])
        self.objects = [g.pop("objects") for g in got]
        limits = c["limits"]
        checks = [(k, max((g[k] for g in got), default=0), limits[k])
                  for k in compare_ws.NUMBERS]
        checks.append(("tiles_missing", self.missing, 0))
        checks.append(("tiles_capped", self.capped, 0))
        checks.append(("tiles_checked_short", max(0, self.mix["min_checked"]
                                                  - len(self.samples)), 0))
        return checks

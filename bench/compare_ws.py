"""The numbers the ``wsi_4k_ws`` check compares, for one tile, against the
reference with the nucleus split (``bench/reference_ws.py``).

Staged as ``bench/compare.py``: hematoxylin from the RGB (``hema_gap``);
then the split mask and labels recomputed from the program's own
hematoxylin, and boxes and features from its own labels, so that each
step after the first is held exactly (``mask_px``, ``label_px``,
``objects_off``, ``feat_gap``).  The same names and limits as ``wsi_4k``.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing

import numpy as np

from bench import compare, reference, reference_ws

NUMBERS = compare.NUMBERS


def wsi_tile(rgb: np.ndarray, got: dict, cfg: dict) -> dict:
    """``compare.wsi_tile`` with the split; ``got`` holds the program's
    ``hema``, ``labels``, ``mask``, ``boxes`` and ``features``."""
    hema = np.asarray(got["hema"])
    labels = np.asarray(got["labels"])
    out = {}
    want_hema = reference.normalise(reference.hematoxylin(rgb))
    out["hema_gap"] = float(np.abs(hema.astype(np.float64) - want_hema).max())
    mask, want_labels = reference_ws.segment_from_hema(
        hema, cfg["seg_threshold"], cfg["split_h"], cfg["split_min_area"])
    out["mask_px"] = int((mask != (np.asarray(got["mask"]) != 0)).sum())
    out["label_px"] = int((want_labels != labels).sum())
    del mask, want_labels
    crops, boxes = reference.object_rois(labels, hema, cfg["nucleus_roi"])
    out["objects_off"] = compare.boxes_off(got["boxes"], boxes)
    out["feat_gap"] = (
        compare.feature_gap(got["features"],
                            reference.texture_features(crops.astype(np.float64), cfg["num_bins"]))
        if not out["objects_off"] else 0.0)
    out["objects"] = len(boxes)
    return out


def _one(args) -> dict:
    return wsi_tile(*args)


def tiles(jobs: list[tuple[np.ndarray, dict, dict]]) -> list[dict]:
    """``wsi_tile`` over (rgb, got, cfg) jobs, each in a worker process that
    never imports JAX."""
    if not jobs:
        return []
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(min(len(jobs), compare.MAX_WORKERS),
                                                mp_context=ctx) as pool:
        return list(pool.map(_one, jobs))

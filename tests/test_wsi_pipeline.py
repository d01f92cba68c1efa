"""The paper's use-case pipeline on synthetic tiles: correctness + RT parity."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.wsi import WSIConfig
from repro.core import BoundingBox, Intent, RegionTemplate, StorageRegistry
from repro.kernels import ref
from repro.pipeline import (
    FeatureStage,
    SegmentationStage,
    analyze_tile,
    extract_object_rois,
    make_tile,
    segment_tile,
)
from repro.runtime import SysEnv
from repro.storage import DistributedMemoryStorage


@pytest.fixture(scope="module")
def tile():
    return make_tile(128, num_nuclei=8, seed=3)


def _iou(a, b):
    inter = np.logical_and(a, b).sum()
    union = np.logical_or(a, b).sum()
    return inter / max(union, 1)


def test_segmentation_recovers_nuclei(tile):
    rgb, gt = tile
    cfg = WSIConfig(seg_threshold=0.5)
    seg = segment_tile(jnp.asarray(rgb), cfg, impl="xla")
    mask = np.asarray(seg["mask"]) > 0
    assert _iou(mask, gt > 0) > 0.5
    labels = np.asarray(seg["labels"])
    n_objects = len(np.unique(labels[labels >= 0]))
    assert 3 <= n_objects <= 24  # ballpark of 8 seeded nuclei (some merge)


def test_full_tile_analysis_features(tile):
    rgb, _ = tile
    cfg = WSIConfig(seg_threshold=0.5, nucleus_roi=32)
    out = analyze_tile(jnp.asarray(rgb), cfg, impl="xla")
    k = out["features"].shape[0]
    assert k == out["boxes"].shape[0] == out["rois"].shape[0]
    assert out["features"].shape[1] == 9
    assert np.isfinite(out["features"]).all()


def test_object_roi_extraction_fixed_size():
    labels = np.full((64, 64), -1, np.int32)
    labels[10:20, 10:20] = 0
    labels[40:50, 30:44] = 1
    intensity = np.random.default_rng(0).random((64, 64)).astype(np.float32)
    cfg = WSIConfig(nucleus_roi=16)
    rois, boxes = extract_object_rois(labels, intensity, cfg)
    assert rois.shape == (2, 16, 16)
    assert boxes.shape == (2, 4)
    assert (boxes[:, 2] <= 64).all() and (boxes[:, 3] <= 64).all()


def _rois_per_object_loop(labels, intensity, cfg):
    """One mask scan per object: the reference for extract_object_rois."""
    r = cfg.nucleus_roi
    ids = np.unique(labels)
    ids = ids[ids >= 0][: cfg.max_objects_per_tile]
    rois = np.zeros((len(ids), r, r), np.float32)
    boxes = np.zeros((len(ids), 4), np.int32)
    h, w = labels.shape
    for i, oid in enumerate(ids):
        ys, xs = np.nonzero(labels == oid)
        cy, cx = (ys.min() + ys.max() + 1) // 2, (xs.min() + xs.max() + 1) // 2
        y0 = np.clip(cy - r // 2, 0, max(h - r, 0))
        x0 = np.clip(cx - r // 2, 0, max(w - r, 0))
        crop = intensity[y0 : y0 + r, x0 : x0 + r]
        rois[i, : crop.shape[0], : crop.shape[1]] = crop
        boxes[i] = (y0, x0, min(y0 + r, h), min(x0 + r, w))
    return rois, boxes


@pytest.mark.parametrize("max_objects", [512, 7])
def test_object_roi_extraction_matches_per_object_loop(max_objects):
    rng = np.random.default_rng(1)
    labels = ref.ccl_unionfind_host(rng.random((48, 80)) < 0.45)
    intensity = rng.random((48, 80)).astype(np.float32)
    cfg = WSIConfig(nucleus_roi=16, max_objects_per_tile=max_objects)
    rois, boxes = extract_object_rois(labels, intensity, cfg)
    want_rois, want_boxes = _rois_per_object_loop(labels, intensity, cfg)
    assert len(boxes) == min(max_objects, len(np.unique(labels)) - 1)
    np.testing.assert_array_equal(boxes, want_boxes)
    np.testing.assert_array_equal(rois, want_rois)


def test_rt_two_stage_pipeline_matches_plain(tile):
    """RT-based Segmentation->Features == plain function pipeline (the
    precondition for the Fig. 11 overhead comparison)."""
    rgb, _ = tile
    h, w = rgb.shape[1:]
    cfg = WSIConfig(seg_threshold=0.5, nucleus_roi=32)
    plain = analyze_tile(jnp.asarray(rgb), cfg, impl="xla")

    reg = StorageRegistry()
    dom3 = BoundingBox((0, 0, 0), (3, h, w))
    dom2 = BoundingBox((0, 0), (h, w))
    dms3 = reg.register(DistributedMemoryStorage(dom3, (3, h, w), 1, name="DMS3"))
    dms2 = reg.register(DistributedMemoryStorage(dom2, (h, w), 1, name="DMS2"))

    rt = RegionTemplate("Patient")
    rgb_region = rt.new_region("RGB", dom3, np.float32, input_storage="DMS3", lazy=True)
    dms3.put(rgb_region.key, dom3, np.asarray(rgb))

    env = SysEnv(num_workers=1, cpus_per_worker=2, accels_per_worker=1, registry=reg)
    seg = SegmentationStage(cfg, impl="xla")
    seg.add_region_template(rt, "RGB", dom3, Intent.INPUT, read_storage="DMS3")
    seg.add_region_template(rt, "Mask", dom2, Intent.OUTPUT, storage="DMS2")
    seg.add_region_template(rt, "Hema", dom2, Intent.OUTPUT, storage="DMS2")
    feat = FeatureStage(cfg, impl="xla")
    feat.add_region_template(rt, "Mask", dom2, Intent.INPUT, read_storage="DMS2")
    feat.add_region_template(rt, "Hema", dom2, Intent.INPUT, read_storage="DMS2")
    feat.add_dependency(seg)
    env.execute_component(seg)
    env.execute_component(feat)
    env.startup_execution()
    env.finalize_system()

    mask_key = seg.templates["Patient"].get("Mask").key
    got_mask = dms2.get(mask_key, dom2)
    np.testing.assert_array_equal(got_mask, np.asarray(plain["labels"]))

    feats_region = feat.templates["Patient"].get("Features")
    got = feats_region.data
    np.testing.assert_allclose(got["features"], plain["features"], rtol=1e-4, atol=1e-4)
    assert feats_region.num_objects == plain["features"].shape[0]

"""The nucleus split (Pre-Watershed, Watershed, AreaThreshold): the exact
distance transform against SciPy, the split against the host oracle
``ref.split_nuclei_host``, the runtime stage against ``segment_tile``, and
the fixed-point loops' counts."""
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import ndimage

from repro.configs.wsi import WSIConfig
from repro.core import BoundingBox, Intent, RegionTemplate, StorageRegistry
from repro.kernels import ops, ref
from repro.pipeline import SegmentationStage, make_tile, segment_tile, split_nuclei
from repro.pipeline.wsi import area_threshold
from repro.runtime import SysEnv
from repro.storage import DistributedMemoryStorage

SPLIT = WSIConfig(split_h=11, split_min_area=21)


def _edt_case(name: str) -> np.ndarray:
    rng = np.random.default_rng(7)
    if name == "random":
        return rng.random((128, 256)) < 0.93
    if name == "edge":  # foreground along every side of the tile
        m = rng.random((192, 128)) < 0.97
        m[:9], m[-5:], m[:, :3], m[:, -12:] = True, True, True, True
        return m
    if name == "full":
        return np.ones((128, 128), bool)
    return np.zeros((128, 128), bool)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["random", "edge", "full", "empty"])
def test_distance_transform_equals_scipy(case, impl):
    m = _edt_case(case)
    d2, calls = ops.distance_transform(jnp.asarray(m.astype(np.int32)), impl=impl)
    want = np.rint(ndimage.distance_transform_edt(m) ** 2).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(d2), want)
    assert int(calls) >= (1 if case in ("random", "edge") else 0)


@pytest.fixture(scope="module")
def clumps():
    """Tiles whose opened masks hold touching nuclei, with those masks."""
    out = []
    for size, nuclei, seed in ((128, 12, 3), (256, 60, 1)):
        rgb, _ = make_tile(size, num_nuclei=nuclei, seed=seed)
        seg = segment_tile(jnp.asarray(rgb), WSIConfig(), impl="xla")
        out.append((rgb, np.asarray(seg["mask"]), np.asarray(seg["labels"])))
    return out


def _objects(labels) -> int:
    labels = np.asarray(labels)
    return len(np.unique(labels[labels >= 0]))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("which", [0, 1])
def test_split_matches_host_reference(clumps, which, impl):
    _, mask, unsplit = clumps[which]
    got = split_nuclei(jnp.asarray(mask), SPLIT, impl=impl)
    want = ref.split_nuclei_host(mask, SPLIT.split_h, SPLIT.split_min_area)
    np.testing.assert_array_equal(np.asarray(got["labels"]), want)
    np.testing.assert_array_equal(np.asarray(got["mask"]), (want >= 0).astype(np.int32))
    assert _objects(want) > _objects(unsplit)  # at least one clump was split
    assert got["split"]["capped"] == []
    assert min(got["split"]["calls"].values()) >= 1


@pytest.mark.parametrize("min_area", [1, 21, 22])
def test_area_threshold_drops_small_basins(min_area):
    """Basins of 20, 21 and 24 pixels, and pixels no marker reached (int32
    max)."""
    basins = np.full((16, 24), -1, np.int32)
    basins[1:5, 2:7] = 26  # 20 pixels
    basins[6:9, 3:10] = 147  # 21 pixels
    basins[10:14, 12:18] = 252  # 24 pixels
    basins[14, 0:3] = np.iinfo(np.int32).max
    want = np.full(basins.shape, -1, np.int32)
    for label in (26, 147, 252):
        if (basins == label).sum() >= min_area:
            want[basins == label] = label
    got = np.asarray(area_threshold(jnp.asarray(basins), min_area))
    np.testing.assert_array_equal(got, want)


def test_segmentation_stage_splits_like_segment_tile(clumps):
    rgb, _, _ = clumps[0]
    h, w = rgb.shape[1:]
    plain = segment_tile(jnp.asarray(rgb), SPLIT, impl="xla")

    reg = StorageRegistry()
    dom3 = BoundingBox((0, 0, 0), (3, h, w))
    dom2 = BoundingBox((0, 0), (h, w))
    dms3 = reg.register(DistributedMemoryStorage(dom3, (3, h, w), 1, name="DMS3"))
    dms2 = reg.register(DistributedMemoryStorage(dom2, (h, w), 1, name="DMS2"))
    rt = RegionTemplate("Patient")
    rgb_region = rt.new_region("RGB", dom3, np.float32, input_storage="DMS3", lazy=True)
    dms3.put(rgb_region.key, dom3, np.asarray(rgb))
    env = SysEnv(num_workers=1, cpus_per_worker=2, accels_per_worker=1, registry=reg)
    seg = SegmentationStage(SPLIT, impl="xla")
    seg.add_region_template(rt, "RGB", dom3, Intent.INPUT, read_storage="DMS3")
    seg.add_region_template(rt, "Mask", dom2, Intent.OUTPUT, storage="DMS2")
    seg.add_region_template(rt, "Hema", dom2, Intent.OUTPUT, storage="DMS2")
    env.execute_component(seg)
    env.startup_execution()
    env.finalize_system()

    got = dms2.get(seg.templates["Patient"].get("Mask").key, dom2)
    np.testing.assert_array_equal(got, np.asarray(plain["labels"]))
    assert seg.split == plain["split"]


def test_split_is_off_by_default(clumps):
    rgb, mask, unsplit = clumps[0]
    assert WSIConfig().split_h is None
    out = segment_tile(jnp.asarray(rgb), WSIConfig(), impl="xla")
    assert set(out) == {"mask", "labels", "hematoxylin"}
    np.testing.assert_array_equal(
        np.asarray(out["labels"]), np.asarray(ops.connected_components(jnp.asarray(mask)))
    )
    assert SegmentationStage().split is None


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_split_reports_its_loops_and_a_cap_that_stopped_one(clumps, impl):
    _, mask, _ = clumps[1]
    full = split_nuclei(jnp.asarray(mask), SPLIT, impl=impl)
    calls = full["split"]["calls"]
    assert set(calls) == {"edt", "hmax", "maxima", "markers", "cost", "watershed", "relabel"}
    assert calls["watershed"] >= 2 and full["split"]["capped"] == []
    short = split_nuclei(jnp.asarray(mask), SPLIT, impl=impl, max_iters=1)
    assert "watershed" in short["split"]["capped"]
    assert all(short["split"]["calls"][k] == 1 for k in short["split"]["capped"])

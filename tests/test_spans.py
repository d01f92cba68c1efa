"""Program spans (``repro.core.spans``): a 256x256 segmentation and feature
run on the Manager/WRM runtime over tiered storage, under the profiler,
puts each span where the runtime and the store cross a layer boundary; a
bare tiered store shows the promotion's span."""
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import spans as bench_spans
from repro.configs.wsi import WSIConfig
from repro.core import BoundingBox, ElementType, Intent, RegionKey, RegionTemplate
from repro.pipeline import FeatureStage, SegmentationStage, make_tile, make_wsi_storage
from repro.runtime import SysEnv
from repro.storage import MemoryTier, Tier, TieredStore

EDGE = 256
RGB_BYTES = 3 * EDGE * EDGE * 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The run's spans as ``bench/spans.py`` loads them, each span's stats
    by name, and the two stages."""
    root = tmp_path_factory.mktemp("spans")
    rgb, _ = make_tile(EDGE, num_nuclei=10, seed=5)
    # the RAM tier holds the mask and hematoxylin but not the RGB: its put
    # is demoted at once, and its get from DMS declines a promotion on size
    reg = make_wsi_storage(EDGE, EDGE, mode="tiered", tile=EDGE, root=str(root / "tiers"),
                           mem_capacity_bytes=RGB_BYTES * 3 // 4, promote_after=1)
    dom3 = BoundingBox((0, 0, 0), (3, EDGE, EDGE))
    dom2 = BoundingBox((0, 0), (EDGE, EDGE))
    rt = RegionTemplate("Patient")
    rgb_region = rt.new_region("RGB", dom3, np.float32, input_storage="DMS3", lazy=True)
    cfg = WSIConfig(seg_threshold=0.5, nucleus_roi=32)
    seg = SegmentationStage(cfg, impl="xla")
    seg.add_region_template(rt, "RGB", dom3, Intent.INPUT, read_storage="DMS3")
    seg.add_region_template(rt, "Mask", dom2, Intent.OUTPUT, storage="DMS2")
    seg.add_region_template(rt, "Hema", dom2, Intent.OUTPUT, storage="DMS2")
    feat = FeatureStage(cfg, impl="xla")
    feat.add_region_template(rt, "Mask", dom2, Intent.INPUT, read_storage="DMS2")
    feat.add_region_template(rt, "Hema", dom2, Intent.INPUT, read_storage="DMS2")
    feat.add_dependency(seg)
    trace_dir = root / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        reg.get("DMS3").put(rgb_region.key, dom3, np.asarray(rgb))
        env = SysEnv(num_workers=1, cpus_per_worker=2, accels_per_worker=1, registry=reg)
        env.execute_component(seg)
        env.execute_component(feat)
        try:
            env.startup_execution()
        finally:
            env.finalize_system()
    finally:
        jax.profiler.stop_trace()
        for name in reg.names():
            reg.get(name).close()
    xplane = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    assert feat.templates["Patient"].get("Features").data["features"].shape[1] == 9
    rgb_mem = reg.get("DMS3").tier_stats()["MEM"]
    return bench_spans.load(xplane)["spans"], _rt_stats(xplane), seg, feat, rgb_mem


def _rt_stats(xplane) -> dict[str, list[dict]]:
    """Each ``rt.`` span's stats, by name, from a profiler trace."""
    stats: dict[str, list[dict]] = {}
    for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rt."):
                    stats.setdefault(e.name, []).append(dict(e.stats))
    return stats


def _enclosing(host, span, prefix):
    """The spans named ``prefix...`` on ``span``'s thread that enclose it."""
    name, s, d, thread = span
    return [h for h in host if h[3] == thread and h is not span and h[0].startswith(prefix)
            and h[1] <= s and s + d <= h[1] + h[2]]


def test_each_span_appears_nested_per_thread(traced):
    host, _, _, _, _ = traced
    names = {h[0] for h in host}
    expected = {
        "rt.stage/Segmentation", "rt.stage/FeatureComputation", "rt.stage.inputs",
        "rt.stage.body", "rt.stage.outputs", "rt.task/Color deconv.", "rt.task/ObjectROIs",
        "rt.task/Features", "rt.tier.get/DMS", "rt.tier.get/MEM", "rt.tier.put/MEM",
        "rt.tier.put/DMS", "rt.tier.demote",
    }
    assert expected <= names
    assert all(n.startswith("rt.") for n in names)
    # spans of one thread nest: any two are disjoint or one holds the other
    by_thread: dict[int, list] = {}
    for h in host:
        by_thread.setdefault(h[3], []).append((h[1], h[1] + h[2]))
    for spans in by_thread.values():
        spans.sort(key=lambda x: (x[0], -x[1]))
        open_: list = []
        for s, e in spans:
            while open_ and open_[-1] <= s:
                open_.pop()
            assert not open_ or e <= open_[-1]
            open_.append(e)
    for h in host:
        if h[0] in ("rt.stage.inputs", "rt.stage.body", "rt.stage.outputs"):
            assert len(_enclosing(host, h, "rt.stage/")) == 1
        if h[0].startswith("rt.task/"):
            assert not _enclosing(host, h, "rt.stage")  # on a WRM thread of its own
    # the workers' gets and promotions happen while their stage's inputs load
    for h in host:
        if h[0].startswith("rt.tier.get/") or h[0] == "rt.tier.promote":
            assert _enclosing(host, h, "rt.stage.inputs")
        if h[0] == "rt.tier.put/DMS" and not _enclosing(host, h, "rt.stage"):
            staged = h  # the RGB put, before the runtime starts
    assert not _enclosing(host, staged, "rt.")


def test_spans_carry_stage_ids_and_bytes(traced):
    _, stats, seg, feat, rgb_mem = traced
    assert [s["sid"] for s in stats["rt.stage/Segmentation"]] == [seg.sid]
    assert [s["sid"] for s in stats["rt.stage/FeatureComputation"]] == [feat.sid]
    for s in stats["rt.stage/Segmentation"] + stats["rt.stage/FeatureComputation"]:
        assert s["worker"] == 0 and s["attempt"] == 0
    assert {s["sid"] for s in stats["rt.stage.inputs"]} == {seg.sid, feat.sid}
    assert [s["sid"] for s in stats["rt.task/ObjectROIs"]] == [feat.sid]
    assert {s["kind"] for n, rows in stats.items() if n.startswith("rt.task/")
            for s in rows} <= {"cpu", "accel"}
    assert stats["rt.task/Color deconv."][0]["sid"] == seg.sid
    # the RGB tile: staged, demoted from RAM, read from DMS, and its
    # promotion declined on size, before any fetch: no promotion span
    assert RGB_BYTES in [s["bytes"] for s in stats["rt.tier.put/MEM"]]
    assert RGB_BYTES in [s["bytes"] for s in stats["rt.tier.demote"]]
    assert RGB_BYTES in [s["bytes"] for s in stats["rt.tier.get/DMS"]]
    assert "rt.tier.promote" not in stats
    assert rgb_mem.promotions == 0 and rgb_mem.promotions_declined == 1


@pytest.mark.parametrize("roi", [BoundingBox((0, 0), (64, 128)), BoundingBox((0, 0), (128, 128))],
                         ids=["partial_roi", "whole_box"])
def test_promote_span_carries_box_bytes(tmp_path, roi):
    """A promotion that fits the RAM tier opens ``rt.tier.promote`` with
    the whole box's bytes, and says whether the box was fetched again."""
    box = BoundingBox((0, 0), (128, 128))
    ts = TieredStore([Tier("MEM", MemoryTier(name="MEM"), box.volume * 4),
                      Tier("DMS", MemoryTier(name="DMS"))], promote_after=1)
    key = RegionKey("t", "hot", ElementType.FLOAT32)
    ts.tiers[-1].backend.put(key, box, np.ones(box.shape, np.float32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        ts.get(key, roi)
    finally:
        jax.profiler.stop_trace()
        ts.close()
    stats = _rt_stats(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    assert stats["rt.tier.promote"] == [{"bytes": box.volume * 4, "refetched": roi != box}]
    assert ts.locality(key) == "MEM" and ts.tier_stats()["MEM"].promotions == 1


@pytest.mark.parametrize("module", ["repro.core.spans", "repro.storage.net",
                                    "repro.storage.tiers"])
def test_span_helper_imports_no_jax(module):
    """The DMS server processes import the store's modules and never JAX."""
    src = Path(__file__).resolve().parents[1] / "src"
    code = f"import sys, {module}; sys.exit('jax' in sys.modules)"
    assert subprocess.run([sys.executable, "-c", code], cwd=src, timeout=120).returncode == 0

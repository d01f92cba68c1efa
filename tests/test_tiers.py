"""Tiered staging subsystem: promotion, spill-down, write-back, locality."""
import threading
import time

import numpy as np
import pytest

from repro.core import BoundingBox, ElementType, RegionKey, StorageBackend, StorageRegistry
from repro.runtime.dag import Task, TaskCost
from repro.runtime.scheduler import SchedulerConfig
from repro.storage import (
    MemoryTier,
    PlacementPolicy,
    Tier,
    TieredStore,
    pin_namespace,
    size_threshold,
)

DOM = BoundingBox((0, 0), (128, 128))
TILE_BYTES = 128 * 128 * 4  # one float32 domain-sized region


def _key(name: str, ns: str = "t") -> RegionKey:
    return RegionKey(ns, name, ElementType.FLOAT32)


def _arr(seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).random((128, 128)).astype(np.float32)


def _mem_stack(capacity_tiles: int = 2, **kw) -> TieredStore:
    """Three in-memory tiers: deterministic, no disk/DMS setup needed."""
    return TieredStore(
        [
            Tier("MEM", MemoryTier(name="MEM"), capacity_tiles * TILE_BYTES),
            Tier("DISK", MemoryTier(name="DISK")),
            Tier("DMS", MemoryTier(name="DMS")),
        ],
        **kw,
    )


def test_protocol_and_registry_drop_in():
    ts = _mem_stack()
    assert isinstance(ts, StorageBackend)
    reg = StorageRegistry()
    reg.register(ts)
    assert reg.get("TIERED") is ts
    k, a = _key("r"), _arr()
    ts.put(k, DOM, a)
    np.testing.assert_array_equal(reg.get("TIERED").get(k, DOM), a)
    assert reg.locality("TIERED", k) == "MEM"
    ts.close()


def test_promotion_on_repeat_read():
    ts = _mem_stack(capacity_tiles=4, promote_after=2)
    k, a = _key("hot"), _arr()
    # stage directly into the bottom tier (externally produced data):
    # metadata-only locality cannot see it, the probing form can
    ts.tiers[-1].backend.put(k, DOM, a)
    assert ts.locality(k) is None
    assert ts.locality(k, probe=True) == "DMS"
    ts.get(k, DOM)
    assert ts.locality(k) == "DMS"  # below the promotion threshold
    ts.get(k, DOM)  # second read crosses promote_after -> straight to RAM
    assert ts.locality(k) == "MEM"
    stats = ts.tier_stats()
    assert stats["MEM"].promotions == 1
    assert stats["MEM"].bytes_promoted == a.nbytes
    # the promoted copy serves subsequent reads from RAM
    before = stats["MEM"].hits
    np.testing.assert_array_equal(ts.get(k, DOM), a)
    assert ts.tier_stats()["MEM"].hits == before + 1
    ts.close()


class _CountingTier(MemoryTier):
    """A memory tier that counts the gets made of it."""

    def __init__(self, name: str) -> None:
        super().__init__(name=name)
        self.gets = 0

    def get(self, key: RegionKey, roi: BoundingBox) -> np.ndarray:
        self.gets += 1
        return super().get(key, roi)


@pytest.mark.parametrize("roi", [DOM, BoundingBox((0, 0), (128, 384))],
                         ids=["partial_roi", "whole_box"])
def test_oversize_promotion_declined_before_fetch(roi):
    """A box larger than the RAM tier is never promoted, and the store
    decides so from its size, without fetching the box."""
    dms = _CountingTier("DMS")
    ts = TieredStore(
        [Tier("MEM", MemoryTier(name="MEM"), 2 * TILE_BYTES), Tier("DMS", dms)],
        promote_after=2,
    )
    box = BoundingBox((0, 0), (128, 384))  # three tiles: over the RAM tier
    k = _key("slide")
    a = np.random.default_rng(3).random((128, 384)).astype(np.float32)
    dms.put(k, box, a)
    for _ in range(2):  # the second get crosses promote_after
        np.testing.assert_array_equal(ts.get(k, roi), a[roi.local_slices(box)])
    assert dms.gets == 2  # one per get: no fetch of the whole box
    stats = ts.tier_stats()["MEM"]
    assert stats.promotions == 0 and stats.promotions_declined == 1
    assert stats.bytes_promoted == 0
    assert ts.locality(k) == "DMS"
    ts.close()


def test_capacity_eviction_demotes_not_drops():
    ts = _mem_stack(capacity_tiles=2, write_policy="write_back")
    keys = [_key(f"r{i}") for i in range(4)]
    arrs = [_arr(i) for i in range(4)]
    for k, a in zip(keys, arrs):
        ts.put(k, DOM, a)
    # MEM holds at most 2 tiles; older tiles must have been spilled DOWN
    assert ts.used_bytes("MEM") <= 2 * TILE_BYTES
    assert ts.tier_stats()["MEM"].demotions >= 2
    for k, a in zip(keys, arrs):  # nothing was dropped
        np.testing.assert_array_equal(ts.get(k, DOM), a)
    demoted = [k for k in keys if ts.locality(k) != "MEM"]
    assert demoted, "older regions should live in a lower tier"
    ts.close()


def test_write_through_is_immediately_durable():
    ts = _mem_stack(write_policy="write_through")
    k, a = _key("wt"), _arr()
    ts.put(k, DOM, a)
    np.testing.assert_array_equal(ts.tiers[-1].backend.get(k, DOM), a)
    assert not ts.dirty(k)
    ts.close()


def test_write_back_drain_makes_bottom_durable():
    ts = _mem_stack(write_policy="write_back")
    k, a = _key("wb"), _arr()
    ts.put(k, DOM, a)
    ts.drain()
    assert not ts.dirty(k)
    np.testing.assert_array_equal(ts.tiers[-1].backend.get(k, DOM), a)
    # delete cancels any still-queued flush without resurrecting the key
    k2 = _key("wb2")
    ts.put(k2, DOM, a)
    ts.delete(k2)
    ts.drain()
    with pytest.raises(KeyError):
        ts.get(k2, DOM)
    ts.close()


def test_concurrent_readers_and_flusher():
    ts = _mem_stack(capacity_tiles=3, write_policy="write_back", promote_after=1)
    keys = [_key(f"c{i}") for i in range(6)]
    arrs = [_arr(100 + i) for i in range(6)]
    for k, a in zip(keys, arrs):  # pre-populate: reads must NEVER fail
        ts.put(k, DOM, a)
    errors: list[BaseException] = []

    def writer():
        try:
            for _ in range(3):
                for k, a in zip(keys, arrs):
                    ts.put(k, DOM, a)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def reader(seed: int):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(40):
                i = int(rng.integers(len(keys)))
                # demotion/promotion/flush churn must never surface as a
                # missing key or torn payload
                got = ts.get(keys[i], DOM)
                np.testing.assert_array_equal(got, arrs[i])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(s,)) for s in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    ts.drain()
    assert not errors, errors
    bottom = ts.tiers[-1].backend
    for k, a in zip(keys, arrs):  # every write-back reached the bottom tier
        np.testing.assert_array_equal(bottom.get(k, DOM), a)
    ts.close()


def test_locality_reporting_tracks_movement():
    ts = _mem_stack(capacity_tiles=1, write_policy="write_through")
    k1, k2 = _key("a"), _key("b")
    ts.put(k1, DOM, _arr(1))
    assert ts.locality(k1) == "MEM"
    ts.put(k2, DOM, _arr(2))  # evicts k1 from the 1-tile MEM budget
    assert ts.locality(k2) == "MEM"
    assert ts.locality(k1) in ("DISK", "DMS")
    assert ts.locality(_key("missing")) is None
    ts.close()


def test_placement_pin_and_size_threshold():
    policy = PlacementPolicy(
        [
            pin_namespace("hot", "MEM"),
            size_threshold(TILE_BYTES // 2, "DMS"),
        ]
    )
    ts = _mem_stack(capacity_tiles=1, policy=policy)
    hot, big = _key("h", ns="hot"), _key("big")
    ts.put(hot, DOM, _arr(0))
    ts.put(big, DOM, _arr(1))  # > threshold: bypasses MEM straight to DMS
    assert ts.locality(big) == "DMS"
    # pinned region is never evicted from MEM even over budget
    ts.put(_key("h2", ns="hot"), DOM, _arr(2))
    assert ts.locality(hot) == "MEM"
    ts.close()


def test_lazy_drain_pushes_down_to_bottom():
    ts = _mem_stack(write_policy="lazy")
    k, a = _key("lz"), _arr()
    ts.put(k, DOM, a)
    assert ts.dirty(k)  # resident in MEM only
    with pytest.raises(KeyError):
        ts.tiers[-1].backend.get(k, DOM)
    ts.drain()
    assert not ts.dirty(k)
    np.testing.assert_array_equal(ts.tiers[-1].backend.get(k, DOM), a)
    ts.close()


def test_roi_granularity_spill():
    policy = PlacementPolicy(spill_block=(64, 64))
    ts = _mem_stack(capacity_tiles=1, policy=policy, write_policy="lazy")
    k1, k2 = _key("s1"), _key("s2")
    a1 = _arr(1)
    ts.put(k1, DOM, a1)
    ts.put(k2, DOM, _arr(2))  # k1 spills to DISK in 4 (64, 64) blocks
    disk = ts.tiers[1].backend
    found = dict(disk.query("t", "s1"))
    assert found[k1] == DOM  # union of the spill tiles covers the domain
    assert len(disk._chunks[k1]) == 4
    roi = BoundingBox((0, 0), (64, 64))
    np.testing.assert_array_equal(ts.get(k1, roi), a1[:64, :64])
    ts.close()


def test_scheduler_transfer_impact_refinement():
    ts = _mem_stack(capacity_tiles=1)
    mem_key, far_key = _key("near"), _key("far")
    ts.put(mem_key, DOM, _arr(0))
    ts.tiers[-1].backend.put(far_key, DOM, _arr(1))
    cfg = SchedulerConfig(
        data_locality=True,
        transfer_impact=0.3,
        locality_fn=ts.locality,
        tier_bandwidth={"MEM": 2e10, "DISK": 1.2e9, "DMS": 6e9},
    )
    cost = TaskCost(cpu_s=1e-3, speedup=2.0, input_bytes=TILE_BYTES)
    near = Task("near", cpu_fn=lambda: None, cost=cost, region_key=mem_key)
    far = Task("far", cpu_fn=lambda: None, cost=cost, region_key=far_key)
    unknown = Task("unknown", cpu_fn=lambda: None, cost=cost)
    # memory-resident input -> near-zero impact; DMS-resident -> larger
    assert cfg.transfer_impact_for(near) < 0.05
    assert cfg.transfer_impact_for(far) > cfg.transfer_impact_for(near)
    # no locality info -> the paper's flat user-provided impact
    assert cfg.transfer_impact_for(unknown) == pytest.approx(0.3)
    assert SchedulerConfig().transfer_impact_for(near) == pytest.approx(0.2)
    ts.close()


def test_query_unions_across_tiers():
    ts = _mem_stack(capacity_tiles=1)
    k1, k2 = _key("q", ns="qq"), _key("q2", ns="qq")
    ts.put(k1, DOM, _arr(1))
    ts.put(k2, DOM, _arr(2))  # k1 demoted out of MEM
    assert dict(ts.query("qq", "q"))[k1] == DOM
    assert dict(ts.query("qq", "q2"))[k2] == DOM
    ts.close()


def test_delete_removes_from_all_tiers():
    ts = _mem_stack(capacity_tiles=1)
    k = _key("d")
    ts.put(k, DOM, _arr())
    ts.put(_key("d2"), DOM, _arr(2))  # push k down
    ts.delete(k)
    assert ts.locality(k) is None
    with pytest.raises(KeyError):
        ts.get(k, DOM)
    ts.close()


def test_overwrite_survives_demotion_with_stale_lower_copy():
    """A lazy overwrite in MEM must be spilled (not dropped) on eviction
    even though a lower tier still holds the previous generation."""
    ts = _mem_stack(capacity_tiles=1, write_policy="lazy")
    k1, k2, k3 = _key("v"), _key("f1"), _key("f2")
    v1, v2 = _arr(1), _arr(2)
    ts.put(k1, DOM, v1)
    ts.put(k2, DOM, _arr(3))  # evict k1 -> spilled to DISK (gen 1)
    ts.get(k1, DOM)
    ts.get(k1, DOM)  # promote k1 back to MEM (DISK keeps the gen-1 copy)
    ts.put(k1, DOM, v2)  # lazy overwrite: MEM gen 2, DISK still gen 1
    ts.put(k3, DOM, _arr(4))  # evict k1 again — must spill v2, not drop
    np.testing.assert_array_equal(ts.get(k1, DOM), v2)
    ts.drain()  # checkpoint must also carry the new generation
    np.testing.assert_array_equal(ts.tiers[-1].backend.get(k1, DOM), v2)
    ts.close()


def test_cross_tier_roi_assembly():
    """Placement can split one key's chunks across tiers; a spanning ROI
    must still assemble (the flat backends honor this contract)."""
    from repro.storage import size_threshold

    threshold = 32 * 128 * 4  # the small chunk's exact size
    policy = PlacementPolicy([size_threshold(threshold, "DMS")])
    ts = _mem_stack(policy=policy, write_policy="lazy")
    k = _key("split")
    top = BoundingBox((0, 0), (32, 128))
    bottom = BoundingBox((32, 0), (128, 128))
    small = _arr(1)[:32]  # == threshold -> stays in MEM
    big = _arr(2)[:96]  # > threshold -> routed to DMS
    ts.put(k, top, small)
    ts.put(k, bottom, big)
    got = ts.get(k, DOM)  # spans both tiers
    np.testing.assert_array_equal(got[:32], small)
    np.testing.assert_array_equal(got[32:], big)
    ts.close()


def test_fresh_overwrite_wins_over_stale_faster_tier():
    """A fresh overwrite routed to a slower tier must win over stale
    chunks lingering in a faster tier, and locality must report the
    serving tier."""
    policy = PlacementPolicy([size_threshold(64 * 128 * 4, "DISK")])
    ts = _mem_stack(policy=policy, write_policy="lazy")
    k = _key("ow")
    top = BoundingBox((0, 0), (64, 128))
    bottom = BoundingBox((64, 0), (128, 128))
    ts.put(k, top, np.full((64, 128), 1.0, np.float32))  # gen1 -> MEM
    ts.put(k, bottom, np.full((64, 128), 2.0, np.float32))  # gen2 -> MEM
    ts.put(k, DOM, np.full((128, 128), 9.0, np.float32))  # gen3 -> DISK
    assert (ts.get(k, DOM) == 9.0).all()
    assert ts.locality(k) == "DISK"
    ts.close()


def test_placement_write_policy_validated():
    from repro.storage import Placement

    with pytest.raises(ValueError):
        Placement(write_policy="writeback")  # typo must fail loudly
    Placement(write_policy="write_back")  # valid values pass


def test_delete_then_reput_does_not_lose_new_data():
    """Generations stay monotonic across delete/re-put, so a late flush
    of the old incarnation can never shadow the new one."""
    ts = _mem_stack(write_policy="write_back")
    k = _key("re")
    v1, v2 = _arr(1), _arr(2)
    ts.put(k, DOM, v1)
    ts.delete(k)
    ts.put(k, DOM, v2)
    ts.drain()
    np.testing.assert_array_equal(ts.get(k, DOM), v2)
    np.testing.assert_array_equal(ts.tiers[-1].backend.get(k, DOM), v2)
    ts.close()


def test_standard_stack_opt_in_auto_repair(tmp_path):
    """TieredStore.standard(repair_interval=...) runs the DMS tier's
    anti-entropy sweep in the background; close() stops it."""
    store = TieredStore.standard(
        BoundingBox((0, 0), (64, 64)),
        (16, 16),
        root=str(tmp_path),
        num_servers=4,
        replication=2,
        repair_interval=0.05,
    )
    dms = store.tiers[2].backend
    assert dms._repair_thread is not None and dms._repair_thread.is_alive()
    key = RegionKey("t", "heal", ElementType.FLOAT32)
    arr = np.random.default_rng(9).random((64, 64)).astype(np.float32)
    dms.put(key, BoundingBox((0, 0), (64, 64)), arr)
    shard = dms.transport.servers[1]
    shard._blocks.clear()
    shard._meta.clear()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and dms.stats.repaired_blocks == 0:
        time.sleep(0.02)
    assert dms.stats.repaired_blocks > 0  # healed without an explicit call
    store.close()
    assert dms._repair_thread is None


def test_wsi_pipeline_runs_unmodified_on_tiered_storage(tmp_path):
    """Acceptance: the RT two-stage pipeline runs against TieredStore
    registered under the same names, with zero call-site changes."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.configs.wsi import WSIConfig
    from repro.core import Intent, RegionTemplate
    from repro.pipeline import (
        FeatureStage,
        SegmentationStage,
        analyze_tile,
        make_tile,
        make_wsi_storage,
    )
    from repro.runtime import SysEnv

    rgb, _ = make_tile(96, num_nuclei=6, seed=5)
    h, w = rgb.shape[1:]
    cfg = WSIConfig(seg_threshold=0.5, nucleus_roi=16)
    plain = analyze_tile(jnp.asarray(rgb), cfg, impl="xla")

    reg = make_wsi_storage(h, w, mode="tiered", num_servers=1, root=str(tmp_path))
    dom3 = BoundingBox((0, 0, 0), (3, h, w))
    dom2 = BoundingBox((0, 0), (h, w))
    rt = RegionTemplate("Patient")
    rgb_region = rt.new_region("RGB", dom3, np.float32, input_storage="DMS3", lazy=True)
    reg.get("DMS3").put(rgb_region.key, dom3, np.asarray(rgb))

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
    got_mask = reg.get("DMS2").get(mask_key, dom2)
    np.testing.assert_array_equal(got_mask, np.asarray(plain["labels"]))
    got = feat.templates["Patient"].get("Features").data
    np.testing.assert_allclose(got["features"], plain["features"], rtol=1e-4, atol=1e-4)

    # the hierarchy actually absorbed the traffic + locality events flowed
    stats = reg.get("DMS2").tier_stats()
    assert stats["MEM"].puts > 0
    assert any(ev == "locality" for ev, _ in env.manager.events)
    for backend_name in ("DMS3", "DMS2"):
        reg.get(backend_name).close()

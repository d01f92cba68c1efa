"""Chip smoke: the WSI pipeline and near-data compute on one TPU chip.

    python chip_smoke.py [--seed N]

One process, in order:

  1. backend  — JAX must find a TPU; anything else exits non-zero;
  2. tile     — two 4096x4096 tiles of a synthetic slide through
                ``analyze_tile`` with the default ``WSIConfig``, once with
                the Pallas kernels and once with the XLA references: mask
                and labels must be equal, features within FEATURE_RTOL, and
                each kernel's jitted wrapper must compile to a
                ``tpu_custom_call``;
  3. runtime  — the same tiles through ``SegmentationStage`` /
                ``FeatureStage`` on the Manager/Worker runtime
                (``impl="auto"``) over tiered storage whose DMS tier is
                socket-server subprocesses: the staged labels must equal
                the tile phase's, with no failed stage;
  4. serve    — a ``RegionGateway`` with ``compute_impl="pallas"`` answers
                ``deconv|threshold|ccl|count`` over a whole tile and
                ``glcm`` over a 64x64 ROI: each must equal the chain run
                with ``impl="xla"`` on the same bytes.

Any failure exits non-zero.  Lines prefixed ``smoke:`` are progress notes
(wall times include compilation), not measurements.  The last line, on
success only, is ``{"ok": true, "device": {...}}`` as JAX reports it.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TILE = 4096  # WSIConfig().tile: the paper's 4K x 4K tiles
# GLCM and histogram counts are exact integers; the features derived from
# them (O(0.1)..O(100)) may differ by float rounding only
FEATURE_RTOL, FEATURE_ATOL = 1e-5, 1e-6
GLCM_ROI = 64  # WSIConfig().nucleus_roi


def note(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def backend_phase() -> dict:
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, but JAX found platform {platform!r}; "
            f"refusing to run the smoke anywhere else"
        )
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    note(f"device {device['kind']} x{device['count']} ({device['platform']})")
    return device


def tile_phase(tiles, cfg) -> list[dict]:
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.pipeline import analyze_tile

    results = []
    for i, tile in enumerate(tiles):
        x = jnp.asarray(tile)
        t0 = time.perf_counter()
        got = analyze_tile(x, cfg, impl="pallas")
        t1 = time.perf_counter()
        want = analyze_tile(x, cfg, impl="xla")
        t2 = time.perf_counter()
        mask, want_mask = np.asarray(got["mask"]), np.asarray(want["mask"])
        if not np.array_equal(mask, want_mask):
            minv = jnp.asarray(ref.stain_inverse())
            ddiff = jnp.abs(
                ops.color_deconv(x, minv, impl="pallas") - ops.color_deconv(x, minv, impl="xla")
            ).max()
            raise AssertionError(
                f"tile {i}: pallas mask differs from xla in "
                f"{int((mask != want_mask).sum())} pixels "
                f"(max color-deconv difference {float(ddiff):.3g})"
            )
        check(
            np.array_equal(np.asarray(got["labels"]), np.asarray(want["labels"])),
            f"tile {i}: pallas labels differ from xla",
        )
        f, wf = got["features"], want["features"]
        check(f.shape == wf.shape, f"tile {i}: feature shapes {f.shape} vs {wf.shape}")
        check(np.isfinite(f).all(), f"tile {i}: non-finite pallas features")
        np.testing.assert_allclose(f, wf, rtol=FEATURE_RTOL, atol=FEATURE_ATOL, err_msg=f"tile {i}")
        labels = np.asarray(got["labels"])
        n_obj = np.unique(labels[labels >= 0]).size
        note(
            f"tile {i}: {int(mask.sum())} nucleus pixels, {n_obj} objects, "
            f"{f.shape[0]} ROIs; pallas {t1 - t0:.1f}s, xla {t2 - t1:.1f}s (with compile)"
        )
        results.append(got)

    # the Pallas kernels really ran: each wrapper compiles to a Mosaic call
    x = jnp.asarray(tiles[0])
    minv = jnp.asarray(ref.stain_inverse())
    plane = jnp.zeros((TILE, TILE), jnp.float32)
    bins = jnp.zeros(results[0]["rois"].shape, jnp.int32)
    wrappers = {
        "color_deconv": (ops.color_deconv, (x, minv)),
        "morph_recon": (ops.morph_recon, (plane, plane)),
        "connected_components": (ops.connected_components, (plane.astype(jnp.int32),)),
        "texture_features": (ops.texture_features, (bins, cfg.num_bins)),
    }
    for name, (fn, args) in wrappers.items():
        text = fn.lower(*args, impl="pallas").compile().as_text()
        check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in the compiled wrapper")
    note(f"tpu_custom_call present in {', '.join(wrappers)}")
    return results


def runtime_phase(registry, rgb, cfg, want: list[dict]):
    from repro.core import BoundingBox, Intent, RegionTemplate
    from repro.pipeline import FeatureStage, SegmentationStage
    from repro.runtime import SchedulerConfig, SysEnv

    h, w = rgb.shape[1:]
    dom3 = BoundingBox((0, 0, 0), (3, h, w))
    dom2 = BoundingBox((0, 0), (h, w))
    rt = RegionTemplate("Patient")
    rgb_region = rt.new_region("RGB", dom3, np.float32, input_storage="DMS3", lazy=True)
    registry.get("DMS3").put(rgb_region.key, dom3, rgb)

    env = SysEnv(
        num_workers=2, cpus_per_worker=2, accels_per_worker=1,
        sched=SchedulerConfig(policy="PATS"), registry=registry, heartbeat_timeout=60.0,
    )
    segs, feats = [], []
    for part2 in dom2.tiles((TILE, TILE)):
        part3 = BoundingBox((0,) + part2.lo, (3,) + part2.hi)
        seg = SegmentationStage(cfg, impl="auto")
        seg.add_region_template(rt, "RGB", part3, Intent.INPUT, read_storage="DMS3")
        seg.add_region_template(rt, "Mask", part2, Intent.OUTPUT, storage="DMS2")
        seg.add_region_template(rt, "Hema", part2, Intent.OUTPUT, storage="DMS2")
        feat = FeatureStage(cfg, impl="auto")
        feat.add_region_template(rt, "Mask", part2, Intent.INPUT, read_storage="DMS2")
        feat.add_region_template(rt, "Hema", part2, Intent.INPUT, read_storage="DMS2")
        feat.add_dependency(seg)
        env.execute_component(seg)
        env.execute_component(feat)
        segs.append((part2, seg))
        feats.append(feat)
    t0 = time.perf_counter()
    try:
        env.startup_execution()
    finally:
        env.finalize_system()
    wall = time.perf_counter() - t0

    failed = [e for e in env.manager.events if e[0] == "failed"]
    check(not failed, f"runtime: failed stage events {failed}")
    dms2 = registry.get("DMS2")
    for i, ((part2, seg), feat) in enumerate(zip(segs, feats)):
        # the stage stores BWLabel's labels in "Mask" (background -1)
        staged = dms2.get(seg.templates["Patient"].get("Mask").key, part2)
        check(
            np.array_equal(staged, np.asarray(want[i]["labels"])),
            f"runtime: staged labels of tile {i} differ from the tile phase",
        )
        got_feats = feat.templates["Patient"].get("Features").data["features"]
        np.testing.assert_allclose(
            got_feats, want[i]["features"], rtol=FEATURE_RTOL, atol=FEATURE_ATOL,
            err_msg=f"runtime features, tile {i}",
        )
    note(f"runtime: {len(segs)} tiles through Manager/WRM in {wall:.1f}s, no failed stage")
    hema_key = segs[0][1].templates["Patient"].get("Hema").key
    return rgb_region.key, hema_key


def serve_phase(registry, rgb_key, hema_key) -> None:
    from repro.core import BoundingBox
    from repro.kernels.chains import resolve_chain
    from repro.serve.gateway import GatewayConfig, RegionGateway

    c = TILE // 2  # a nucleus-sized ROI inside tile 0's hematoxylin plane
    requests = [
        ("DMS3", rgb_key, BoundingBox((0, 0, 0), (3, TILE, TILE)), "deconv|threshold|ccl|count"),
        ("DMS2", hema_key, BoundingBox((c, c), (c + GLCM_ROI, c + GLCM_ROI)), "glcm"),
    ]
    config = GatewayConfig(compute_impl="pallas")
    for store_name, key, roi, chain in requests:
        store = registry.get(store_name)
        gw = RegionGateway(store, config=config)
        try:
            t0 = time.perf_counter()
            got = gw.compute(key, roi, chain)
            wall = time.perf_counter() - t0
            want = resolve_chain(chain)(store.get(key, roi), impl="xla")
            check(
                got.shape == want.shape and got.dtype == want.dtype,
                f"serve {chain}: {got.shape}/{got.dtype} vs {want.shape}/{want.dtype}",
            )
            check(np.array_equal(got, want), f"serve {chain}: {got!r} != xla {want!r}")
            stats = gw.stats.as_dict()
            check(stats["compute_failed"] == 0, f"serve {chain}: compute_failed {stats}")
            shown = np.array2string(got.ravel(), precision=4, max_line_width=200)
            note(f"serve {chain} over {roi.shape}: {shown} in {wall:.1f}s (with compile)")
        finally:
            gw.close(close_store=False)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="synthetic slide seed")
    args = ap.parse_args()

    t_start = time.perf_counter()
    device = backend_phase()

    from repro.configs.wsi import WSIConfig
    from repro.core import StorageRegistry
    from repro.launch.compile_cache import enable_compile_cache
    from repro.pipeline import make_slide, make_wsi_storage

    note(f"compile cache at {enable_compile_cache()}")
    cfg = WSIConfig()
    t0 = time.perf_counter()
    rgb, _ = make_slide(1, 2, TILE, seed=args.seed)
    tiles = [rgb[:, :, i * TILE : (i + 1) * TILE] for i in range(2)]
    note(f"slide {rgb.shape} made in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    want = tile_phase(tiles, cfg)
    note(f"tile phase passed in {time.perf_counter() - t0:.1f}s")

    root = tempfile.mkdtemp(prefix="chip_smoke_tiers_")
    h, w = rgb.shape[1:]
    registry = StorageRegistry()
    try:
        make_wsi_storage(
            h, w, mode="tiered", transport="socket", tile=TILE, root=root, registry=registry
        )
        note(f"DMS servers: {len(registry.server_group.procs)} subprocesses")
        t0 = time.perf_counter()
        rgb_key, hema_key = runtime_phase(registry, rgb, cfg, want)
        note(f"runtime phase passed in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        serve_phase(registry, rgb_key, hema_key)
        note(f"serve phase passed in {time.perf_counter() - t0:.1f}s")
    finally:
        for name in registry.names():
            registry.get(name).close()
        group = getattr(registry, "server_group", None)
        if group is not None:
            group.close()
        shutil.rmtree(root, ignore_errors=True)
    note(f"all phases passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

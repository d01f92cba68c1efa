"""The paper's example application: segmentation + feature computation.

Mirrors Fig. 1: the segmentation stage turns an RGB tile into a nucleus
mask + labels; the feature stage computes per-nucleus texture/shape
features.  Exposed in two forms:

  * plain functions (``segment_tile``, ``compute_features``) — the
    "non-RT" baseline of Fig. 11;
  * region-template stages (``SegmentationStage``, ``FeatureStage``) —
    the RT-based version whose fine-grain operations flow through the
    WRM with per-op speedup estimates (PATS-able), and whose data moves
    through global storage.

Every compute hot spot dispatches through repro.kernels.ops so the same
pipeline runs the Pallas kernels on TPU and the jnp references on CPU.
"""
from __future__ import annotations

import os
import tempfile
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.wsi import PAPER_OP_COSTS, PAPER_OP_SPEEDUPS, WSIConfig
from repro.core import BoundingBox, RegionKind, StorageRegistry
from repro.kernels import ops, ref
from repro.runtime.dag import Stage, Task, TaskCost
from repro.storage import DistributedMemoryStorage, PlacementPolicy, TieredStore


# ---------------------------------------------------------------------------
# Plain (non-RT) pipeline functions
# ---------------------------------------------------------------------------
def segment_tile(rgb: jax.Array, cfg: WSIConfig, impl: str = "auto") -> dict:
    """RGB (3, H, W) -> {"mask", "labels", "hematoxylin"}."""
    minv = jnp.asarray(ref.stain_inverse())
    stains = ops.color_deconv(rgb, minv, impl=impl)
    hema = stains[0]  # hematoxylin density (nuclei stain)
    # normalize to [0,1] for thresholding
    h_lo = jnp.percentile(hema, 5.0)
    h_hi = jnp.percentile(hema, 99.5)
    hema_n = jnp.clip((hema - h_lo) / jnp.maximum(h_hi - h_lo, 1e-6), 0.0, 1.0)
    raw = (hema_n > cfg.seg_threshold).astype(jnp.float32)
    filled = ops.fill_holes(raw, impl=impl)
    # morphological reconstruction opening: erode-ish marker then rebuild
    marker = jnp.minimum(filled, jnp.roll(filled, 1, -1) * jnp.roll(filled, -1, -1)
                         * jnp.roll(filled, 1, -2) * jnp.roll(filled, -1, -2))
    opened = ops.morph_recon(marker, filled, impl=impl)
    mask = (opened > 0.5).astype(jnp.int32)
    labels = ops.connected_components(mask, impl=impl)
    return {"mask": mask, "labels": labels, "hematoxylin": hema_n}


def extract_object_rois(
    labels: np.ndarray, intensity: np.ndarray, cfg: WSIConfig
) -> tuple[np.ndarray, np.ndarray]:
    """Per-object fixed-size ROI batch (replaces dynamic GPU block assignment).

    Returns (rois (K, R, R) float32 intensity crops, boxes (K, 4) int32).
    """
    labels = np.asarray(labels)
    intensity = np.asarray(intensity)
    r = cfg.nucleus_roi
    ids = np.unique(labels)
    ids = ids[ids >= 0][: cfg.max_objects_per_tile]
    n = len(ids)
    rois = np.zeros((n, r, r), np.float32)
    boxes = np.zeros((n, 4), np.int32)
    h, w = labels.shape
    # every object's bounding box in one pass over the labelled pixels
    # (ids holds every label up to ids[-1], so k < n means "selected")
    flat = np.flatnonzero(labels >= 0)
    k = np.searchsorted(ids, labels.ravel()[flat])
    sel = k < n
    k = k[sel]
    ys, xs = np.divmod(flat[sel], w)
    ymin, xmin = np.full(n, h), np.full(n, w)
    ymax, xmax = np.full(n, -1), np.full(n, -1)
    np.minimum.at(ymin, k, ys)
    np.minimum.at(xmin, k, xs)
    np.maximum.at(ymax, k, ys)
    np.maximum.at(xmax, k, xs)
    for i in range(n):
        y0, y1 = ymin[i], ymax[i] + 1
        x0, x1 = xmin[i], xmax[i] + 1
        cy, cx = (y0 + y1) // 2, (x0 + x1) // 2
        y0 = np.clip(cy - r // 2, 0, max(h - r, 0))
        x0 = np.clip(cx - r // 2, 0, max(w - r, 0))
        crop = intensity[y0 : y0 + r, x0 : x0 + r]
        rois[i, : crop.shape[0], : crop.shape[1]] = crop
        boxes[i] = (y0, x0, min(y0 + r, h), min(x0 + r, w))
    return rois, boxes


def compute_features(
    rois: np.ndarray, cfg: WSIConfig, impl: str = "auto"
) -> np.ndarray:
    """(K, R, R) intensity crops -> (K, 9) texture features."""
    if len(rois) == 0:
        return np.zeros((0, 9), np.float32)
    bins = ref.quantize_ref(jnp.asarray(rois), cfg.num_bins)
    feats = ops.texture_features(bins, cfg.num_bins, impl=impl)
    return np.asarray(feats)


def analyze_tile(rgb: jax.Array, cfg: WSIConfig, impl: str = "auto") -> dict:
    seg = segment_tile(rgb, cfg, impl)
    rois, boxes = extract_object_rois(seg["labels"], seg["hematoxylin"], cfg)
    feats = compute_features(rois, cfg, impl)
    return {**seg, "rois": rois, "boxes": boxes, "features": feats}


# ---------------------------------------------------------------------------
# Storage wiring: flat DMS baseline vs. opt-in tiered hierarchy
# ---------------------------------------------------------------------------
def make_wsi_storage(
    h: int,
    w: int,
    *,
    mode: str = "dms",
    transport: str = "inproc",
    registry: StorageRegistry | None = None,
    root: str | None = None,
    tile: int | None = None,
    num_servers: int = 4,
    server_processes: int = 2,
    endpoints=None,
    replication: int = 1,
    repair=None,
    wire_codec=None,
    membership=None,
    mem_capacity_bytes: int = 64 << 20,
    write_policy: str = "write_through",
    policy: PlacementPolicy | None = None,
    promote_after: int = 2,
    serve=False,
    compute=False,
) -> StorageRegistry:
    """Build the storage backing the WSI stages under the canonical names
    ("DMS3" for the (3, H, W) RGB volume, "DMS2" for the 2-D mask/hema
    domain), so stage bindings never change.

    ``mode="dms"`` is the paper baseline (one DMS per domain);
    ``mode="tiered"`` swaps in :class:`TieredStore` stacks (bounded RAM
    -> DISK -> DMS) behind the same names — the opt-in hierarchy with
    zero call-site changes.

    ``transport`` picks the DMS server link: ``"inproc"`` keeps the
    in-process shards, ``"socket"`` puts the DMS tier on real TCP
    servers, and ``"shm"`` is ``"socket"`` plus the negotiated
    shared-memory data plane — co-located fetches arrive by arena
    reference instead of a TCP stream copy, degrading automatically to
    socket payloads for remote or pre-arena servers.  ``wire_codec``
    (one of ``repro.storage.codec.WIRE_CODECS``, e.g. ``"zlib"``, or a
    per-key glob mapping like ``{"labels/*": "zlib", "feat/*": "bf16"}``)
    compresses socket payloads per connection; raw-vs-wire savings show
    up in ``storage_stats()``.  ``membership`` seeds the stores' elastic
    fleet view (:class:`~repro.storage.membership.RingView`); ``None``
    means the genesis ring, and each store's ``add_server`` /
    ``remove_server`` / ``rebalance`` then resize the fleet live.
    With ``endpoints`` (a list of
    ``(host, port)`` / "host:port"
    addresses, one per server id) the stores attach to an already-running
    fleet; otherwise ``num_servers`` shards are spawned locally across
    ``server_processes`` processes and the started
    :class:`~repro.storage.net.ServerGroup` is attached to the returned
    registry as ``registry.server_group`` — the caller owns it (close it
    after closing the stores).  ``replication=R`` turns on the DMS
    stores' R-way block replication (home + next R-1 servers along the
    SFC ring): reads fail over between replicas and puts re-home blocks
    past dead replicas, so any R-1 dead servers cause zero failed reads
    AND zero failed puts.  ``repair=`` opts into the DMS stores'
    background anti-entropy sweep (``True`` for the 30 s default or a
    float interval in seconds): a crashed server that rejoins empty is
    re-filled until every block has R live copies again; closing the
    stores stops the sweeps.

    In tiered mode the DISK tiers live under ``root`` (subdirs per
    store).  Pass your own ``root`` if you want to clean it up; the
    default is a fresh ``tempfile.mkdtemp`` the caller owns (reachable
    via each store's DISK backend: ``store.tiers[1].backend.root``).

    ``serve`` fronts every store with a
    :class:`~repro.serve.gateway.RegionGateway` (pass ``True`` for the
    defaults or a :class:`~repro.serve.gateway.GatewayConfig`): many
    concurrent clients then share one hierarchy through a bounded,
    request-coalescing worker pool with ``TierStats``-driven admission
    control.  The gateways register under the same names ("DMS3"/
    "DMS2"), so stage bindings never change; closing a gateway closes
    its store.

    ``compute=True`` turns the gateways into the paper's near-data
    analysis service: clients call ``registry.get("DMS3").compute(key,
    roi, "deconv|threshold|ccl")`` and the kernel chain runs server-side
    (Pallas on TPU, jnp references elsewhere), returning only the
    derived mask/labels/features — an order-of-magnitude egress cut for
    derived-product queries, with a put-generation-invalidated derived
    cache for repeated hot analyses.  ``compute=True`` implies
    ``serve=True``; pass a :class:`~repro.serve.gateway.GatewayConfig`
    via ``serve=`` to size the derived cache (``compute_cache_bytes``)
    or pin the kernel impl (``compute_impl``).
    """
    from repro.storage import SocketTransport, spawn_servers

    registry = registry or StorageRegistry()
    dom3 = BoundingBox((0, 0, 0), (3, h, w))
    dom2 = BoundingBox((0, 0), (h, w))
    blk = tile or max(h, w)
    if repair is True:
        repair = 30.0
    repair_interval = None if not repair else float(repair)
    if transport not in ("inproc", "socket", "shm"):
        raise ValueError(
            f"unknown transport {transport!r} (want 'inproc' | 'socket' | 'shm')"
        )
    if transport == "inproc" and wire_codec is not None:
        raise ValueError(
            "wire_codec= needs transport='socket' or 'shm' (in-process shards "
            "move no wire bytes); refusing to silently ignore it"
        )
    if endpoints is not None:
        if transport == "inproc":
            raise ValueError(
                f"endpoints= only makes sense with transport='socket'/'shm' "
                f"(got transport={transport!r}); refusing to silently build "
                f"in-process shards"
            )
        num_servers = len(endpoints)  # one server id per endpoint entry
    shm_mode = "auto" if transport == "shm" else "off"

    def _transport(scope: str):
        """One transport per store: shards are shared across stores, so
        each store scopes its keyspace (and owns its connections)."""
        if transport == "inproc":
            return None
        kw = dict(scope=scope, wire_codec=wire_codec, shm=shm_mode)
        if endpoints is not None:
            return SocketTransport(endpoints, **kw)
        group = getattr(registry, "server_group", None)
        if group is None:
            group = spawn_servers(num_servers, processes=server_processes)
            registry.server_group = group
        return group.transport(**kw)

    if mode == "dms":
        for sname, dom, bshape in (
            ("DMS3", dom3, (3, blk, blk)),
            ("DMS2", dom2, (blk, blk)),
        ):
            dms = DistributedMemoryStorage(
                dom, bshape, num_servers, name=sname,
                transport=_transport(sname), replication=replication,
                membership=membership,
            )
            if repair_interval is not None:
                dms.start_auto_repair(repair_interval)
            registry.register(dms)
    elif mode == "tiered":
        root = root or tempfile.mkdtemp(prefix="wsi_tiers_")
        for name, dom, bshape in (
            ("DMS3", dom3, (3, blk, blk)),
            ("DMS2", dom2, (blk, blk)),
        ):
            registry.register(
                TieredStore.standard(
                    dom,
                    bshape,
                    root=os.path.join(root, name.lower()),
                    name=name,
                    mem_capacity_bytes=mem_capacity_bytes,
                    num_servers=num_servers,
                    write_policy=write_policy,
                    policy=policy,
                    promote_after=promote_after,
                    dms_transport=_transport(name),
                    replication=replication,
                    repair_interval=repair_interval,
                    membership=membership,
                )
            )
    else:
        raise ValueError(f"unknown storage mode {mode!r} (want 'dms' | 'tiered')")
    if compute and not serve:
        serve = True  # near-data compute runs inside the serving gateway
    if serve:
        from repro.serve.gateway import GatewayConfig, RegionGateway

        if isinstance(serve, GatewayConfig):
            gw_config = serve
        elif serve is True:
            gw_config = None  # gateway defaults
        else:
            raise TypeError(
                f"serve= wants True or a GatewayConfig, got {serve!r}; "
                f"refusing to silently ignore gateway settings"
            )
        for name in ("DMS3", "DMS2"):
            registry.register(RegionGateway(registry.get(name), config=gw_config))
    return registry


# ---------------------------------------------------------------------------
# Region-template stages (paper Fig. 8)
# ---------------------------------------------------------------------------
def _task_cost(op: str, scale: float = 1.0, input_bytes: int = 0) -> TaskCost:
    return TaskCost(
        cpu_s=PAPER_OP_COSTS.get(op, 1.0) * scale,
        speedup=PAPER_OP_SPEEDUPS.get(op, 1.0),
        input_bytes=input_bytes,
    )


class SegmentationStage(Stage):
    """Reads "RGB", produces "Mask" (+ float labels channel)."""

    def __init__(self, cfg: WSIConfig | None = None, impl: str = "auto") -> None:
        super().__init__("Segmentation")
        self.cfg = cfg or WSIConfig()
        self.impl = impl

    def run(self, ctx) -> Any:
        rgb_region = ctx.region("Patient", "RGB")
        rgb = jnp.asarray(rgb_region.data)
        rt = self.get_region_template("Patient")
        roi = rgb_region.roi
        # mask/hema live on the spatial (H, W) domain; drop the channel axis
        spatial = (
            roi
            if roi.rank == 2
            else BoundingBox(roi.lo[-2:], roi.hi[-2:], roi.t_lo, roi.t_hi)
        )
        mask_region = rt.new_region(
            "Mask", spatial, np.int32, timestamp=rgb_region.key.timestamp
        )
        hema_region = rt.new_region(
            "Hema", spatial, np.float32, timestamp=rgb_region.key.timestamp
        )

        results: dict[str, Any] = {}

        def op(name, fn, deps=(), region_key=None, input_bytes=0):
            def work():
                results[name] = fn()

            return ctx.submit(
                Task(
                    name,
                    cpu_fn=work,
                    accel_fn=work,
                    deps=list(deps),
                    cost=_task_cost(name, input_bytes=input_bytes),
                    region_key=region_key,
                )
            )

        t_deconv = op(
            "Color deconv.",
            lambda: ops.color_deconv(rgb, jnp.asarray(ref.stain_inverse()), impl=self.impl),
            region_key=rgb_region.key,
            input_bytes=rgb_region.nbytes,
        )

        def threshold():
            hema = results["Color deconv."][0]
            lo = jnp.percentile(hema, 5.0)
            hi = jnp.percentile(hema, 99.5)
            hn = jnp.clip((hema - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)
            results["hema_n"] = hn
            return (hn > self.cfg.seg_threshold).astype(jnp.float32)

        t_thr = op("AreaThreshold", threshold, deps=[t_deconv])
        t_fill = op(
            "FillHolles",
            lambda: ops.fill_holes(results["AreaThreshold"], impl=self.impl),
            deps=[t_thr],
        )

        def recon():
            filled = results["FillHolles"]
            marker = jnp.minimum(
                filled,
                jnp.roll(filled, 1, -1) * jnp.roll(filled, -1, -1)
                * jnp.roll(filled, 1, -2) * jnp.roll(filled, -1, -2),
            )
            return ops.morph_recon(marker, filled, impl=self.impl)

        t_recon = op("ReconToNuclei", recon, deps=[t_fill])
        t_label = op(
            "BWLabel",
            lambda: ops.connected_components(
                (results["ReconToNuclei"] > 0.5).astype(jnp.int32), impl=self.impl
            ),
            deps=[t_recon],
        )

        def finalize():
            mask_region.set_data(np.asarray(results["BWLabel"], np.int32))
            hema_region.set_data(np.asarray(results["hema_n"], np.float32))

        ctx.submit(Task("stage-finalize", cpu_fn=finalize, deps=[t_label],
                        cost=TaskCost(cpu_s=0.05)))
        return None


class FeatureStage(Stage):
    """Reads "Mask"+"Hema", produces the "Features" object set."""

    def __init__(self, cfg: WSIConfig | None = None, impl: str = "auto") -> None:
        super().__init__("FeatureComputation")
        self.cfg = cfg or WSIConfig()
        self.impl = impl

    def run(self, ctx) -> Any:
        mask_region = ctx.region("Patient", "Mask")
        hema_region = ctx.region("Patient", "Hema")
        rt = self.get_region_template("Patient")
        feat_region = rt.new_region(
            "Features",
            mask_region.roi,
            np.float32,
            kind=RegionKind.OBJECTSET,
            timestamp=mask_region.key.timestamp,
        )
        results: dict[str, Any] = {}

        def rois():
            results["rois"], results["boxes"] = extract_object_rois(
                mask_region.data, hema_region.data, self.cfg
            )

        t_rois = ctx.submit(
            Task(
                "ObjectROIs",
                cpu_fn=rois,
                cost=_task_cost(
                    "BWLabel",
                    input_bytes=mask_region.nbytes + hema_region.nbytes,
                ),
                region_key=mask_region.key,
            )
        )

        def feats():
            f = compute_features(results["rois"], self.cfg, self.impl)
            feat_region.set_data({
                "features": f,
                "boxes": results["boxes"],
            })

        ctx.submit(
            Task("Features", cpu_fn=feats, accel_fn=feats, deps=[t_rois],
                 cost=_task_cost("Features"))
        )
        return None

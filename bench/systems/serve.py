"""The slide-serving deployment: one slide staged in tiered storage whose DMS
tier is socket-server subprocesses, behind a ``RegionGateway`` that serves
ROI reads (``submit``) and near-data kernel chains (``submit_compute``).

Mix kind ``open_loop`` (``bench/traffic.py``): requests are sent at their due
times whatever the gateway's state, and each is timed from when it was due.
A request that fails, is refused or never answers counts as missing: its
latency is the time from its due time to the end of the drain.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from bench import reference, synth, traffic
from bench.harness import OUT

DRAIN_S = 60.0


def build(config: dict, mix: dict, seed: int, spans):
    return Serve(config, mix, seed, spans)


def make_slide(config: dict, seed: int) -> np.ndarray:
    """The slide to stage: the pool's tiles in an order drawn from the seed."""
    ty, tx = config["slide_tiles"]
    t = config["tile"]
    pool = synth.TilePool(seed, t, config["pool_base_tiles"], config["nuclei_per_512"],
                          tuple(config["nucleus_radius"]))
    order = np.random.default_rng([seed, 1]).permutation(len(pool))
    slide = np.empty((3, ty * t, tx * t), np.float32)
    for j in range(ty * tx):
        y, x = divmod(j, tx)
        slide[:, y * t : (y + 1) * t, x * t : (x + 1) * t] = pool.tile(int(order[j % len(order)]))
    return slide


def sample(mix: dict, requests: list[traffic.Request], seed: int) -> set[int]:
    """The indices of the requests the check compares: ``check`` of each
    class, drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    picked: set[int] = set()
    for k, cls in enumerate(mix["classes"]):
        idx = [r.index for r in requests if r.cls == k]
        picked |= {int(i) for i in rng.choice(idx, min(cls["check"], len(idx)), replace=False)}
    return picked


def _p95(values: np.ndarray) -> float:
    return float(np.percentile(values, 95)) if values.size else float("nan")


class Serve:
    def __init__(self, config: dict, mix: dict, seed: int, spans) -> None:
        from repro.core import BoundingBox, RegionTemplate, StorageRegistry
        from repro.pipeline import make_wsi_storage
        from repro.serve.gateway import GatewayConfig

        if mix["kind"] != "open_loop":
            raise ValueError(f"the serve system runs no {mix['kind']!r} mix")
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        ty, tx = config["slide_tiles"]
        t = config["tile"]
        self.extent = (ty * t, tx * t)
        with spans.span("bench.setup.tiles"):
            self.slide = make_slide(config, seed)
        st = config["storage"]
        OUT.mkdir(parents=True, exist_ok=True)
        self._root = tempfile.mkdtemp(prefix="tiers_", dir=OUT)
        self.registry = StorageRegistry()
        try:
            make_wsi_storage(
                *self.extent, mode=st["mode"], transport=st["transport"], tile=config["block"],
                root=self._root, registry=self.registry, num_servers=st["num_servers"],
                server_processes=st["server_processes"],
                mem_capacity_bytes=st["mem_capacity_bytes"],
                serve=GatewayConfig(compute_impl=config["compute_impl"]), compute=True,
            )
            self.gw = self.registry.get("DMS3")
            self.requests: list[traffic.Request] = []
            self.kept: dict[int, object] = {}
            self.lateness: dict = {}
            self._sampled: set[int] = set()
            dom3 = BoundingBox((0, 0, 0), (3,) + self.extent)
            self.key = RegionTemplate("Slide").new_region("RGB", dom3, np.float32).key
            with spans.span("bench.stage_in"):
                for j in range(ty * tx):
                    y, x = divmod(j, tx)
                    bb = BoundingBox((0, y * t, x * t), (3, (y + 1) * t, (x + 1) * t))
                    self.gw.put(self.key, bb, self.slide[bb.slices()])
            with spans.span("bench.setup.warmup"):
                for k, cls in enumerate(mix["classes"]):
                    far = (self.extent[0] - cls["edge"], self.extent[1] - cls["edge"])
                    self._submit(traffic.Request(-1, 0.0, k, far)).result(DRAIN_S)
        except BaseException:
            self.close()
            raise

    def _roi(self, req: traffic.Request):
        from repro.core import BoundingBox

        e = self.mix["classes"][req.cls]["edge"]
        y, x = req.origin
        return BoundingBox((0, y, x), (3, y + e, x + e))

    def _submit(self, req: traffic.Request):
        cls = self.mix["classes"][req.cls]
        if cls["op"] == "read":
            fut = self.gw.submit(self.key, self._roi(req))
        else:
            fut = self.gw.submit_compute(self.key, self._roi(req), cls["chain"])
        if req.index in self._sampled:
            self.kept[req.index] = fut
        return fut

    def run(self, seconds: float, rate: float | None = None, seed: int | None = None) -> dict:
        """One window of the mix; ``rate`` and ``seed`` (of the requests, not
        the slide) override the mix's and the build's, for a sweep."""
        mix = dict(self.mix, rate=rate or self.mix["rate"])
        seed = self.seed if seed is None else seed
        reqs = traffic.schedule(mix, self.extent, seconds, seed)
        self._sampled = sample(mix, reqs, seed)
        self.kept = {}
        loop = traffic.OpenLoop(reqs, self._submit)
        loop.run(drain_s=DRAIN_S)
        end = time.perf_counter() - loop.t0
        self.requests, self.lateness = reqs, loop.lateness_ms()
        out = {"attempted": len(reqs),
               "failed": sum(1 for r in reqs if r.error is not None or not np.isfinite(r.done))}
        for op in ("read", "compute"):
            cls = {k for k, c in enumerate(mix["classes"]) if c["op"] == op}
            if not cls:
                continue
            mine = [r for r in reqs if r.cls in cls]
            lat = np.array([
                (r.done - r.due) * 1e3 if r.error is None and np.isfinite(r.done)
                else (end - r.due) * 1e3
                for r in mine
            ])
            out[f"{op}_p95_ms"] = _p95(lat)
            out[f"{op}_p50_ms"] = float(np.median(lat)) if lat.size else float("nan")
            ok = sum(1 for r in mine if r.error is None and r.done <= seconds)
            out[f"{op}_per_s"] = ok / seconds
        return out

    def counters(self) -> dict:
        stats = self.gw.storage_stats()
        gw = stats["gateway"]
        chains = gw.get("compute", {}).get("chains", {})
        mem = stats.get("tiers", {}).get("MEM", {})
        return {
            "requests": gw["requests"],
            "response_cache_hits": gw["response_cache_hits"],
            "compute_served": gw["compute_served"],
            "raw_fetch_bytes": gw["raw_fetch_bytes"],
            "chain_served": sum(c["served"] for c in chains.values()),
            "chain_compute_ms": sum(c["compute_ms"] for c in chains.values()),
            "ram_hits": mem.get("hits", 0),
            "ram_misses": mem.get("misses", 0),
        }

    def collect(self) -> None:
        """Nothing to gather: ``run`` drains every request it sent."""

    def notes(self) -> list[str]:
        late = ", ".join(f"{k} {v:.3f}" for k, v in self.lateness.items())
        return [f"{len(self.requests)} requests; generator lateness ms: {late}"]

    def close(self) -> None:
        if self.registry is None:
            return
        for name in self.registry.names():
            self.registry.get(name).close()
        group = getattr(self.registry, "server_group", None)
        if group is not None:
            group.close()
        self.registry = None
        shutil.rmtree(self._root, ignore_errors=True)

    def answers(self) -> list[tuple[traffic.Request, object]]:
        """(request, answer or the exception) of each sampled request."""
        out = []
        for r in self.requests:
            fut = self.kept.get(r.index)
            if fut is None:
                continue
            if not fut.done():
                out.append((r, TimeoutError("never answered")))
                continue
            err = fut.exception()
            out.append((r, err if err is not None else fut.result()))
        return out

    def check(self) -> list[tuple[str, float, float]]:
        """Every sampled answer against the reference over the staged slide:
        reads exactly, a component count exactly, a stain density within
        its limit.  A refusal (the gateway shedding load) is no answer to
        judge; any other error, or no answer at all, counts as wrong."""
        from repro.serve.gateway import Overloaded

        limits = self.config["limits"]
        worst: dict[str, float] = {}
        wrong = 0
        for r, ans in self.answers():
            cls = self.mix["classes"][r.cls]
            if isinstance(ans, Overloaded):
                continue
            if isinstance(ans, BaseException):
                wrong += 1
                continue
            y, x = r.origin
            e = cls["edge"]
            window = self.slide[:, y : y + e, x : x + e]
            if cls["op"] == "read":
                name, gap = "read_gap", _gap(ans, window)
            elif cls["chain"] == "deconv":
                name, gap = "deconv_gap", _gap(ans, reference.hematoxylin(window))
            else:
                want = reference.chain_count(window, self.config["chain_threshold"])
                worst["count_off"] = worst.get("count_off", 0) + int(
                    np.asarray(ans).ravel()[0] != want)
                continue
            worst[name] = max(worst.get(name, 0.0), gap)
        checks = [(k, v, limits[k]) for k, v in sorted(worst.items())]
        checks.append(("answers_wrong", wrong, 0))
        return checks


def _gap(got, want) -> float:
    """Largest absolute difference; a wrong shape reads as 1e30."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return 1e30
    return float(np.abs(got.astype(np.float64) - want).max())

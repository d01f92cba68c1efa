"""Jit'd public wrappers for every kernel, with implementation dispatch.

``impl`` selects:
  * ``"pallas"``   — the Pallas kernel.  Compiled on a TPU; off the TPU it
                     runs in Pallas *interpret mode* (the same kernel body,
                     emulated), which says nothing about the device;
  * ``"xla"``      — the pure-jnp reference (used for dry-run lowering and
                     as the oracle);
  * ``"auto"``     — pallas on TPU, xla elsewhere (the production default:
                     CPU hosts shouldn't pay interpret-mode overhead).

Neither ``"pallas"`` nor ``"auto"`` fails off the TPU, so a run that must
exercise the device asserts ``jax.default_backend() == "tpu"`` itself
(``chip_smoke.py`` does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ccl import ccl_pallas, ccl_pallas_counted, watershed_pallas
from repro.kernels.color_deconv import color_deconv_pallas
from repro.kernels.distance import distance_transform_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.glcm import glcm_pallas
from repro.kernels.morph_recon import morph_recon_pallas, morph_recon_pallas_counted
from repro.kernels.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def _interpret() -> bool:
    return not _on_tpu()


# -- color deconvolution ------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("impl",))
def color_deconv(rgb: jax.Array, minv: jax.Array, impl: str = "auto") -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return color_deconv_pallas(rgb, minv, interpret=_interpret())
    return ref.color_deconv_ref(rgb, minv)


# -- morphological reconstruction ----------------------------------------------
@functools.partial(jax.jit, static_argnames=("impl", "max_iters"))
def morph_recon(
    marker: jax.Array, mask: jax.Array, impl: str = "auto", max_iters: int = 128
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return morph_recon_pallas(marker, mask, max_iters=max_iters, interpret=_interpret())
    return ref.morph_recon_ref(marker, mask, max_iters=max_iters)


@functools.partial(jax.jit, static_argnames=("impl",))
def fill_holes(mask01: jax.Array, impl: str = "auto") -> jax.Array:
    marker, inv = ref.fill_holes_seed(mask01)
    # the reference's iteration bound, so impl="xla" equals fill_holes_ref
    return 1.0 - morph_recon(marker, inv, impl=impl, max_iters=256)


# -- connected components ----------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("impl", "max_iters"))
def connected_components(
    mask: jax.Array, impl: str = "auto", max_iters: int = 128
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return ccl_pallas(mask, max_iters=max_iters, interpret=_interpret())
    return ref.ccl_ref(mask, max_iters=max_iters)


# -- nucleus split (ref.py's "Nucleus split" has the equations) -------------------------
# Each fixed-point loop reports (kernel calls, converged); a loop stopped by
# max_iters has not reached its fixed point.
def _recon_counted(impl: str, max_iters: int):
    if impl == "pallas":
        return functools.partial(
            morph_recon_pallas_counted, max_iters=max_iters, interpret=_interpret()
        )
    return functools.partial(ref.morph_recon_ref_counted, max_iters=max_iters)


@functools.partial(jax.jit, static_argnames=("impl",))
def distance_transform(mask: jax.Array, impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """Step 1: (H, W) mask -> (int32 squared distances, window calls)."""
    if _resolve(impl) == "pallas":
        return distance_transform_pallas(mask, interpret=_interpret())
    return ref.distance_transform_ref(mask)


@functools.partial(jax.jit, static_argnames=("h", "impl", "max_iters"))
def hmax_markers(
    d2: jax.Array, h: int, impl: str = "auto", max_iters: int = 128
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step 2: int32 squared distances -> (marker labels, -1 off the
    markers; calls and converged of the loops g, R(g - 1, g) and the CCL)."""
    impl = _resolve(impl)
    recon = _recon_counted(impl, max_iters)
    on = d2 > 0
    d = d2.astype(jnp.float32)
    # g + h = R(d2, d2 + h) on the mask, 0 off it
    g, c1, ok1 = recon(jnp.where(on, d, 0.0), jnp.where(on, d + h, 0.0))
    below, c2, ok2 = recon(g - 1.0, g)
    markers = (on & (g - below >= 1.0)).astype(jnp.int32)
    if impl == "pallas":
        labels, c3, ok3 = ccl_pallas_counted(markers, max_iters=max_iters,
                                             interpret=_interpret())
    else:
        labels, c3, ok3 = ref.ccl_ref_counted(markers, max_iters=max_iters)
    return labels, jnp.stack([c1, c2, c3]), jnp.stack([ok1, ok2, ok3])


@functools.partial(jax.jit, static_argnames=("impl", "max_iters"))
def watershed(
    d2: jax.Array, markers: jax.Array, impl: str = "auto", max_iters: int = 128
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Steps 3-4: squared distances and marker labels -> (basin labels, each
    basin's smallest raster index, -1 off the mask and int32 max where no
    marker reached; calls and converged of the cost reconstruction, the
    label propagation and the relabelling)."""
    impl = _resolve(impl)
    big = jnp.iinfo(jnp.int32).max
    if impl == "pallas":
        propagate = functools.partial(watershed_pallas, max_iters=max_iters,
                                      interpret=_interpret())
    else:
        propagate = functools.partial(ref.watershed_ref, max_iters=max_iters)
    d = d2.astype(jnp.float32)
    cost, c1, ok1 = _recon_counted(impl, max_iters)(jnp.where(markers >= 0, d, 0.0), d)
    basins, c2, ok2 = propagate(jnp.where(markers >= 0, markers, big),
                                ref.watershed_edges(d2, cost))
    # basins are connected, so the smallest raster index spreads over each
    reached = (d2 > 0) & (basins != big)
    index = jnp.arange(d2.size, dtype=jnp.int32).reshape(d2.shape)
    named, c3, ok3 = propagate(jnp.where(reached, index, big),
                               ref.same_label_edges(basins, reached))
    return (jnp.where(d2 > 0, named, -1), jnp.stack([c1, c2, c3]),
            jnp.stack([ok1, ok2, ok3]))


# -- GLCM / histogram features -------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_bins", "impl"))
def glcm_histogram(
    bins: jax.Array, num_bins: int, impl: str = "auto"
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "pallas":
        return glcm_pallas(bins, num_bins, interpret=_interpret())
    return ref.glcm_ref(bins, num_bins), ref.histogram_ref(bins, num_bins)


@functools.partial(jax.jit, static_argnames=("num_bins", "impl"))
def texture_features(bins: jax.Array, num_bins: int, impl: str = "auto") -> jax.Array:
    """(B, H, W) int bins -> (B, 9) [5 GLCM + 4 histogram] features."""
    g, h = glcm_histogram(bins, num_bins, impl=impl)
    return jnp.concatenate(
        [ref.glcm_features_ref(g), ref.histogram_features_ref(h)], axis=-1
    )


# -- attention -----------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("causal", "window", "impl", "q_offset", "block_q", "block_k")
)
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return flash_attention_pallas(
            q,
            k,
            v,
            causal=causal,
            window=window,
            q_offset=q_offset,
            block_q=block_q,
            block_k=block_k,
            interpret=_interpret(),
        )
    if impl == "chunked":
        return ref.attention_chunked_ref(
            q, k, v, causal=causal, window=window, q_offset=q_offset, chunk=block_k * 4
        )
    return ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


# -- mamba2 SSD ---------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_: jax.Array,
    c_: jax.Array,
    d_: jax.Array | None = None,
    *,
    impl: str = "auto",
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "pallas":
        return ssd_scan_pallas(x, dt, a, b_, c_, d_, chunk=chunk, interpret=_interpret())
    if impl == "chunked":
        return ref.ssd_scan_chunked_ref(x, dt, a, b_, c_, d_, chunk=chunk)
    return ref.ssd_scan_ref(x, dt, a, b_, c_, d_)

"""Pallas kernel: GLCM (gray-level co-occurrence) + histogram accumulation.

The paper's feature-computation stage (S5.1) computes per-nucleus
histograms and co-occurrence matrices with one GPU thread-block per
nucleus bounding box.  TPU adaptation: the scatter-add accumulation is
recast as a *one-hot matmul* — for each tile, GLCM = OneHot(left) @
OneHot(right)^T — which runs on the MXU with fully regular access.  The
grid runs one program per object tile (objects padded into fixed-size ROI
batches by the pipeline, replacing dynamic GPU block assignment).

Layout: the XLA wrapper flattens each tile and forms the horizontal
neighbour pairs, so the kernel sees pixels on lanes — three rows (all
pixels, left of each pair, right of each pair), padded to a multiple of
128 lanes with bin -1, which matches no bin.  The one-hots are then
(NB, P) with bins on sublanes, and the kernel needs no lane-to-sublane
reshape, which Mosaic does not lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _kernel(pix_ref, glcm_ref, hist_ref, *, num_bins: int):
    pix = pix_ref[0]  # (3, P) int32: all pixels, left, right; -1 = padding
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_bins, pix.shape[1]), 0)

    def one_hot(row):  # (NB, P); 0/1 is exact in bf16
        return (pix[row : row + 1, :] == iota).astype(jnp.float32).astype(jnp.bfloat16)

    hist_ref[0] = one_hot(0).astype(jnp.float32).sum(axis=1, keepdims=True)
    # MXU contraction over pixels: (NB, P) @ (P, NB)
    glcm_ref[0] = jax.lax.dot_general(
        one_hot(1),
        one_hot(2),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def glcm_pallas(
    bins: jax.Array,
    num_bins: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(B, H, W) int32 bins -> (glcm (B, NB, NB), hist (B, NB)) float32.

    One grid program per object tile; whole tile in VMEM (object ROIs are
    small — nuclei are ~64x64 after padding).
    """
    b, h, w = bins.shape
    bins = bins.astype(jnp.int32)
    p = pl.cdiv(h * w, _LANES) * _LANES
    rows = (
        bins.reshape(b, h * w),
        bins[:, :, :-1].reshape(b, h * (w - 1)),
        bins[:, :, 1:].reshape(b, h * (w - 1)),
    )
    pix = jnp.stack(
        [jnp.pad(r, ((0, 0), (0, p - r.shape[1])), constant_values=-1) for r in rows],
        axis=1,
    )  # (B, 3, P)
    glcm, hist = pl.pallas_call(
        functools.partial(_kernel, num_bins=num_bins),
        out_shape=(
            jax.ShapeDtypeStruct((b, num_bins, num_bins), jnp.float32),
            jax.ShapeDtypeStruct((b, num_bins, 1), jnp.float32),
        ),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 3, p), lambda i: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, num_bins, num_bins), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, num_bins, 1), lambda i: (i, 0, 0)),
        ),
        interpret=interpret,
    )(pix)
    return glcm, hist[..., 0]

"""Pallas kernel: grayscale morphological reconstruction by dilation.

TPU adaptation of the paper's IWPP (irregular wavefront propagation, [65]):
GPU wavefronts use per-thread work queues — no TPU analogue.  We observe
that the 1-D reconstruction recurrence

    m_j = min(mask_j, max(marker_j, m_{j-1}))

is a composition of clamp functions f(x) = min(c, max(d, x)) which compose
in closed form, so each directional sweep is a *log-depth associative
scan* along sublanes/lanes — fully regular, VPU-friendly (the roll-based
form of ``kernels/scan.py``, which Mosaic lowers).  One kernel call
performs ``n_sweeps`` 4-direction sweeps over its VMEM tile; the ops
wrapper iterates kernel calls to the global fixed point (block-synchronous
relaxation).  Connectivity: 4-neighbor, matching ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fixed_point import fixed_point
from repro.kernels.scan import roll_scan


def _combine(a, b):
    c1, d1 = a
    c2, d2 = b
    return jnp.minimum(c2, jnp.maximum(d2, c1)), jnp.maximum(d1, d2)


def _scan_dir(j, mask, axis, reverse):
    c, d = roll_scan(_combine, (mask, j), axis, reverse, roll=pltpu.roll)
    return jnp.minimum(c, d)


def _kernel(marker_ref, mask_ref, out_ref, *, n_sweeps: int):
    mask = mask_ref[...]
    j = jnp.minimum(marker_ref[...], mask)

    def sweep(_, j):
        j = _scan_dir(j, mask, axis=0, reverse=False)
        j = _scan_dir(j, mask, axis=0, reverse=True)
        j = _scan_dir(j, mask, axis=1, reverse=False)
        j = _scan_dir(j, mask, axis=1, reverse=True)
        return j

    out_ref[...] = jax.lax.fori_loop(0, n_sweeps, sweep, j)


def morph_recon_sweep_pallas(
    marker: jax.Array,
    mask: jax.Array,
    *,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One block-relaxation step: n_sweeps 4-dir sweeps per VMEM tile.

    Tiles are processed independently (no halo): the caller's outer
    fixed-point loop propagates information across tile boundaries, since
    every call re-reads the neighbors' updated values.  For a (H, W) image
    the grid is over spatial tiles.
    """
    h, w = marker.shape
    bh, bw = min(block_h, h), min(block_w, w)
    # pad to block multiples (OOB grid padding is undefined in pallas)
    hp, wp = pl.cdiv(h, bh) * bh, pl.cdiv(w, bw) * bw
    marker_p = jnp.pad(marker.astype(jnp.float32), ((0, hp - h), (0, wp - w)))
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, hp - h), (0, wp - w)))
    grid = (hp // bh, wp // bw)
    out = pl.pallas_call(
        functools.partial(_kernel, n_sweeps=n_sweeps),
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        interpret=interpret,
    )(marker_p, mask_p)
    return out[:h, :w]


def morph_recon_pallas(
    marker: jax.Array,
    mask: jax.Array,
    *,
    max_iters: int = 64,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Fixed-point iteration of tile sweeps + cross-tile halo exchange."""
    return morph_recon_pallas_counted(
        marker, mask, max_iters=max_iters, n_sweeps=n_sweeps, block_h=block_h,
        block_w=block_w, interpret=interpret,
    )[0]


def morph_recon_pallas_counted(
    marker: jax.Array,
    mask: jax.Array,
    *,
    max_iters: int = 64,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``morph_recon_pallas``, with its kernel calls and whether it reached
    its fixed point within ``max_iters`` (``fixed_point.fixed_point``).

    Between kernel calls, a 1-pixel neighborhood max is exchanged across
    the whole array (cheap XLA shifts) so wavefronts cross tile borders;
    the kernel then relaxes interiors at VMEM speed.
    """
    mask_f = mask.astype(jnp.float32)
    j0 = jnp.minimum(marker.astype(jnp.float32), mask_f)
    sweep = functools.partial(
        morph_recon_sweep_pallas,
        n_sweeps=n_sweeps,
        block_h=block_h,
        block_w=block_w,
        interpret=interpret,
    )

    def halo(j):
        # cross-border propagation: 4-neighbor dilation clamped by mask
        up = jnp.pad(j[1:, :], ((0, 1), (0, 0)), constant_values=-jnp.inf)
        dn = jnp.pad(j[:-1, :], ((1, 0), (0, 0)), constant_values=-jnp.inf)
        lf = jnp.pad(j[:, 1:], ((0, 0), (0, 1)), constant_values=-jnp.inf)
        rt = jnp.pad(j[:, :-1], ((0, 0), (1, 0)), constant_values=-jnp.inf)
        neigh = jnp.maximum(jnp.maximum(up, dn), jnp.maximum(lf, rt))
        return jnp.minimum(mask_f, jnp.maximum(j, neigh))

    return fixed_point(lambda j: sweep(halo(j), mask_f), j0, sweep(j0, mask_f), max_iters)

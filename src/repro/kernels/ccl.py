"""Pallas kernel: connected component labeling by min-label propagation.

TPU adaptation of the paper's union-find BWLabel ([50]): pointer-chasing
union-find is hostile to the VPU, so the device path instead iterates
min-label propagation within mask runs.  The 1-D recurrence

    m_j = min(v_j, m_{j-1} if pass_j else +inf)

composes closed-form ((v', p') = (min(v2, v1 if p2 else inf), p1 & p2)),
giving log-depth scans per direction (``kernels/scan.py``).  The fixed point labels
every component by its minimum flat index — identical canonical labels to
union-find, verified in tests.

The watershed (``watershed_pallas``) is the same propagation over directed
edges: each pixel says, one bit per direction, from which of its four
neighbours a label may enter it, so each directional scan gets a pass flag
of its own where connected components use the mask for all four.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fixed_point import fixed_point
from repro.kernels.scan import roll_scan

_BIG = jnp.iinfo(jnp.int32).max
# the directional scans, in sweep order, by the way a label moves: down
# (from the pixel above), up, right (from the left), left; edge bit k of
# the watershed's pass plane belongs to scan k
_SCANS = ((0, False), (0, True), (1, False), (1, True))


def _combine(a, b):
    # the pass flag p is a 0/1 int32: the scan rolls it, and Mosaic may
    # not lower a roll of bool vectors
    v1, p1 = a
    v2, p2 = b
    v = jnp.minimum(v2, jnp.where(p2 != 0, v1, _BIG))
    return v, p1 & p2


def _scan_dir(labels, mask, axis, reverse):
    v, _ = roll_scan(_combine, (labels, mask), axis, reverse, roll=pltpu.roll)
    return jnp.where(mask != 0, jnp.minimum(labels, v), labels)


def _kernel(labels_ref, mask_ref, out_ref, *, n_sweeps: int, directed: bool):
    mask = mask_ref[...]  # int32: 0/1, or one edge bit per scan if directed
    labels = labels_ref[...]
    flags = [(mask >> k) & 1 for k in range(4)] if directed else [mask] * 4

    def sweep(_, l):
        for (axis, reverse), flag in zip(_SCANS, flags):
            l = _scan_dir(l, flag, axis=axis, reverse=reverse)
        return l

    out_ref[...] = jax.lax.fori_loop(0, n_sweeps, sweep, labels)


def ccl_sweep_pallas(
    labels: jax.Array,
    mask: jax.Array,
    *,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    directed: bool = False,
    name: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One block-relaxation step over (block_h, block_w) tiles; ``mask`` is
    the pass plane: 0/1 for every scan, or with ``directed`` one edge bit
    per scan (``_SCANS``)."""
    h, w = labels.shape
    bh, bw = min(block_h, h), min(block_w, w)
    # pad to block multiples: OOB grid padding is undefined, and garbage
    # mask bits would leak labels across runs
    hp, wp = pl.cdiv(h, bh) * bh, pl.cdiv(w, bw) * bw
    labels_p = jnp.pad(labels, ((0, hp - h), (0, wp - w)), constant_values=_BIG)
    mask_p = jnp.pad(mask.astype(jnp.int32), ((0, hp - h), (0, wp - w)))
    grid = (hp // bh, wp // bw)
    out = pl.pallas_call(
        functools.partial(_kernel, n_sweeps=n_sweeps, directed=directed),
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        interpret=interpret,
        name=name,
    )(labels_p, mask_p)
    return out[:h, :w]


def ccl_pallas(
    mask: jax.Array,
    *,
    max_iters: int = 64,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Labels: min flat index per 4-connected component; background -1."""
    return ccl_pallas_counted(
        mask, max_iters=max_iters, n_sweeps=n_sweeps, block_h=block_h, block_w=block_w,
        interpret=interpret,
    )[0]


def ccl_pallas_counted(
    mask: jax.Array,
    *,
    max_iters: int = 64,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``ccl_pallas``, with its kernel calls and whether it reached its fixed
    point within ``max_iters`` (``fixed_point.fixed_point``)."""
    mask_b = mask != 0
    h, w = mask.shape
    init = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    labels = jnp.where(mask_b, init, _BIG)
    sweep = functools.partial(
        ccl_sweep_pallas,
        n_sweeps=n_sweeps,
        block_h=block_h,
        block_w=block_w,
        interpret=interpret,
    )
    mask_i = mask_b.astype(jnp.int32)

    def halo(l):
        big = jnp.asarray(_BIG, jnp.int32)
        up = jnp.pad(l[1:, :], ((0, 1), (0, 0)), constant_values=big)
        dn = jnp.pad(l[:-1, :], ((1, 0), (0, 0)), constant_values=big)
        lf = jnp.pad(l[:, 1:], ((0, 0), (0, 1)), constant_values=big)
        rt = jnp.pad(l[:, :-1], ((0, 0), (1, 0)), constant_values=big)
        # neighbor labels only propagate into masked pixels from masked pixels
        mup = jnp.pad(mask_b[1:, :], ((0, 1), (0, 0)), constant_values=False)
        mdn = jnp.pad(mask_b[:-1, :], ((1, 0), (0, 0)), constant_values=False)
        mlf = jnp.pad(mask_b[:, 1:], ((0, 0), (0, 1)), constant_values=False)
        mrt = jnp.pad(mask_b[:, :-1], ((0, 0), (1, 0)), constant_values=False)
        neigh = jnp.minimum(
            jnp.minimum(jnp.where(mup, up, big), jnp.where(mdn, dn, big)),
            jnp.minimum(jnp.where(mlf, lf, big), jnp.where(mrt, rt, big)),
        )
        return jnp.where(mask_b, jnp.minimum(l, neigh), l)

    l, calls, converged = fixed_point(
        lambda l: sweep(halo(l), mask_i), labels, sweep(labels, mask_i), max_iters
    )
    return jnp.where(mask_b, l, -1), calls, converged


def _from_neighbours(l: jax.Array, edges: jax.Array) -> jax.Array:
    """Each pixel's smallest label among the neighbours its edge bits let
    in (``_SCANS`` order: from above, below, the left, the right)."""
    big = jnp.asarray(_BIG, jnp.int32)
    came = (
        jnp.pad(l[:-1, :], ((1, 0), (0, 0)), constant_values=big),
        jnp.pad(l[1:, :], ((0, 1), (0, 0)), constant_values=big),
        jnp.pad(l[:, :-1], ((0, 0), (1, 0)), constant_values=big),
        jnp.pad(l[:, 1:], ((0, 0), (0, 1)), constant_values=big),
    )
    out = big
    for k, v in enumerate(came):
        out = jnp.minimum(out, jnp.where((edges >> k) & 1 != 0, v, big))
    return out


def watershed_pallas(
    labels: jax.Array,
    edges: jax.Array,
    *,
    max_iters: int = 128,
    n_sweeps: int = 2,
    block_h: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Least fixed point of ``l(p) = min(labels(p), l(q) for each edge q -> p)``.

    ``labels`` holds each marker pixel's label and ``_BIG`` elsewhere;
    ``edges`` holds, per pixel, one bit per neighbour a label may come from
    (``_SCANS`` order).  Kernel sweeps relax each block; between calls one
    XLA step carries labels across block borders.  Returns (labels, kernel
    calls, converged), ``_BIG`` where no marker reaches.
    """
    sweep = functools.partial(
        ccl_sweep_pallas,
        n_sweeps=n_sweeps,
        block_h=block_h,
        block_w=block_w,
        directed=True,
        name="watershed",
        interpret=interpret,
    )

    def halo(l):
        return jnp.minimum(l, _from_neighbours(l, edges))

    return fixed_point(lambda l: sweep(halo(l), edges), labels, sweep(labels, edges), max_iters)

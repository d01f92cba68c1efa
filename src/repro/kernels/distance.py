"""Pallas kernels: exact squared Euclidean distance transform (EDT).

``d2(p)`` is the squared distance from a foreground pixel to the nearest
background pixel of the image, an int32; 0 on background.  Pixels outside
the image are not background (``scipy.ndimage.distance_transform_edt``'s
convention), and an image with no background pixel takes SciPy's value,
the squared distance to the pixel (-1, 0).  No square root is taken.

The transform is separable.  Pass 1 runs along rows: each pixel's distance
to the nearest background pixel of its row, from a max-scan of background
column indices from the left and a min-scan from the right (log-depth
roll scans, ``kernels/scan.py``).  Pass 2 runs along columns on the
transposed plane: ``d2(i, j) = min_i' a(i', j)**2 + (i - i')**2``.  Taken
over offsets ``|i - i'| <= T`` only, that minimum is exact wherever it is at
most ``(T + 1)**2``, since every farther candidate costs more; so the
kernel widens the window ``_CHUNK`` offsets per call until every pixel's
minimum is within its bound (in a tile of nuclei, a few calls).  Both
passes run on blocks of whole rows, so no block needs a halo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan import roll_scan

# a distance past any image side: no background along the row; its square
# plus any offset's square still fits in int32
FAR = 1 << 14
_CHUNK = 8  # window offsets per kernel call of pass 2
_LANE = 128


def _max(a, b):
    return (jnp.maximum(a[0], b[0]),)


def _min(a, b):
    return (jnp.minimum(a[0], b[0]),)


def _row_distance_kernel(fg_ref, out_ref):
    fg = fg_ref[...]
    n = fg.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, fg.shape, 1)
    bg = fg == 0
    (last,) = roll_scan(_max, (jnp.where(bg, col, -FAR),), 1, roll=pltpu.roll)
    (nxt,) = roll_scan(_min, (jnp.where(bg, col, n + FAR),), 1, reverse=True, roll=pltpu.roll)
    out_ref[...] = jnp.minimum(jnp.minimum(col - last, nxt - col), FAR)


def _shift(x, k, col):
    """``x`` moved ``k`` lanes right (``k < 0``: left), ``FAR`` shifted in."""
    n = x.shape[1]
    rolled = pltpu.roll(x, k % n, 1)
    return jnp.where(col >= k if k > 0 else col < n + k, rolled, FAR)


def _window_kernel(t0_ref, left_ref, right_ref, best_ref, out_ref):
    """Offsets ``t0 + 1 .. t0 + _CHUNK`` on both sides; ``left``/``right``
    hold the row distances already moved ``t0`` lanes right/left."""
    left, right = left_ref[...], right_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, left.shape, 1)
    best = best_ref[...]
    t0 = t0_ref[0]
    for k in range(1, _CHUNK + 1):
        t2 = (t0 + k) * (t0 + k)
        a = _shift(left, k, col)
        b = _shift(right, -k, col)
        best = jnp.minimum(best, jnp.minimum(a * a, b * b) + t2)
    out_ref[...] = best


def _rows_spec(bh: int, n: int) -> pl.BlockSpec:
    return pl.BlockSpec((bh, n), lambda i: (i, 0))


def _row_call(kernel, n_in: int, rows: int, n: int, bh: int, name: str, interpret: bool,
              smem: int = 0):
    specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * smem + [_rows_spec(bh, n)] * n_in
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.int32),
        grid=(rows // bh,),
        in_specs=specs,
        out_specs=_rows_spec(bh, n),
        interpret=interpret,
        name=name,
    )


def _pad_to(x: jax.Array, rows: int, cols: int, value: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])), constant_values=value)


def _shift_xla(x: jax.Array, t: int) -> jax.Array:
    """``x`` moved ``t`` lanes right (``t < 0``: left), ``FAR`` shifted in."""
    if t > 0:
        return jnp.pad(x[:, :-t], ((0, 0), (t, 0)), constant_values=FAR)
    return jnp.pad(x[:, -t:], ((0, 0), (0, -t)), constant_values=FAR)


def distance_transform_pallas(
    mask: jax.Array, *, block_rows: int = 16, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """(H, W) foreground mask (nonzero) -> (int32 squared distances, pass-2
    kernel calls)."""
    h, w = mask.shape
    fg = (mask != 0).astype(jnp.int32)
    bh = block_rows

    def rup(x, m):
        return -(-x // m) * m

    # pass 1 along rows; padded columns are foreground, so never a nearest
    # background pixel
    hp, wp = rup(h, bh), rup(w, _LANE)
    a = _row_call(_row_distance_kernel, 1, hp, wp, bh, "distance_transform_rows",
                  interpret)(_pad_to(fg, hp, wp, 1))
    # pass 2 along the columns, as rows of the transpose; padded positions
    # are FAR, so never a candidate, and not waited for
    at = _pad_to(a[:h, :w].T, rup(w, bh), rup(h, _LANE), FAR)
    rows, n = at.shape
    window = _row_call(_window_kernel, 3, rows, n, bh, "distance_transform", interpret, smem=1)

    def cond(state):
        _, _, best, t0 = state
        return jnp.logical_and(t0 < h - 1, jnp.any(best[:w, :h] > (t0 + 1) * (t0 + 1)))

    def body(state):
        left, right, best, t0 = state
        best = window(t0.reshape(1), left, right, best)
        return _shift_xla(left, _CHUNK), _shift_xla(right, -_CHUNK), best, t0 + _CHUNK

    has_bg = jnp.any(fg == 0)
    start = (at, at, at * at, jnp.where(has_bg, jnp.int32(0), jnp.int32(h)))
    _, _, best, t0 = jax.lax.while_loop(cond, body, start)
    d2 = jnp.where(has_bg, best[:w, :h].T, no_background(h, w))
    return d2, jnp.where(has_bg, t0 // _CHUNK, 0)


def no_background(h: int, w: int) -> jax.Array:
    """SciPy's answer for an image with no background pixel: the squared
    distance to the pixel (-1, 0)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    return (i + 1) * (i + 1) + j * j

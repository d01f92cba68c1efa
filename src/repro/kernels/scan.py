"""Log-step inclusive scan built from rolls: lowers inside a TPU kernel.

``jax.lax.associative_scan`` lowers to strided slices and concatenations:
Mosaic rejects their vector shapes inside a TPU kernel, and at 4096-wide
images the TPU's XLA compiler takes minutes over a 4-direction sweep of
them.  This scan is the Hillis–Steele doubling form instead: step ``k``
rolls every element by ``s = 2**k`` along ``axis`` and combines it into
the positions that have a predecessor ``s`` away, so an axis of length
``n`` takes ``ceil(log2(n))`` roll + combine steps, all elementwise.

``roll`` follows ``jnp.roll``: ``roll(x, s, axis)[i] == x[i - s]`` (mod
n).  The XLA references use ``jnp.roll``; the Pallas kernels pass
``pltpu.roll`` (a lane/sublane rotation), whose direction on hardware
only a chip run checks — interpret mode runs ``jnp.roll`` itself.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def roll_scan(
    combine: Callable[[tuple, tuple], tuple],
    elems: tuple[jax.Array, ...],
    axis: int,
    reverse: bool = False,
    roll: Callable[[jax.Array, int, int], jax.Array] = jnp.roll,
) -> tuple[jax.Array, ...]:
    """Inclusive scan of ``elems`` (same-shape arrays) along ``axis``.

    Same contract as ``associative_scan(combine, elems, axis=axis,
    reverse=reverse)``: ``combine(a, b)`` takes the earlier partial result
    (in scan order) first.
    """
    axis = axis % elems[0].ndim
    n = elems[0].shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, elems[0].shape, axis)
    s = 1
    while s < n:
        # forward: position i takes i - s; reverse: i takes i + s, which a
        # roll by n - s brings to i
        shift, has_prev = (n - s, idx < n - s) if reverse else (s, idx >= s)
        prev = tuple(roll(e, shift, axis) for e in elems)
        merged = combine(prev, elems)
        elems = tuple(jnp.where(has_prev, m, e) for m, e in zip(merged, elems))
        s *= 2
    return elems

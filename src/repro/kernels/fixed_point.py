"""Iterate a step on the device until it changes nothing, and say how it ended.

The reconstruction, labelling and watershed loops all share this shape: a
first step, then ``body`` until the state stops changing or ``max_iters``
steps have run.  A loop that stops at ``max_iters`` has not reached its
fixed point, and its result may differ from the reference's; ``converged``
says which happened, and ``calls`` counts the steps (kernel calls on the
Pallas path), the first included.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def fixed_point(
    step: Callable[[jax.Array], jax.Array], x0: jax.Array, x1: jax.Array, max_iters: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``x1`` is the first step's result from ``x0``; ``step`` is applied to
    it until the state repeats or ``max_iters`` steps have run.

    Returns (state, calls, converged), the last two as device scalars.
    """

    def cond(state):
        x, prev, it = state
        return jnp.logical_and(jnp.any(x != prev), it < max_iters)

    def body(state):
        x, _, it = state
        return step(x), x, it + 1

    x, prev, it = jax.lax.while_loop(cond, body, (x1, x0, jnp.asarray(1)))
    return x, it, jnp.all(x == prev)

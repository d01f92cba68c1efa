"""Faults in the nucleus split, planted under the timed path of
``wsi_4k_ws`` to show that its check catches them (``bench/faults.py``'s
contract: ``mp`` is pytest's ``monkeypatch``, or ``faults.Patch``).  Each
one reports its loops as converged, so only the comparison can see it."""
from __future__ import annotations


def _seeds(d2, markers):
    """The watershed's starting labels: the markers', -1 off the mask and
    int32 max on it elsewhere."""
    import jax.numpy as jnp

    big = jnp.iinfo(jnp.int32).max
    return jnp.where(d2 > 0, jnp.where(markers >= 0, markers, big), -1)


def skipped_watershed(mp):
    """The watershed returns the marker labels unchanged."""
    from repro.kernels import ops

    inner = ops.watershed

    def skipped(d2, markers, impl="auto", max_iters=128):
        _, calls, ok = inner(d2, markers, impl=impl, max_iters=max_iters)
        return _seeds(d2, markers), calls, ok

    mp.setattr(ops, "watershed", skipped)


def watershed_one_sweep_short(mp):
    """The watershed's labels as they stood one step before its last change."""
    from repro.kernels import ops

    inner = ops.watershed

    def short(d2, markers, impl="auto", max_iters=128):
        basins, calls, ok = inner(d2, markers, impl=impl, max_iters=max_iters)
        n = int(calls[1])  # the last step changed nothing, the one before did
        if n > 2:
            basins = inner(d2, markers, impl=impl, max_iters=n - 2)[0]
        else:
            basins = _seeds(d2, markers)
        return basins, calls, ok

    mp.setattr(ops, "watershed", short)


FAULTS = {f.__name__: f for f in (skipped_watershed, watershed_one_sweep_short)}

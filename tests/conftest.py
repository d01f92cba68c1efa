import os
import sys

# Tests run on the single real CPU device (the 512-device override is
# dryrun-only, per the brief). Keep hypothesis deadlines off: CI boxes jit.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Make `from tests.<module> import ...` work regardless of rootdir layout.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hypothesis import settings  # noqa: E402

settings.register_profile("ci", deadline=None, max_examples=25, derandomize=True)
settings.load_profile("ci")

# The envdrift marker machinery that used to live here is gone: the jax
# API drifts it tracked (jax.sharding.AxisType, jax.shard_map) are fixed
# with version-tolerant accessors, so the whole suite runs unconditionally.

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _lock_witness(request):
    """Runtime lock-order witness (tools/relint/witness.py).

    Off by default; the CI net/chaos legs set REPRO_LOCK_WITNESS=1 so
    every test in those legs records real lock-acquisition orders and
    fails on an order-graph cycle or a blocking call under a held lock.
    Tests that install their own witness (the relint suite's deliberate
    cycles) opt out with @pytest.mark.no_lock_witness.
    """
    if not os.environ.get("REPRO_LOCK_WITNESS") or request.node.get_closest_marker(
        "no_lock_witness"
    ):
        yield
        return
    from tools.relint.witness import LockWitness

    witness = LockWitness()
    witness.install()
    try:
        yield
    finally:
        witness.uninstall()
    witness.check()

"""The WSI Pallas kernels compile for a TPU v5e at the paper's full size.

Interpret mode (tests/test_kernels.py) runs the kernel bodies on the CPU
but never asks Mosaic to lower them; these tests compile each kernel with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, so a
layout the TPU compiler refuses fails here, with no chip attached.

The topology is described inside a module fixture: only one process may
load the TPU library at a time, so nothing here touches it while the
module is imported.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ccl import ccl_pallas
from repro.kernels.color_deconv import color_deconv_pallas
from repro.kernels.glcm import glcm_pallas
from repro.kernels.morph_recon import morph_recon_pallas

TILE = 4096  # WSIConfig.tile
ROIS, ROI, BINS = 512, 64, 32  # max_objects_per_tile, nucleus_roi, num_bins


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "kernel",
    [
        pytest.param(
            (color_deconv_pallas, [((3, TILE, TILE), jnp.float32), ((3, 3), jnp.float32)]),
            id="color_deconv",
        ),
        pytest.param(
            (morph_recon_pallas, [((TILE, TILE), jnp.float32)] * 2), id="morph_recon"
        ),
        pytest.param((ccl_pallas, [((TILE, TILE), jnp.int32)]), id="ccl"),
        pytest.param(
            (lambda b: glcm_pallas(b, BINS), [((ROIS, ROI, ROI), jnp.int32)]), id="glcm"
        ),
    ],
)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = kernel
    args = [_spec(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dms_server_import_chain_loads_no_jax():
    """DMS servers are ``python -m repro.storage.net`` subprocesses: if
    that import chain pulled JAX in, each server would claim the chip
    that the pipeline process holds."""
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run(
        [sys.executable, "-X", "importtime", "-m", "repro.storage.net", "--help"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr
    imported = {
        line.split("|")[-1].strip()
        for line in out.stderr.splitlines()
        if line.startswith("import time:")
    }
    assert "repro.storage.net" in imported or "repro.storage" in imported
    assert not [m for m in imported if m == "jax" or m.startswith("jax.")]

"""Gradient-compression collectives. Multi-device psum semantics need >1
device, so the core check runs in a subprocess with a forced 8-device host
platform; the quantization math is also validated in-process."""
import subprocess
import sys

import numpy as np

from repro.train.compression import compression_ratio

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.train.compression import compressed_psum
from jax import shard_map

mesh = jax.make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

for mode, tol in [("fp32", 1e-6), ("bf16", 2e-2), ("int8", 3e-2)]:
    f = jax.jit(
        shard_map(
            lambda v: compressed_psum(v, "pod", mode),
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
        )
    )
    out = np.asarray(f(x))
    want = np.asarray(x).reshape(2, 4, 16)
    want = want.sum(axis=0, keepdims=True).repeat(2, 0).reshape(8, 16)
    err = np.abs(out - want).max() / (np.abs(want).max() + 1e-9)
    assert err < tol, (mode, err)
    print(f"{mode} ok rel_err={err:.2e}")
print("SUBPROC_OK")
"""


def test_compressed_psum_multi_device_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=300, cwd=".",
    )
    assert "SUBPROC_OK" in res.stdout, res.stdout + res.stderr


def test_int8_quantization_roundtrip_error_bound():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(1000).astype(np.float32)
    scale = np.abs(x).max() / 127.0
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    err = np.abs(q.astype(np.float32) * scale - x).max()
    assert err <= scale / 2 + 1e-7


def test_compression_ratios():
    assert compression_ratio("fp32") == 1.0
    assert compression_ratio("bf16") == 2.0
    assert compression_ratio("int8") == 4.0

"""int8 compressed gradient all-reduce: distributed correctness."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core.gluon import device_mesh
from repro.optim.grad_compress import compressed_psum

mesh = device_mesh(4)
rng = np.random.default_rng(0)
local = jnp.asarray(rng.normal(size=(4, 64, 32)).astype(np.float32))

def f(g):
    return compressed_psum({"w": g[0]}, "dev")["w"]

out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("dev"),),
                            out_specs=P(), check_vma=False))(local)
want = np.asarray(local).sum(axis=0)
got = np.asarray(out)
# error bounded by #participants * quantum
err = np.abs(got - want)
scale = np.abs(np.asarray(local)).max() / 127.0
assert err.max() <= 4 * scale + 1e-5, (err.max(), scale)
print("COMPRESS_OK", err.max())
"""


@pytest.mark.slow
def test_compressed_psum_multi_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMPRESS_OK" in out.stdout

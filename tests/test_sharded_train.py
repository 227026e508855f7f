"""Sharded execution correctness: the SAME sharding rules the dry-run
uses, executed for real on a small forced-device-count mesh, must match
single-device training bit-for-bit-ish.

Runs in a subprocess so device count never leaks.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.train.steps import make_train_step, init_train_state
from repro.optim import OptConfig
from repro.launch import sharding as SH

assert len(jax.devices()) == 4
for arch in ["llama3-8b", "deepseek-moe-16b", "mamba2-2.7b"]:
    cfg = get_smoke_config(arch)
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}

    ref_step = jax.jit(make_train_step(cfg, OptConfig()))
    rp, ro, rm = ref_step(params, opt, batch)

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pspec = SH.param_specs(jax.eval_shape(lambda: params))
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    psh = named(pspec)
    osh = named(SH.opt_specs(pspec))
    bsh = named(SH.batch_specs(False, cfg.num_codebooks))
    shard_fn = SH.make_shard_fn(mesh, False)
    with mesh:
        sharded_step = jax.jit(
            make_train_step(cfg, OptConfig(), shard_fn),
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, named({"loss": P(), "ce": P(),
                                            "grad_norm": P()})))
        sp, so, sm = sharded_step(params, opt, batch)
    assert np.allclose(float(rm["loss"]), float(sm["loss"]),
                       rtol=2e-3, atol=2e-3), (
        arch, float(rm["loss"]), float(sm["loss"]))
    # spot-check a parameter leaf after the update
    rl = jax.tree.leaves(rp)[0]
    sl = jax.tree.leaves(sp)[0]
    assert np.allclose(np.asarray(rl), np.asarray(sl), rtol=1e-2,
                       atol=1e-3), arch
    print(arch, "sharded==single ok", float(rm["loss"]))
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout

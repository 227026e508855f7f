"""The paper's technique inside the LM stack: ALB-adaptive MoE dispatch.

Skewed inputs make the router send nearly all tokens to two experts
(the power-law situation).  The static (blocked) dispatch drops the
overflow; the ALB executor re-deals overflow slots cyclically across
the free capacity of ALL experts via the same prefix-sum + searchsorted
renumbering the graph LB kernel uses.

  PYTHONPATH=src python examples/moe_alb_demo.py
"""
import sys

sys.path.insert(0, "src")

import dataclasses                                  # noqa: E402
import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import ModelConfig, MoEConfig  # noqa: E402
from repro.models import moe as MOE                 # noqa: E402

enable_compile_cache()


def cfg_with(adaptive):
    return ModelConfig(
        name="demo", family="moe", num_layers=1, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=8, top_k=2, num_shared_experts=0,
                      d_expert=32, capacity_factor=1.0,
                      adaptive=adaptive))


key = jax.random.PRNGKey(0)
cfg = cfg_with(True)
params = MOE.moe_init(key, cfg)

# skewed tokens: nearly identical -> router sends everyone to the same
# two experts
base = jax.random.normal(jax.random.PRNGKey(2), (1, 1, cfg.d_model))
x = base + 0.01 * jax.random.normal(jax.random.PRNGKey(3),
                                    (8, 64, cfg.d_model))

for adaptive in [False, True]:
    c = cfg_with(adaptive)
    t = x.shape[0] * x.shape[1]
    xf = x.reshape(t, -1)
    probs = jax.nn.softmax(
        (xf @ params["router"]).astype(jnp.float32), axis=-1)
    flat_e, pos, gate, keep, cap = MOE.dispatch_plan(probs, c.moe, t)
    load = np.bincount(np.asarray(flat_e)[np.asarray(keep)],
                       minlength=8)
    kept = float(jnp.mean(keep.astype(jnp.float32)))
    name = "ALB (adaptive)" if adaptive else "static (blocked)"
    print(f"{name:18s}: kept {kept * 100:5.1f}% of token-slots; "
          f"per-expert load = {load.tolist()} (cap={cap})")

print("\nALB inspector-executor: identical machinery to the paper's LB "
      "kernel\n(exclusive prefix sum over free slots + searchsorted "
      "re-deal).")

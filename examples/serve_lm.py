"""Batched serving example: prefill a batch of prompts, then decode
tokens autoregressively with the KV/SSM cache — the serve-side twin of
train_lm.py, exercised on two architecture families (dense + SSM).

  PYTHONPATH=src python examples/serve_lm.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_smoke_config     # noqa: E402
from repro.models import transformer as T     # noqa: E402

enable_compile_cache()

B, PROMPT, GEN = 4, 48, 16

for arch in ["llama3-8b", "mamba2-2.7b"]:
    cfg = get_smoke_config(arch)
    params = T.init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT),
                                 0, cfg.vocab_size, jnp.int32)
    cache = T.zeros_cache(cfg, B, PROMPT + GEN)

    prefill = jax.jit(lambda p, t, c: T.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts, cache)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    generated = [tok]
    for _ in range(GEN - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None] \
            .astype(jnp.int32)
        generated.append(tok)
    out = jnp.concatenate(generated, axis=1)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"{arch}: batch={B} prompt={PROMPT} generated={GEN} tokens "
          f"in {dt * 1e3:.0f} ms (incl. compile); "
          f"sample: {out[0, :8].tolist()}")

"""Pallas TPU flash attention (forward) — the prefill fast path.

Grid: (batch*kv_heads, q_blocks); each grid step streams KV blocks of
``block_k`` rows through VMEM with the online-softmax recurrence.  The
q/k/v tiles are explicit BlockSpecs (MXU-aligned: block_q × head_dim
and block_k × head_dim, both 128-multiples for full-size heads).

This kernel is the TPU-native replacement for the pure-JAX
``chunked_attention`` scan (ref.py oracle = plain softmax attention);
causal masking skips fully-masked KV blocks via ``@pl.when``.
Validated in interpret mode on CPU; the dry-run lowers the pure-JAX
path (kernel bodies are opaque to HloCostAnalysis anyway).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k,
                  seq_len, causal, sm_scale):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale           # [bq, d]
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    num_kb = seq_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        s = q @ k.T                                        # [bq, bk]
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        acc_new = acc * scale[:, None] + p @ v
        return m_new, l_new, acc_new

    if causal:
        # only blocks with k_start <= q_end participate
        last_kb = jnp.minimum(((qi + 1) * block_q - 1) // block_k + 1,
                              num_kb)
    else:
        last_kb = num_kb
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l[:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q: [B, S, H, hd]; k/v: [B, S, Hkv, hd]. Returns [B, S, H, hd].

    GQA is handled by repeating KV heads logically via the index map
    (no materialized repeat).
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(hd)

    # layout: fold heads into the grid's leading dim
    qg = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kg = jnp.moveaxis(k, 2, 1).reshape(b * hkv, s, hd)
    vg = jnp.moveaxis(v, 2, 1).reshape(b * hkv, s, hd)

    grid = (b * h, s // block_q)
    kern = functools.partial(_flash_kernel, block_q=block_q,
                             block_k=block_k, seq_len=s, causal=causal,
                             sm_scale=sm_scale)
    out = pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
            # KV: whole sequence for this head (streamed via pl.ds)
            pl.BlockSpec((1, s, hd), lambda i, j, g=g: (i // g, 0, 0)),
            pl.BlockSpec((1, s, hd), lambda i, j, g=g: (i // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
    )(qg, kg, vg)
    return jnp.moveaxis(out.reshape(b, h, s, hd), 1, 2)

"""Pure-jnp oracles for the Pallas kernels (per-kernel allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def edge_lb_map_ref(start_e, row_start, hval, total_edges, n_enum,
                    *, tile_edges: int = 2048, distribution: str = "cyclic",
                    num_tiles: int = 64):
    """Oracle for edge_lb.edge_lb_map (same output contract)."""
    w_per = -(-n_enum // num_tiles)
    span = w_per * num_tiles            # exact bijection domain
    n_pad = -(-span // tile_edges) * tile_edges
    eid0 = jnp.arange(n_pad, dtype=jnp.int32)
    if distribution == "blocked":
        eid = (eid0 % num_tiles) * w_per + eid0 // num_tiles
    else:
        eid = eid0
    emask = (eid0 < span) & (eid < total_edges)
    eid_c = jnp.where(emask, eid, 0)
    j = jnp.searchsorted(start_e, eid_c, side="right") - 1
    j = jnp.clip(j, 0, start_e.shape[0] - 1)
    ge = jnp.where(emask, row_start[j] + (eid_c - start_e[j]), 0)
    return ge, j, hval[j], emask


def twc_bin_map_ref(vidx, deg, row_start, val, *, width: int,
                    chunk: int = 0, sentinel: int = 1 << 30):
    """Oracle for twc_gather.twc_bin_map."""
    from .twc_gather import ROW_PAD
    b = vidx.shape[0]
    bp = -(-b // ROW_PAD) * ROW_PAD
    pad = bp - b
    if pad:
        vidx = jnp.pad(vidx, (0, pad), constant_values=sentinel)
        deg = jnp.pad(deg, (0, pad))
        row_start = jnp.pad(row_start, (0, pad))
        val = jnp.pad(val, (0, pad))
    off = chunk * width + jnp.arange(width, dtype=jnp.int32)[None, :]
    emask = (off < deg[:, None]) & (vidx[:, None] < sentinel)
    ge = jnp.where(emask, row_start[:, None] + off, 0)
    anchor = jnp.broadcast_to(vidx[:, None], emask.shape)
    v = jnp.broadcast_to(val[:, None], emask.shape)
    return ge, anchor, v, emask


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """Oracle for flash_attention: plain softmax attention (f32)."""
    import math
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, s, hkv, g, hd) / math.sqrt(hd)
    sc = jnp.einsum("bqkgd,bckd->bqkgc", qf, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None, :, None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def positions_in_expert_ref(flat_expert, num_experts: int):
    """Oracle for moe_dispatch: one-hot cumsum formulation."""
    onehot = jax.nn.one_hot(flat_expert, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.take_along_axis(pos, flat_expert[:, None], axis=1)[:, 0]

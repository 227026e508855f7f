"""Worklist (frontier) utilities.

The paper's D-IrGL baseline uses *implicit dense worklists* (a boolean
flag per vertex, Section 6.1); the GPU kernels are launched per round
with runtime-sized geometry.  We mirror both:

* dense frontier: ``bool[V]`` mask,
* compacted frontier: ``int32[F]`` vertex indices, padded with ``V``
  (an out-of-range sentinel, dropped by ``mode='drop'`` scatters), where
  ``F`` is a *bucketed* capacity so the per-round jitted functions are
  reused across rounds (the CPU/GPU analogue of launching a kernel with
  runtime grid size).

The batched query engine (DESIGN.md section 7) adds a third shape: a
*batch* of dense frontiers ``bool[B, V]``, one row per independent
query over the shared CSR.  The balancer round inspects the **union**
frontier (``union_frontier``) — binning, the huge-bin inspector, and
the LB prefix-sum deal run once for all B queries — while per-query
activity is recovered by gathering the ``[B, V]`` mask at each
enumerated edge's source vertex.

The serving engine (DESIGN.md section 8) treats each batch row as a
*slot* with a lifecycle: a row whose frontier empties has *retired*
(``rows_active``) and can be *refilled* mid-loop with a fresh source
(``refill_rows``) or restored from a preemption snapshot
(``load_rows``) — all at fixed ``[B, V]`` shapes, so the round loop
never recompiles across admissions.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def next_bucket(n: int, minimum: int = 64) -> int:
    """Smallest power of two >= max(n, minimum). Bounds re-jit count."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


_SCAN_BLOCK = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array: ``jnp.cumsum``'s
    values, computed in two levels (within blocks of 1024, then across
    the block totals, recursively).

    ``jnp.cumsum`` lowers to ONE reduce-window as long as the array,
    which XLA's TPU compiler takes seconds (at 2**16 entries) to tens of
    seconds (at 2**19) to compile — per shape bucket, on every round
    that meets a new bucket.  1024-wide windows compile in well under a
    second at any length."""
    n = x.shape[0]
    if n == 0:
        return x
    rows = -(-n // _SCAN_BLOCK)
    blocks = jnp.pad(x, (0, rows * _SCAN_BLOCK - n)).reshape(
        rows, _SCAN_BLOCK)
    within = jnp.cumsum(blocks, axis=1)
    if rows > 1:
        totals = within[:, -1]
        within = within + (prefix_sum(totals) - totals)[:, None]
    return within.reshape(-1)[:n]


# Largest single-row scatter operand XLA's TPU compiler takes as is;
# above it, it sorts the updates first (see scatter_rows)
_SCATTER_CHUNK = 1 << 21


def _scatter(rows, idx, updates, combine):
    if combine == "min":
        return rows.at[:, idx].min(updates, mode="drop")
    if combine == "add":
        return rows.at[:, idx].add(updates, mode="drop")
    if combine == "set":
        return rows.at[:, idx].set(updates, mode="drop")
    raise ValueError(combine)


def scatter_rows(rows: jax.Array, idx: jax.Array, updates: jax.Array,
                 combine: str) -> jax.Array:
    """``rows.at[:, idx].<combine>(updates, mode="drop")`` over ``[B,
    V]`` row state, ``combine`` in ``min`` | ``add`` | ``set``.

    A single row longer than 2**21 entries is scattered one 2**21-entry
    column chunk at a time (indices outside a chunk are dropped there).
    XLA's TPU compiler sorts the updates of a scalar scatter into a
    longer array, and compiling that sort takes 10-20 s per shape
    bucket once there are 2**16 updates or more; a chunk, or a
    multi-row scatter, compiles in under a second."""
    b, v = rows.shape
    if b > 1 or v <= _SCATTER_CHUNK:
        return _scatter(rows, idx, updates, combine)
    parts = []
    for lo in range(0, v, _SCATTER_CHUNK):
        width = min(_SCATTER_CHUNK, v - lo)
        local = jnp.where((idx >= lo) & (idx < lo + width), idx - lo,
                          width)                   # elsewhere: dropped
        parts.append(_scatter(rows[:, lo:lo + width], local, updates,
                              combine))
    return jnp.concatenate(parts, axis=1)


def spread_index(keep: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """``idx`` where ``keep``, elsewhere a pseudo-random index into an
    ``n``-entry array (a multiplicative hash of the slot position).

    Masked slots of a padded tile still issue their gathers, whose
    results are then discarded.  On a TPU a gather whose indices all
    hit one entry runs at half the rate of spread indices (2**24
    indices into 65M entries: 511 ms against 244 ms on a v5e), so
    masked slots read scattered entries in place of entry 0."""
    k = max(int(n), 1).bit_length() - 1            # 2**k <= n
    if k == 0:
        return jnp.where(keep, idx, 0)
    pos = jnp.arange(idx.size, dtype=jnp.uint32).reshape(idx.shape)
    hashed = (pos * jnp.uint32(0x9E3779B1)) >> (32 - k)
    return jnp.where(keep, idx, hashed.astype(idx.dtype))


@partial(jax.jit, static_argnames=("size",))
def compact(mask: jax.Array, size: int) -> jax.Array:
    """Indices of set bits in ascending order, the first ``size`` of
    them, padded with len(mask) (sentinel) — ``jnp.nonzero(mask,
    size=size, fill_value=len(mask))`` built on :func:`prefix_sum`."""
    n = mask.shape[0]
    rank = prefix_sum(mask.astype(jnp.int32)) - 1
    slot = jnp.where(mask, rank, size)             # unset: dropped
    return scatter_rows(jnp.full((1, size), n, jnp.int32), slot,
                        jnp.arange(n, dtype=jnp.int32)[None], "set")[0]


@jax.jit
def count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32))


@jax.jit
def dirty_mask(old: jax.Array, new: jax.Array) -> jax.Array:
    """Per-label "touched this round" bitvector (Gluon's dirty set):
    the master/mirror sync only exchanges vertices set here (DESIGN.md
    section 6).  Elementwise, so a batched ``[B, V]`` label pair yields
    a per-query dirty mask."""
    return new != old


@jax.jit
def dirty_vertices(old: jax.Array, new: jax.Array) -> jax.Array:
    """Per-**vertex** dirty mask: a vertex is dirty when its label
    changed in *any* query of the batch — the granularity the mirror
    sync ships at, since each dirty vertex carries its whole ``[B]``
    label vector (DESIGN.md section 7)."""
    d = new != old
    return d if d.ndim == 1 else jnp.any(d, axis=0)


@jax.jit
def union_frontier(frontier: jax.Array) -> jax.Array:
    """Dense union of a batch of frontiers: ``[B, V] -> [V]`` (identity
    on an un-batched ``[V]`` mask).  The balancer round plans bins and
    the LB deal over this union so one launch serves every query."""
    return frontier if frontier.ndim == 1 else jnp.any(frontier, axis=0)


@partial(jax.jit, static_argnames=("num_vertices",))
def seed_from_edges(src: jax.Array, dst: jax.Array, mask: jax.Array,
                    num_vertices: int) -> jax.Array:
    """Dense ``bool[V]`` frontier seeded from the endpoints of changed
    edges — the worklist an incremental label repair starts from
    (DESIGN.md section 10).  ``src``/``dst``/``mask`` are the
    fixed-capacity ``[K]`` arrays of an update delta (``mask`` False =
    padding slot); both endpoints of every live entry are set, so the
    repair round re-relaxes every edge whose shape or weight changed.
    Fixed ``K`` means one jit trace serves every batch of a stream."""
    off = jnp.zeros((num_vertices,), dtype=bool)
    ssafe = jnp.where(mask, src, num_vertices)     # sentinel: dropped
    dsafe = jnp.where(mask, dst, num_vertices)
    return off.at[ssafe].set(True, mode="drop") \
              .at[dsafe].set(True, mode="drop")


def full_frontier(num_vertices: int) -> jax.Array:
    return jnp.ones((num_vertices,), dtype=bool)


def single_source(num_vertices: int, src: int) -> jax.Array:
    return jnp.zeros((num_vertices,), dtype=bool).at[src].set(True)


def coerce_sources(sources) -> jax.Array:
    """Host-provided source vertices as a validated int32 ``[B]``
    vector — the ONE entry point through which batch source lists
    reach the device.  Centralizing the coercion keeps every batch
    init agreeing on dtype (int32 indexes the one-hot scatters) and
    shape (a scalar or nested list here would silently broadcast into
    the wrong frontier), and gives the host-sync lint a single
    annotated host->device crossing instead of per-caller copies."""
    srcs = jnp.asarray(sources, jnp.int32)
    if srcs.ndim != 1:
        raise ValueError(
            f"sources must be a flat [B] vector of vertex ids; got "
            f"shape {tuple(srcs.shape)}")
    return srcs


def single_sources(num_vertices: int, sources) -> jax.Array:
    """Batched one-hot frontiers ``bool[B, V]``: row b activates only
    ``sources[b]`` — the initial worklists of a multi-source batch."""
    srcs = coerce_sources(sources)
    b = srcs.shape[0]
    return jnp.zeros((b, num_vertices), dtype=bool) \
        .at[jnp.arange(b), srcs].set(True)


@jax.jit
def rows_active(frontier: jax.Array) -> jax.Array:
    """Per-slot liveness ``bool[B]`` of a batched frontier: row b is
    active while any of its vertices is on the worklist.  A row that
    goes inactive has *retired* — its query converged and its slot can
    be refilled (DESIGN.md section 8)."""
    return jnp.any(frontier, axis=-1)


@jax.jit
def refill_rows(labels: jax.Array, frontier: jax.Array,
                slots: jax.Array, sources: jax.Array, fill) -> tuple:
    """Admit fresh single-source queries into batch slots, in place of
    whatever the rows held before (DESIGN.md section 8).

    ``slots``/``sources`` are int32 ``[K]`` (pad unused entries with
    ``slots[k] = B`` — the out-of-range sentinel is dropped by the
    ``mode="drop"`` scatter, so one fixed ``K`` serves any number of
    admissions without re-jitting).  Each named slot's labels row is
    reset to ``fill`` with 0 at its own source and its frontier row to
    the one-hot source — exactly :func:`multi_source_state` for that
    row, so a refilled slot evolves bitwise like a standalone run.
    """
    v = labels.shape[-1]
    k = slots.shape[0]
    ssafe = jnp.clip(sources, 0, v - 1)
    lrows = jnp.full((k, v), fill, labels.dtype) \
        .at[jnp.arange(k), ssafe].set(0)
    frows = jnp.zeros((k, v), dtype=bool) \
        .at[jnp.arange(k), ssafe].set(True)
    return (labels.at[slots].set(lrows, mode="drop"),
            frontier.at[slots].set(frows, mode="drop"))


@jax.jit
def load_rows(labels: jax.Array, frontier: jax.Array, slots: jax.Array,
              label_rows: jax.Array, frontier_rows: jax.Array) -> tuple:
    """Restore snapshot rows into batch slots: the resume half of the
    serving engine's preempt/resume pair (DESIGN.md section 8).
    ``slots`` is int32 ``[K]`` (sentinel ``B`` entries dropped) and
    ``label_rows``/``frontier_rows`` are the ``[K, V]`` per-slot states
    captured when the queries were preempted; restoring them is exact,
    so a resumed query's labels evolve bitwise as if never paused."""
    return (labels.at[slots].set(label_rows, mode="drop"),
            frontier.at[slots].set(frontier_rows, mode="drop"))


def multi_source_state(num_vertices: int, sources, fill,
                       dtype=jnp.int32):
    """Initial ``[B, V]`` state of a multi-source batch: labels filled
    with ``fill`` except 0 at each query's own source, plus the one-hot
    frontiers.  The single entry-point init shared by the single-device
    and distributed batch drivers (so their label dtype/sentinel can
    never diverge)."""
    srcs = coerce_sources(sources)
    b = srcs.shape[0]
    labels = jnp.full((b, num_vertices), fill, dtype=dtype) \
        .at[jnp.arange(b), srcs].set(0)
    return labels, single_sources(num_vertices, srcs)

"""Gluon-analog distributed BSP runtime over shard_map.

Execution model (paper Section 2.1 / 5, DESIGN.md section 4): each
device computes a round on its local partition with the full ALB
machinery, then participates in a global synchronization that
reconciles vertex labels with the operator's combiner (min for
bfs/sssp/cc, add for pr/kcore deltas).

Two sync substrates are available (``sync=`` on every driver):

* ``"replicated"`` — every vertex mirrored everywhere; sync is a single
  ``pmin``/``psum`` over the ``dev`` mesh axis — one fused all-reduce
  per round.  Communication-heaviest but simplest; kept as the parity
  baseline.
* ``"mirror"`` — the master/mirror substrate (DESIGN.md section 6):
  labels live per device, every vertex has one master
  (``PartitionMeta.master_bounds``), and each round runs Gluon's
  reduce-broadcast pair over the *boundary only* — a dirty-masked
  reduce-to-master followed by a broadcast-to-mirrors, both built from
  gathers over the padded mirror index lists plus ``lax.ppermute``
  rings over the ``dev`` axis.  Only labels touched this round (the
  jit-safe dirty bitvector out of ``relax_spmd``) carry payload;
  ``RoundStatsDev.bytes_synced`` / ``mirrors_synced`` count them.

The per-device round is the fully-jit ``relax_spmd`` variant, whose
``lax.cond`` inspector skips the LB executor's work on devices whose
local partition has no huge frontier vertex this round — the paper's
adaptivity, per device.  ``relax_spmd`` dispatches through the executor
registry (DESIGN.md section 3), so ``BalancerConfig.use_pallas=True``
runs the Pallas LB/TWC mapping kernels *inside* ``shard_map``, and
``collect_stats=True`` threads jit-safe per-device ``RoundStatsDev``
through the same ``shard_map`` boundary (stacked along the ``dev``
axis).

Both substrates accept **batched** label/frontier state (DESIGN.md
section 7): ``relax_spmd`` plans each device's round over the union
frontier of all B queries, the replicated all-reduce simply spans the
``[B, V]`` array, and the mirror substrate ships one ``[B]`` label
vector per dirty boundary vertex (``bytes_synced`` scales by B while
``mirrors_synced`` keeps counting vertices).
``sssp_batch_distributed`` / ``bfs_batch_distributed`` are the
multi-source entry points.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .graph import Graph, INF
from .balancer import (BalancerConfig, RoundStats, RoundStatsDev,
                       relax_spmd, combine_neutral, _note_host_transfer)
from .frontier import multi_source_state, scatter_rows
from .operators import Operator
from .partition import PartitionMeta
from . import operators as ops
from . import wire as wirecodec
from .wire import step_logical_bytes


def device_mesh(num_devices: int | None = None):
    """A 1-D ``("dev",)`` mesh over the first ``num_devices`` local
    devices (all of them by default) — what every distributed driver
    here expects.  Raises ``ValueError`` when fewer than
    ``num_devices`` devices exist: a smaller mesh would run the
    partitions on fewer devices than the caller asked for."""
    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(f"device_mesh({num_devices}) needs "
                             f"{num_devices} devices; {len(devs)} "
                             f"{devs[0].platform} device(s) exist")
        devs = devs[:num_devices]
    return jax.sharding.Mesh(np.asarray(devs), ("dev",))


def _sync(labels, combine: str):
    if combine == "min":
        return jax.lax.pmin(labels, "dev")
    return jax.lax.psum(labels, "dev")


def make_round_fn(mesh, cfg: BalancerConfig, op: Operator,
                  sync_delta: bool = False, collect_stats: bool = False):
    """Build the jitted one-BSP-round function (replicated sync).

    sync_delta: for ``add``-combine operators the per-device scatter
    accumulates into a zero-initialized delta that is psum'd, then added
    to the replicated base — avoids double counting the base.

    collect_stats: the round function additionally returns a
    ``RoundStatsDev`` whose leaves carry a leading ``dev`` axis — one
    instrumentation record per device per round (Fig 1/5 in SPMD mode).
    ``bytes_synced`` reports the all-reduce's per-device volume —
    ``V * itemsize`` every round, the baseline the mirror substrate
    undercuts; ``bytes_wire`` is what ``cfg.wire``'s codec would put
    on a real wire for the same round (the all-reduce itself stays
    full-width — encoding a commutative reduction tree is the
    transport's job, so the codec is accounting-only here).
    """
    codec = wirecodec.get_codec(cfg.wire, op)

    def round_fn(stacked_g: Graph, values, labels, frontier):
        # shard_map hands each device a [1, ...] block: squeeze to local
        stacked_g = Graph(row_ptr=stacked_g.row_ptr[0],
                          col_idx=stacked_g.col_idx[0],
                          edge_w=stacked_g.edge_w[0])
        # per-device local compute
        if sync_delta:
            delta = jnp.zeros_like(labels)
            out = relax_spmd(stacked_g, values, delta, frontier, cfg, op,
                             collect_stats=collect_stats)
            delta, st = out if collect_stats else (out, None)
            shipped, prev = delta, jnp.zeros_like(delta)
            delta = _sync(delta, "add")
            new = labels + delta
        else:
            out = relax_spmd(stacked_g, values, labels, frontier, cfg, op,
                             collect_stats=collect_stats)
            new, st = out if collect_stats else (out, None)
            shipped, prev = new, labels
            new = _sync(new, op.combine)
        if collect_stats:
            # all-reduce volume spans every label entry: V vertices
            # exchanged (same unit as the mirror substrate's count),
            # each carrying a [B] vector -> bytes scale by the batch
            st = st._replace(
                mirrors_synced=jnp.int32(labels.shape[-1]),
                bytes_synced=jnp.int32(labels.size * labels.dtype.itemsize),
                bytes_wire=codec.allreduce_wire_bytes(shipped, prev))
            # leading axis of size 1 -> stacked to [D, ...] by out_specs
            return new, jax.tree_util.tree_map(lambda x: x[None], st)
        return new

    gspec = Graph(row_ptr=P("dev"), col_idx=P("dev"), edge_w=P("dev"))
    out_specs = P()
    if collect_stats:
        out_specs = (P(), RoundStatsDev(
            *([P("dev")] * len(RoundStatsDev._fields))))
    fn = jax.shard_map(round_fn, mesh=mesh,
                       in_specs=(gspec, P(), P(), P()),
                       out_specs=out_specs,
                       check_vma=False)
    return jax.jit(fn)


def make_fused_traversal_fn(mesh, cfg: BalancerConfig, op: Operator,
                            sync_delta: bool = False,
                            max_rounds: int = 10_000,
                            values_of=lambda l: l,
                            next_frontier=lambda old, new, f: new < old):
    """Build the fused replicated-sync traversal: the whole BSP loop
    as ONE ``lax.while_loop`` *inside* ``shard_map`` (DESIGN.md
    section 11 applied to the distributed runtime).

    The per-round all-reduce keeps labels identical across devices, so
    the derived frontier — and therefore the loop condition — is
    uniform without any extra collective: between dispatch and the
    final label fetch no value crosses to the host.  ``values_of`` /
    ``next_frontier`` move inside the traced loop (the host loop
    applies them between dispatches instead).  Returns
    ``(labels, rounds)`` — both device values."""
    def trav_fn(stacked_g: Graph, labels, frontier):
        g = Graph(row_ptr=stacked_g.row_ptr[0],
                  col_idx=stacked_g.col_idx[0],
                  edge_w=stacked_g.edge_w[0])

        def cond(carry):
            r, lab, fr = carry
            return (r < max_rounds) & jnp.any(fr)

        def body(carry):
            r, lab, fr = carry
            values = values_of(lab)
            if sync_delta:
                delta = jnp.zeros_like(lab)
                delta = relax_spmd(g, values, delta, fr, cfg, op)
                new = lab + _sync(delta, "add")
            else:
                new = _sync(relax_spmd(g, values, lab, fr, cfg, op),
                            op.combine)
            return r + 1, new, next_frontier(lab, new, fr)

        r, labels, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), labels, frontier))
        return labels, r

    gspec = Graph(row_ptr=P("dev"), col_idx=P("dev"), edge_w=P("dev"))
    fn = jax.shard_map(trav_fn, mesh=mesh,
                       in_specs=(gspec, P(), P()),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn)


# ---- master/mirror substrate (DESIGN.md section 6) -------------------------

def make_mirror_round_fn(mesh, cfg: BalancerConfig, op: Operator,
                         meta: PartitionMeta,
                         sync_delta: bool = False,
                         collect_stats: bool = False,
                         values_of=lambda l: l,
                         next_frontier=lambda old, new, f: new < old,
                         post_sync=None, global_of=None,
                         fused: bool = False, max_rounds: int = 10_000,
                         tol: float | None = None):
    """One BSP round over owned state: local ALB round, then Gluon's
    reduce-to-master -> broadcast-to-mirrors pair over the padded mirror
    lists.

    Per-device label/frontier state is carried across rounds as a
    ``[D, B, V]`` array sharded over ``dev`` (``B`` = query batch, 1
    for single-query drivers — the loop canonicalizes).  The invariant
    maintained: after the round, a device's copy is globally correct
    for every vertex it masters or mirrors (= every endpoint of a local
    edge, the only entries the next local round can read or write);
    other entries may be stale, and the final labels are assembled
    owner-by-owner.

    Sync payloads are per-**vertex**: a boundary vertex is dirty when
    any query touched it, and a dirty vertex ships its whole ``[B]``
    label vector plus its int32 index word in one ring step (DESIGN.md
    section 7) — ``mirrors_synced`` counts vertices, ``bytes_synced``
    is the logical volume (index side included), and ``bytes_wire``
    the post-encode volume under ``cfg.wire``'s codec
    (repro.core.wire), which both rings route every payload through.

    ``values_of`` / ``next_frontier`` / ``post_sync`` are traced inside
    ``shard_map`` so frontier and value derivation stay device-local —
    only a scalar activity count (and a residual, for convergence-driven
    drivers) crosses to the host each round.

    ``global_of`` (optional): ``(labels, owned_mask) -> scalar``
    evaluated on each device over its owned master range — the one
    slice of pre-round state guaranteed globally correct — and
    ``psum``'d across devices; the global scalar is then passed as a
    third argument to ``post_sync(labels, acc, glob)``.  PageRank uses
    it for the dangling-mass sum (no extra host traffic: the reduction
    rides the round's existing collectives).

    ``fused=True`` wraps the same round body in a ``lax.while_loop``
    *inside* ``shard_map`` (DESIGN.md section 11): the activity count
    and residual that the host loop fetches every round become carried
    loop state — the psum/pmax in the round body make them uniform
    across devices, so the loop condition is collective-safe — and the
    traversal returns ``(labels_dev, frontier_dev, rounds)`` after a
    single dispatch.  Stats collection stays per-dispatch, so
    ``fused`` requires ``collect_stats=False``.
    """
    ndev = meta.num_devices
    v = meta.num_vertices
    codec = wirecodec.get_codec(cfg.wire, op)
    if fused and collect_stats:
        raise ValueError("fused mirror traversal does not collect "
                         "per-round stats (one dispatch, no per-round "
                         "host boundary)")
    if post_sync is None:
        post_sync = ((lambda lab, acc: lab + acc) if sync_delta
                     else (lambda lab, acc: acc))

    def round_fn(stacked_g: Graph, mirror_t, incoming_t, lo_t, hi_t,
                 labels0, frontier0):
        g = Graph(row_ptr=stacked_g.row_ptr[0],
                  col_idx=stacked_g.col_idx[0],
                  edge_w=stacked_g.edge_w[0])
        mirror_t = mirror_t[0]        # [D, L]: rows indexed by owner
        incoming_t = incoming_t[0]    # [D, L]: rows indexed by toucher
        lo, hi = lo_t[0], hi_t[0]     # my owned range
        labels0, frontier0 = labels0[0], frontier0[0]  # [B, V]
        b = labels0.shape[0]
        me = jax.lax.axis_index("dev")

        def one_round(labels, frontier):
            values = values_of(labels)
            base = jnp.zeros_like(labels) if sync_delta else labels
            out = relax_spmd(g, values, base, frontier, cfg, op,
                             collect_stats=collect_stats,
                             return_dirty=True)
            if collect_stats:
                new, st, dirty = out
            else:
                (new, dirty), st = out, None
            dirty_v = jnp.any(dirty, axis=0)           # [V] any-query
            # non-dirty mirror slots carry the combiner's identity so
            # skipping them is exact (same rule as the balancer's
            # scatter)
            neutral = combine_neutral(op.combine, new.dtype)

            perm_fwd = [[(i, (i + s) % ndev) for i in range(ndev)]
                        for s in range(ndev)]
            perm_bwd = [[(i, (i - s) % ndev) for i in range(ndev)]
                        for s in range(ndev)]

            # ---- reduce-to-master: each ring step s ships my dirty
            # values for vertices mastered s hops ahead; the sentinel-V
            # padding is dropped by the scatter, non-dirty slots carry
            # the neutral.  The payload crosses the ring through
            # ``cfg.wire``'s codec: the reduce direction's delta
            # reference is the round-entry labels (zeros in delta-sync
            # mode, where the payload already IS a delta) — both ends
            # hold identical copies for every real mirror-list slot
            # because the previous broadcast overwrote them.
            prev_reduce = (jnp.zeros_like(labels) if sync_delta
                           else labels)
            acc = new
            n_exch = jnp.int32(0)
            b_log = jnp.int32(0)
            b_wire = jnp.int32(0)
            for s in range(1, ndev):
                out_idx = mirror_t[(me + s) % ndev]
                safe = jnp.where(out_idx < v, out_idx, 0)
                live = (out_idx < v) & dirty_v[safe]
                payload = jnp.where(live[None], new[:, safe], neutral)
                if collect_stats:
                    n_exch += jnp.sum(live.astype(jnp.int32))
                    b_log += step_logical_bytes(
                        live, b, new.dtype.itemsize)
                    b_wire += codec.step_wire_bytes(
                        payload, prev_reduce[:, safe], live, op)
                recv = jax.lax.ppermute(
                    codec.encode(payload, prev_reduce[:, safe], op),
                    "dev", perm_fwd[s])
                in_idx = incoming_t[(me - s) % ndev]
                safe_in = jnp.where(in_idx < v, in_idx, 0)
                recv = codec.decode(recv, prev_reduce[:, safe_in], op,
                                    new.dtype)
                acc = scatter_rows(acc, in_idx, recv, op.combine)

            if global_of is not None:
                ovids = jnp.arange(v, dtype=jnp.int32)
                omask = (ovids >= lo) & (ovids < hi)
                glob = jax.lax.psum(global_of(labels, omask), "dev")
                final = post_sync(labels, acc, glob)
            else:
                final = post_sync(labels, acc)

            # ---- broadcast-to-mirrors: masters push the reduced
            # values back along the reverse ring; mirrors overwrite
            # their copies.  Here the delta reference is always the
            # round-entry labels: the broadcast ships actual labels
            # even in delta-sync mode, and every mirror-list slot's
            # round-entry copy agrees across devices (the previous
            # broadcast's unconditional overwrite).
            gdirty = jnp.any(final != labels, axis=0)  # [V]
            for s in range(1, ndev):
                out_idx = incoming_t[(me - s) % ndev]
                safe = jnp.where(out_idx < v, out_idx, 0)
                live = (out_idx < v) & gdirty[safe]
                payload = final[:, safe]
                if collect_stats:
                    n_exch += jnp.sum(live.astype(jnp.int32))
                    b_log += step_logical_bytes(
                        live, b, final.dtype.itemsize)
                    b_wire += codec.step_wire_bytes(
                        payload, labels[:, safe], live, op)
                recv = jax.lax.ppermute(
                    codec.encode(payload, labels[:, safe], op),
                    "dev", perm_bwd[s])
                in_idx = mirror_t[(me + s) % ndev]
                safe_in = jnp.where(in_idx < v, in_idx, 0)
                # signed=False: the broadcast ships full labels, which
                # are non-negative — unsigned narrow words zero-extend
                # (kcore degrees in [2^15, 2^16) stay exact)
                recv = codec.decode(recv, labels[:, safe_in], op,
                                    final.dtype, signed=False)
                final = scatter_rows(final, in_idx, recv, "set")

            new_frontier = next_frontier(labels, final, frontier)
            active = jax.lax.psum(
                jnp.sum(new_frontier.astype(jnp.int32)), "dev")
            vids = jnp.arange(v, dtype=jnp.int32)
            owned = (vids >= lo) & (vids < hi)
            resid = jax.lax.pmax(jnp.max(jnp.where(
                owned[None],
                jnp.abs(final.astype(jnp.float32)
                        - labels.astype(jnp.float32)),
                0.0)), "dev")
            if collect_stats:
                # bytes_synced is the LOGICAL exchange volume: every
                # live vertex ships its int32 index word alongside the
                # [B] payload (the index side used to be dropped from
                # the count — see tests/test_mirror_sync.py's
                # accounting regression); bytes_wire is the post-encode
                # volume under cfg.wire's codec.
                st = st._replace(
                    mirrors_synced=n_exch,
                    bytes_synced=b_log,
                    bytes_wire=b_wire)
            return final, new_frontier, active, resid, st

        if not fused:
            final, new_frontier, active, resid, st = one_round(
                labels0, frontier0)
            outs = (final[None], new_frontier[None], active, resid)
            if collect_stats:
                outs += (jax.tree_util.tree_map(lambda x: x[None], st),)
            return outs

        # fused: the host loop's per-round observations (activity,
        # residual) become carried state; both are psum/pmax-reduced in
        # the body, so the condition is uniform across devices.
        def cond(carry):
            r, lab, fr, active, resid = carry
            ok = (r < max_rounds) & (active > 0)
            if tol is not None:
                ok = ok & (resid >= tol)
            return ok

        def body(carry):
            r, lab, fr, active, resid = carry
            final, nfr, active, resid, _ = one_round(lab, fr)
            return r + 1, final, nfr, active, resid

        active0 = jax.lax.psum(
            jnp.sum(frontier0.astype(jnp.int32)), "dev")
        r, final, fr, _, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), labels0, frontier0, active0,
                         jnp.float32(jnp.inf)))
        return final[None], fr[None], r

    gspec = Graph(row_ptr=P("dev"), col_idx=P("dev"), edge_w=P("dev"))
    if fused:
        out_specs = (P("dev"), P("dev"), P())
    else:
        out_specs = (P("dev"), P("dev"), P(), P())
        if collect_stats:
            out_specs += (RoundStatsDev(
                *([P("dev")] * len(RoundStatsDev._fields))),)
    fn = jax.shard_map(round_fn, mesh=mesh,
                       in_specs=(gspec, P("dev"), P("dev"), P("dev"),
                                 P("dev"), P("dev"), P("dev")),
                       out_specs=out_specs,
                       check_vma=False)
    return jax.jit(fn)


def _mirror_tables(meta: PartitionMeta):
    """Device-resident sync metadata: the padded mirror lists in both
    orientations plus the owned ranges."""
    mirror_t = jnp.asarray(meta.mirror_idx)                       # [D,D,L]
    incoming_t = jnp.asarray(meta.mirror_idx.transpose(1, 0, 2))  # [o,d,L]
    lo = jnp.asarray(meta.master_bounds[:-1], jnp.int32)
    hi = jnp.asarray(meta.master_bounds[1:], jnp.int32)
    return mirror_t, incoming_t, lo, hi


def assemble_owned(labels_dev, meta: PartitionMeta):
    """Gather each vertex's label from its master's copy — the only
    copies guaranteed globally correct under the mirror substrate.
    Accepts ``[D, V]`` or batched ``[D, B, V]`` state (returns
    ``[V]`` / ``[B, V]``)."""
    arr = np.asarray(labels_dev)
    vsel = np.arange(meta.num_vertices)
    if arr.ndim == 3:
        # advanced indices around the batch slice land in front: [V, B]
        return jnp.asarray(arr[meta.owner, :, vsel].T)
    return jnp.asarray(arr[meta.owner, vsel])


def stats_per_device(st: RoundStatsDev) -> list[RoundStats]:
    """Split a dev-stacked RoundStatsDev into one host RoundStats per
    device."""
    ndev = st.frontier_size.shape[0]
    return [RoundStats.from_device(
        jax.tree_util.tree_map(lambda x: x[d], st)) for d in range(ndev)]


def _any_host(frontier) -> bool:
    """The replicated host loop's per-round frontier probe — a
    blocking device->host sync, counted against the traversal's
    ``host_transfers`` (the quantity ``mode='fused'`` drives to
    zero)."""
    _note_host_transfer()
    return bool(jnp.any(frontier))


def _require_push_direction(cfg: BalancerConfig) -> None:
    """The distributed runtime is push-only (partitions are cut along
    out-edges; the sync substrates ship scatter targets) — refuse
    direction-optimized configs instead of silently running push."""
    if cfg.direction != "push":
        raise ValueError(
            f"the distributed runtime is push-only; "
            f"cfg.direction={cfg.direction!r} is not supported "
            f"(DESIGN.md section 9)")


def _require_meta(meta, sync):
    if sync not in ("replicated", "mirror"):
        raise ValueError(f"unknown sync {sync!r} (replicated|mirror)")
    if sync == "mirror" and meta is None:
        raise ValueError("sync='mirror' needs the PartitionMeta returned "
                         "by partition()")


def run_distributed(stacked_g: Graph, mesh, op: Operator,
                    init_labels, init_frontier,
                    cfg: BalancerConfig = BalancerConfig(),
                    values_of=lambda l: l,
                    next_frontier=lambda old, new, f: new < old,
                    sync_delta: bool = False,
                    max_rounds: int = 10_000,
                    collect_stats: bool = False,
                    sync: str = "replicated",
                    meta: PartitionMeta | None = None,
                    mode: str = "host"):
    """Generic distributed data-driven loop. Returns (labels, rounds,
    total_seconds) — or, with ``collect_stats=True``, (labels, rounds,
    total_seconds, stats) where ``stats[round][device]`` is a host
    :class:`RoundStats` — the compute/comm split feeds the Fig 7/11
    breakdown and the per-device load plots.

    ``sync="mirror"`` (requires ``meta``) swaps the whole-array
    all-reduce for the dirty-tracked boundary exchange; labels and
    frontier stay per-device inside the loop and only a scalar activity
    count comes back to the host each round.

    ``mode="fused"`` dispatches the whole traversal as ONE
    ``lax.while_loop`` inside ``shard_map`` (DESIGN.md section 11):
    zero host syncs between rounds for either substrate.  Per-round
    stats need the per-round host boundary, so fused requires
    ``collect_stats=False``.

    The distributed runtime is push-only: partitions are cut along
    out-edges and the sync substrates exchange scatter targets, so
    direction-optimized configs (DESIGN.md section 9) are refused
    rather than silently run as push.
    """
    _require_push_direction(cfg)
    _require_meta(meta, sync)
    # config-time codec/operator pairing check: a quantize wire on an
    # operator that declares no safe narrowing must fail HERE, before
    # any round is traced or run
    wirecodec.get_codec(cfg.wire, op, init_labels.dtype)
    if mode not in ("host", "fused"):
        raise ValueError(f"unknown distributed mode {mode!r} "
                         "(host|fused)")
    if mode == "fused" and collect_stats:
        raise ValueError("mode='fused' runs with collect_stats=False "
                         "(per-round stats need the per-round host "
                         "boundary)")
    if sync == "mirror":
        return _run_mirror(stacked_g, mesh, op, init_labels, init_frontier,
                           cfg, values_of, next_frontier, sync_delta,
                           max_rounds, collect_stats, meta, mode=mode)
    if mode == "fused":
        trav_fn = make_fused_traversal_fn(
            mesh, cfg, op, sync_delta=sync_delta, max_rounds=max_rounds,
            values_of=values_of, next_frontier=next_frontier)
        t0 = time.perf_counter()
        labels, r = trav_fn(stacked_g, init_labels, init_frontier)
        jax.block_until_ready(labels)
        return labels, int(r), time.perf_counter() - t0
    round_fn = make_round_fn(mesh, cfg, op, sync_delta=sync_delta,
                             collect_stats=collect_stats)
    labels, frontier = init_labels, init_frontier
    rounds = 0
    stats = [] if collect_stats else None
    t0 = time.perf_counter()
    while rounds < max_rounds and _any_host(frontier):
        old = labels
        out = round_fn(stacked_g, values_of(labels), labels, frontier)
        if collect_stats:
            labels, st = out
            stats.append(stats_per_device(st))
        else:
            labels = out
        jax.block_until_ready(labels)
        frontier = next_frontier(old, labels, frontier)
        rounds += 1
    total = time.perf_counter() - t0
    if collect_stats:
        return labels, rounds, total, stats
    return labels, rounds, total


def _run_mirror(stacked_g, mesh, op, init_labels, init_frontier, cfg,
                values_of, next_frontier, sync_delta, max_rounds,
                collect_stats, meta: PartitionMeta, post_sync=None,
                tol: float | None = None, global_of=None,
                mode: str = "host"):
    """Owned-state loop shared by the data-driven drivers and the
    convergence-driven ones: stops when the frontier empties, the round
    budget runs out, or (``tol`` set) the owned-entry residual drops
    below it.  State is carried batched (``[D, B, V]``); un-batched
    callers get the query axis added here and squeezed on return.
    ``mode="fused"`` runs the whole loop on device in one dispatch
    (see :func:`make_mirror_round_fn`)."""
    batched = init_labels.ndim == 2
    if not batched:
        init_labels = init_labels[None]
        init_frontier = init_frontier[None]
    mirror_t, incoming_t, lo, hi = _mirror_tables(meta)
    ndev = meta.num_devices
    labels_dev = jnp.tile(init_labels[None], (ndev, 1, 1))
    frontier_dev = jnp.tile(init_frontier[None], (ndev, 1, 1))
    if mode == "fused":
        trav_fn = make_mirror_round_fn(
            mesh, cfg, op, meta, sync_delta=sync_delta,
            collect_stats=False, values_of=values_of,
            next_frontier=next_frontier, post_sync=post_sync,
            global_of=global_of, fused=True, max_rounds=max_rounds,
            tol=tol)
        t0 = time.perf_counter()
        labels_dev, frontier_dev, r = trav_fn(
            stacked_g, mirror_t, incoming_t, lo, hi,
            labels_dev, frontier_dev)
        jax.block_until_ready(labels_dev)
        labels = assemble_owned(labels_dev, meta)
        if not batched:
            labels = labels[0]
        return labels, int(r), time.perf_counter() - t0
    round_fn = make_mirror_round_fn(
        mesh, cfg, op, meta, sync_delta=sync_delta,
        collect_stats=collect_stats, values_of=values_of,
        next_frontier=next_frontier, post_sync=post_sync,
        global_of=global_of)
    # the per-round activity probe below is a noted transfer site;
    # this one is the pre-loop seed count, paid once per traversal
    active = int(jnp.sum(init_frontier))  # repro: allow[host-sync] -- one-time pre-loop seed count
    rounds = 0
    stats = [] if collect_stats else None
    t0 = time.perf_counter()
    while rounds < max_rounds and active > 0:
        out = round_fn(stacked_g, mirror_t, incoming_t, lo, hi,
                       labels_dev, frontier_dev)
        if collect_stats:
            labels_dev, frontier_dev, active_a, resid, st = out
            stats.append(stats_per_device(st))
        else:
            labels_dev, frontier_dev, active_a, resid = out
        active = int(active_a)
        _note_host_transfer()      # the activity/residual probe blocks
        rounds += 1
        if tol is not None and float(resid) < tol:
            break
    labels = assemble_owned(labels_dev, meta)
    if not batched:
        labels = labels[0]
    total = time.perf_counter() - t0
    if collect_stats:
        return labels, rounds, total, stats
    return labels, rounds, total


# ---- distributed application drivers --------------------------------------

def sssp_distributed(stacked_g: Graph, mesh, source: int,
                     cfg: BalancerConfig = BalancerConfig(),
                     max_rounds: int = 10_000,
                     collect_stats: bool = False,
                     sync: str = "replicated",
                     meta: PartitionMeta | None = None,
                     mode: str = "host"):
    """Distributed single-source SSSP over a partitioned (stacked-CSR)
    graph; ``sync`` selects the replicated all-reduce or the
    master/mirror boundary exchange (DESIGN.md section 6);
    ``mode="fused"`` runs the whole traversal in one device dispatch
    (DESIGN.md section 11)."""
    v = stacked_g.row_ptr.shape[-1] - 1
    dist = jnp.full((v,), INF, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((v,), bool).at[source].set(True)
    return run_distributed(stacked_g, mesh, ops.SSSP_RELAX, dist, frontier,
                           cfg, max_rounds=max_rounds,
                           collect_stats=collect_stats, sync=sync,
                           meta=meta, mode=mode)


def bfs_distributed(stacked_g: Graph, mesh, source: int,
                    cfg: BalancerConfig = BalancerConfig(),
                    max_rounds: int = 10_000,
                    collect_stats: bool = False,
                    sync: str = "replicated",
                    meta: PartitionMeta | None = None,
                    mode: str = "host"):
    """Distributed single-source BFS (see :func:`sssp_distributed`)."""
    v = stacked_g.row_ptr.shape[-1] - 1
    lvl = jnp.full((v,), INF, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((v,), bool).at[source].set(True)
    return run_distributed(stacked_g, mesh, ops.BFS_HOP, lvl, frontier,
                           cfg, max_rounds=max_rounds,
                           collect_stats=collect_stats, sync=sync,
                           meta=meta, mode=mode)


def sssp_batch_distributed(stacked_g: Graph, mesh, sources,
                           cfg: BalancerConfig = BalancerConfig(),
                           max_rounds: int = 10_000,
                           collect_stats: bool = False,
                           sync: str = "replicated",
                           meta: PartitionMeta | None = None,
                           mode: str = "host"):
    """Batched multi-source SSSP on the distributed runtime: B queries
    share every BSP round (union-frontier rounds per device) and, under
    ``sync="mirror"``, every boundary exchange (one ``[B]`` vector per
    dirty vertex — DESIGN.md section 7).  Returns ``labels[B, V]``."""
    v = stacked_g.row_ptr.shape[-1] - 1
    dist, frontier = multi_source_state(v, sources, INF)
    return run_distributed(stacked_g, mesh, ops.SSSP_RELAX, dist, frontier,
                           cfg, max_rounds=max_rounds,
                           collect_stats=collect_stats, sync=sync,
                           meta=meta, mode=mode)


def bfs_batch_distributed(stacked_g: Graph, mesh, sources,
                          cfg: BalancerConfig = BalancerConfig(),
                          max_rounds: int = 10_000,
                          collect_stats: bool = False,
                          sync: str = "replicated",
                          meta: PartitionMeta | None = None,
                          mode: str = "host"):
    """Batched multi-source BFS (see :func:`sssp_batch_distributed`)."""
    v = stacked_g.row_ptr.shape[-1] - 1
    lvl, frontier = multi_source_state(v, sources, INF)
    return run_distributed(stacked_g, mesh, ops.BFS_HOP, lvl, frontier,
                           cfg, max_rounds=max_rounds,
                           collect_stats=collect_stats, sync=sync,
                           meta=meta, mode=mode)


def cc_distributed(stacked_g: Graph, mesh,
                   cfg: BalancerConfig = BalancerConfig(),
                   max_rounds: int = 10_000,
                   collect_stats: bool = False,
                   sync: str = "replicated",
                   meta: PartitionMeta | None = None,
                   mode: str = "host"):
    """Distributed connected components by min-label propagation
    (expects a symmetrized input; see :func:`sssp_distributed` for the
    ``sync`` substrates)."""
    v = stacked_g.row_ptr.shape[-1] - 1
    comp = jnp.arange(v, dtype=jnp.int32)
    frontier = jnp.ones((v,), bool)
    return run_distributed(stacked_g, mesh, ops.CC_MIN, comp, frontier,
                           cfg, max_rounds=max_rounds,
                           collect_stats=collect_stats, sync=sync,
                           meta=meta, mode=mode)


def kcore_distributed(stacked_g: Graph, mesh, k: int,
                      cfg: BalancerConfig = BalancerConfig(),
                      max_rounds: int = 10_000,
                      collect_stats: bool = False,
                      sync: str = "replicated",
                      meta: PartitionMeta | None = None,
                      mode: str = "host"):
    """Distributed k-core over a partitioned *symmetrized* graph.

    Degrees only decrease, so "dead" (< k) is monotone and the
    data-driven loop is exactly :func:`run_distributed` with the
    newly-crossed-the-threshold frontier rule; each dead vertex pushes
    its -1 decrements once, through the delta sync (add combiner).
    Returns in_core labels (1 = in the k-core), like the single-device
    driver.
    """
    rp = stacked_g.row_ptr
    deg = jnp.sum(rp[:, 1:] - rp[:, :-1], axis=0).astype(jnp.int32)
    frontier = (deg < k) & (deg > 0)
    out = run_distributed(
        stacked_g, mesh, ops.KCORE_DEC, deg, frontier, cfg,
        next_frontier=lambda old, new, f: (new < k) & (old >= k),
        sync_delta=True, max_rounds=max_rounds,
        collect_stats=collect_stats, sync=sync, meta=meta, mode=mode)
    labels, rest = out[0], out[1:]
    in_core = (labels >= k).astype(jnp.int32)
    return (in_core,) + rest


@partial(jax.jit, static_argnames=("damping",))
def _pr_update(rank, inv_out, sink, acc, damping: float):
    """Replicated PageRank's post-round rank update + residual as one
    shared jitted subgraph: the host loop calls it between dispatches,
    the fused while_loop inlines it — same fusion decisions both ways,
    so the f32 rounding (FMA contraction of the damping update) is
    bitwise-identical across modes."""
    v = rank.shape[0]
    dangling = jnp.sum(jnp.where(sink, rank, 0.0))
    new_rank = (1.0 - damping) / v + damping * (acc + dangling / v)
    delta = jnp.max(jnp.abs(new_rank - rank))
    return new_rank, delta


def pagerank_distributed(stacked_rg: Graph, mesh, out_degrees,
                         damping: float = 0.85, tol: float = 1e-6,
                         cfg: BalancerConfig = BalancerConfig(),
                         max_rounds: int = 1000,
                         collect_stats: bool = False,
                         sync: str = "replicated",
                         meta: PartitionMeta | None = None,
                         mode: str = "host"):
    """stacked_rg: partitioned *reverse* graph (pull traverses
    in-edges).  Dangling vertices (out-degree 0) redistribute their
    rank mass uniformly each round, matching the single-device
    :func:`repro.core.apps.drivers.pagerank` exactly (under the mirror
    substrate the dangling sum is reduced over owned master ranges via
    the ``global_of`` hook — exact and free of extra host traffic).
    ``mode="fused"`` moves the whole power iteration — including the
    residual check that otherwise blocks the host every round — into
    one ``lax.while_loop`` inside ``shard_map``."""
    _require_push_direction(cfg)
    _require_meta(meta, sync)
    # config-time codec/operator pairing check (quantize forbids float
    # rank payloads, and PR_PULL declares no narrowing anyway)
    wirecodec.get_codec(cfg.wire, ops.PR_PULL, jnp.float32)
    if mode not in ("host", "fused"):
        raise ValueError(f"unknown distributed mode {mode!r} "
                         "(host|fused)")
    if mode == "fused" and collect_stats:
        raise ValueError("mode='fused' runs with collect_stats=False "
                         "(per-round stats need the per-round host "
                         "boundary)")
    v = stacked_rg.row_ptr.shape[-1] - 1
    outdeg = out_degrees.astype(jnp.float32)
    inv_out = jnp.where(outdeg > 0, 1.0 / jnp.maximum(outdeg, 1.0), 0.0)
    sink = outdeg == 0
    rank = jnp.full((v,), 1.0 / v, jnp.float32)
    frontier = jnp.ones((v,), bool)
    if sync == "mirror":
        # topology-driven: full frontier every round, per-round rank
        # update as post_sync, convergence via the owned-entry residual
        return _run_mirror(
            stacked_rg, mesh, ops.PR_PULL, rank, frontier, cfg,
            values_of=lambda r: r * inv_out,
            next_frontier=lambda old, new, f: f,
            sync_delta=True, max_rounds=max_rounds,
            collect_stats=collect_stats, meta=meta,
            post_sync=lambda lab, acc, dang: (
                (1.0 - damping) / v + damping * (acc + dang / v)),
            global_of=lambda lab, owned: jnp.sum(
                jnp.where(owned[None] & sink[None], lab, 0.0)),
            tol=tol, mode=mode)
    if mode == "fused":
        def trav_fn(sg: Graph, rank, inv_out, sink):
            g = Graph(row_ptr=sg.row_ptr[0], col_idx=sg.col_idx[0],
                      edge_w=sg.edge_w[0])
            fr = jnp.ones((v,), bool)

            def cond(carry):
                r, rank, delta = carry
                return (r < max_rounds) & (delta >= tol)

            def body(carry):
                r, rank, delta = carry
                contrib = rank * inv_out
                acc = relax_spmd(g, contrib,
                                 jnp.zeros((v,), jnp.float32), fr,
                                 cfg, ops.PR_PULL)
                acc = _sync(acc, "add")
                new_rank, delta = _pr_update(rank, inv_out, sink, acc,
                                             float(damping))
                return r + 1, new_rank, delta

            r, rank, _ = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), rank, jnp.float32(jnp.inf)))
            return rank, r

        gspec = Graph(row_ptr=P("dev"), col_idx=P("dev"),
                      edge_w=P("dev"))
        fn = jax.jit(jax.shard_map(trav_fn, mesh=mesh,
                                   in_specs=(gspec, P(), P(), P()),
                                   out_specs=(P(), P()),
                                   check_vma=False))
        t0 = time.perf_counter()
        rank, r = fn(stacked_rg, rank, inv_out, sink)
        jax.block_until_ready(rank)
        return rank, int(r), time.perf_counter() - t0
    round_fn = make_round_fn(mesh, cfg, ops.PR_PULL, sync_delta=True,
                             collect_stats=collect_stats)
    rounds = 0
    stats = [] if collect_stats else None
    t0 = time.perf_counter()
    while rounds < max_rounds:
        contrib = rank * inv_out
        out = round_fn(stacked_rg, contrib, jnp.zeros((v,), jnp.float32),
                       frontier)
        if collect_stats:
            acc, st = out
            stats.append(stats_per_device(st))
        else:
            acc = out
        new_rank, delta_dev = _pr_update(rank, inv_out, sink, acc,
                                         float(damping))
        delta = float(delta_dev)
        _note_host_transfer()      # the residual check blocks
        rank = new_rank
        rounds += 1
        if delta < tol:
            break
    total = time.perf_counter() - t0
    if collect_stats:
        return rank, rounds, total, stats
    return rank, rounds, total

"""Gradient-sync entrypoints over the composable collective pipeline.

The strategy implementations live in :mod:`repro.core.pipeline`: every named
strategy is a :class:`~repro.core.pipeline.CollectiveSpec` composing three
orthogonal protocols — a **Topology** (psum / ring / tree / bcube / TAR with
all_to_all or explicit round schedules, 1D or hierarchical 2D pod×data), a
**Transport** (``Reliable``, ``Lossy`` = the UBT drop model + stats,
``AdaptiveTransport`` = the §3.2 controllers picking next-step codec/incast),
and a **Codec** (``Identity``, ``Hadamard``, ``HTQuant`` shared-grid
quantization).  Codec stages call the kernel wrappers, which run Mosaic on
a TPU and the jnp forms elsewhere (``kernels.runtime``).  See DESIGN.md §3
for the layering and the strategy-author migration notes.

This module keeps the stable, config-driven surface:

  ``OptiReduceConfig`` / ``SyncContext``  — static knobs + per-step context
  ``sync_bucket``        — one bucket through the resolved spec
  ``sync_packed``        — the fused engine core on a pre-packed arena,
                           bucket-major (B, R, L) or flat (B, bucket_elems):
                           scan / vmap / the stage-skewed ``pipelined``
                           software schedule (in place over the arena)
  ``sync_pytree``        — pack -> ``sync_packed`` -> unpack over a static
                           :class:`BucketPlan`
  ``sync_pytree_unfused``— the seed bucketing loop, kept as the bitwise
                           parity oracle for the ``parity`` test suite
  ``reduce_scatter_axis``— the FSDP/ZeRO reduction (deferred stage 2),
                           resolved to a TAR spec with the rs-specific codec

Built-in strategy names (``strategies()``):

  psum        — XLA's native all-reduce (what a stock JAX program does)
  gloo_ring   — explicit ring reduce-scatter + all-gather (Gloo Ring)
  nccl_tree   — recursive halving-doubling (NCCL Tree stand-in)
  bcube       — Gloo BCube
  tar_tcp     — Transpose AllReduce, reliable (paper's TAR+TCP baseline)
  tar_rounds  — TAR with the paper's explicit round schedule (ppermute form)
  optireduce  — TAR + UBT drop model + compensated reduce + randomized HT
  optireduce_2d — hierarchical 2D TAR across (pod, data) for multi-pod meshes
  optireduce_q — TAR with THC-quantized shard exchange (beyond-paper)
  optireduce_rounds / tar_rounds_q / ring_ht — registered cross-product
                compositions (see pipeline.register_strategy)

Drops are applied on stage 1 only by default (the aggregated shard is then
authoritative and every replica receives identical bytes from the broadcast,
keeping replicas consistent; see DESIGN §2).

``OptiReduceConfig.active_peers`` (set by the runtime control plane's
``SyncPolicy``, see repro/runtime/ and DESIGN §5) degrades participation:
a proper subset excludes the ejected peers' contributions — via the masked
compensated mean on a2a schedules, via round schedules regenerated over the
active peers' virtual ring on rounds/ring schedules — while ejected peers
still receive every reduced bucket.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import scopes

from .bucket_plan import BucketPlan, bucket_keys
from .pipeline import (CollectiveSpec, Hadamard, HTQuant, Identity, Lossy,
                       OptiReduceConfig, Reliable, SyncContext, TarTopology,
                       Topology, arena_lane, register_strategy, resolve_spec,
                       strategy_names)

__all__ = [
    "OptiReduceConfig", "SyncContext", "CollectiveSpec", "register_strategy",
    "resolve_spec", "strategies", "sync_bucket", "sync_packed", "sync_pytree",
    "sync_pytree_unfused", "reduce_scatter_axis",
]


def strategies() -> tuple[str, ...]:
    """Registered strategy names (see pipeline.register_strategy)."""
    return strategy_names()


def sync_bucket(bucket: jnp.ndarray, ctx: SyncContext,
                spec: CollectiveSpec | None = None) -> jnp.ndarray:
    """Reduce one bucket (flat, or a tile-dense (R, L) slab) to its
    (approximate) DP mean, in the bucket's shape.

    Resolves ``ctx.cfg.strategy`` through the spec registry unless an
    explicit ``spec`` is given (e.g. an unregistered composition or one
    holding a stateful :class:`~repro.core.pipeline.AdaptiveTransport`).
    """
    if spec is None:
        spec = resolve_spec(ctx.cfg)
    return spec.all_reduce(bucket, ctx)


def sync_pytree(grads, ctx: SyncContext, *, bucket_elems: int = 6_553_600,
                plan: BucketPlan | None = None, mode: str = "scan",
                spec: CollectiveSpec | None = None):
    """Sync a gradient pytree via fixed-size buckets (PyTorch uses 25 MB
    buckets == 6.55M fp32 entries; same default here).

    Buckets follow a static :class:`BucketPlan` (leaf->bucket layout from
    the treedef/shapes, computed once — pass ``plan`` to reuse it): leaves
    are packed into one batch — the tile-dense ``(B, R, L)`` arena at the
    spec's :func:`~repro.core.pipeline.arena_lane` where ``L`` divides the
    bucket, else ``(B, bucket_elems)`` — and the strategy
    pipeline runs as a single traced body — constant HLO size in B and no
    second full-gradient materialization. ``mode`` picks the schedule
    tradeoff (see :func:`sync_packed`); all modes are bitwise-identical to
    :func:`sync_pytree_unfused`.
    """
    if spec is None:
        spec = resolve_spec(ctx.cfg)
    if plan is None:
        plan = BucketPlan.for_tree(grads, bucket_elems,
                                   lane=arena_lane(spec, ctx.cfg))
    batch = plan.pack(grads)                         # plan.arena_shape
    return plan.unpack(sync_packed(batch, ctx, mode=mode, spec=spec))


def _sync_pipelined(batch, keys, ctx: SyncContext, spec: CollectiveSpec,
                    stale=None):
    """Stage-skewed software pipeline over the bucket axis (depth-2 skew).

    Iteration k *encodes* bucket k, *exchanges* bucket k-1, and *decodes*
    bucket k-2; the in-flight encoded/exchanged payloads ride in the loop
    carry, so within each traced step the exchange collectives of bucket
    k-1 have no data dependency on the encode/decode kernels of buckets
    k / k-2 and XLA's async collectives genuinely overlap with neighboring
    buckets' Pallas (or MXU-form) codec work.  The first/last two buckets
    run as an unrolled prologue/epilogue; with B <= 3 the steady-state
    window is empty and the whole schedule unrolls (the skew is deeper than
    the bucket count).

    The arena itself rides the carry and is updated in place: iteration k
    reads bucket k with a dynamic index and writes decoded bucket k-2 back
    with a ``dynamic_update_slice`` into the same buffer.  Every write is
    two buckets behind the read, so no bucket is overwritten before it is
    encoded, and no stacked outputs or final concatenation exist.

    Per-bucket computation is the same encode/exchange/decode composition
    ``CollectiveSpec.all_reduce`` runs, so results are bitwise-identical to
    the scan/vmap modes and the unfused oracle (pinned by the ``parity``
    suite).  Returns ``(synced_batch, (dropped, total), recorded)``.
    """
    cfg = ctx.cfg
    nbuckets = batch.shape[0]
    shape = batch.shape[1:]
    recorded = False

    def enc(bucket, key, stale_b=None):
        # the stale cache enters at encode time (re-encoded under the
        # bucket's key) and then rides the stage state through the skew —
        # the per-bucket pairing survives because encode/exchange/decode of
        # one bucket share the carried tuple, not the loop index
        sctx = SyncContext(cfg=cfg, key=key, stale=stale_b)
        with jax.named_scope(scopes.ENCODE):
            return (key, spec.encode_stage(bucket, sctx))

    def exch(state):
        nonlocal recorded
        key, inner = state
        stats: dict = {}
        sctx = SyncContext(cfg=cfg, key=key, stats=stats)
        with jax.named_scope(scopes.EXCHANGE):
            out = spec.exchange_stage(inner, sctx)
        recorded = recorded or ("total" in stats)
        return ((key, out), (stats.get("dropped", jnp.zeros(())),
                             stats.get("total", jnp.zeros(()))))

    def dec(state):
        key, inner = state
        sctx = SyncContext(cfg=cfg, key=key)
        with jax.named_scope(scopes.DECODE):
            return spec.decode_stage(inner, shape, sctx)

    def at(x, k):
        return None if x is None else jax.lax.dynamic_index_in_dim(
            x, k, 0, keepdims=False)

    def put(arena, k, bucket):
        # the barrier keeps the decode kernel a custom call of its own: the
        # TPU compiler would otherwise fuse it into the update, and that
        # fusion takes the kernel's name, which the trace's per-kernel
        # accounting (chipbench/codec_kernels.py) reads as the kernel
        return jax.lax.dynamic_update_index_in_dim(
            arena, jax.lax.optimization_barrier(bucket), k, 0)

    arena = batch
    dropped = total = jnp.zeros(())
    if nbuckets <= 3:
        # fully unrolled: prologue/epilogue swallow the steady-state window
        enc_live: dict = {}
        exch_live: dict = {}
        for it in range(nbuckets + 2):
            if it < nbuckets:
                enc_live[it] = enc(arena[it], keys[it],
                                   None if stale is None else stale[it])
            if 0 <= it - 1 < nbuckets:
                exch_live[it - 1], (d, t) = exch(enc_live.pop(it - 1))
                dropped, total = dropped + d, total + t
            if 0 <= it - 2 < nbuckets:
                arena = put(arena, it - 2, dec(exch_live.pop(it - 2)))
        return arena, (dropped, total), recorded

    # prologue: fill the two pipeline registers
    e_carry = enc(arena[0], keys[0], None if stale is None else stale[0])
    e_next = enc(arena[1], keys[1], None if stale is None else stale[1])
    x_carry, (d, t) = exch(e_carry)
    dropped, total = dropped + d, total + t

    def body(k, carry):
        (cd, ct), e_prev, x_prev, arena = carry
        e_k = enc(at(arena, k), at(keys, k), at(stale, k))  # encode k
        x_k, (d, t) = exch(e_prev)                          # exchange k-1
        arena = put(arena, k - 2, dec(x_prev))              # decode k-2
        return ((cd + d, ct + t), e_k, x_k, arena)

    (d2, t2), e_last, x_last, arena = jax.lax.fori_loop(
        2, nbuckets, body,
        ((jnp.zeros(()), jnp.zeros(())), e_next, x_carry, arena))
    dropped, total = dropped + d2, total + t2

    # epilogue: drain the registers for the last two buckets
    x_fin, (d, t) = exch(e_last)
    dropped, total = dropped + d, total + t
    arena = put(arena, nbuckets - 2, dec(x_last))
    arena = put(arena, nbuckets - 1, dec(x_fin))
    return arena, (dropped, total), recorded


def sync_packed(batch: jnp.ndarray, ctx: SyncContext, *, mode: str = "scan",
                spec: CollectiveSpec | None = None,
                stale: jnp.ndarray | None = None) -> jnp.ndarray:
    """Sync an already-packed batch — the bucket-major ``(B, R, L)`` arena
    or a flat ``(B, bucket_elems)`` one; the result has its shape — the
    engine core behind :func:`sync_pytree`, exposed so the trainer's packed
    gradient arena can feed its accumulator straight in (no pack/unpack HBM
    passes around the sync).

    ``mode``:
      ``'scan'``      one ``lax.scan``'d strategy body; buckets strictly
                      serialized (smallest program).
      ``'vmap'``      vectorized over the bucket axis; collectives batched/
                      concurrent like the seed's unrolled loop.
      ``'pipelined'`` stage-skewed software pipeline: iteration k encodes
                      bucket k, exchanges bucket k-1, decodes bucket k-2,
                      so the exchange collectives overlap neighboring
                      buckets' encode/decode kernels (depth-2 skew with
                      unrolled prologue/epilogue), writing each decoded
                      bucket back into the arena in place.
    All modes are bitwise-identical per bucket (same stage composition).
    Per-bucket PRNG keys are ``fold_in(ctx.key, bucket_index)``, the seed
    loop's derivation.

    ``stale`` (optional, same shape as ``batch``): the previous step's
    decoded arena, threaded per-bucket into the stage pipeline as the
    cross-step prediction cache for a StaleFill recovery codec (DESIGN §8).
    ``None`` — the default, and the only value when recovery is off —
    leaves every code path byte-identical to the seed engine.
    """
    if mode not in ("scan", "vmap", "pipelined"):
        raise ValueError(f"unknown sync mode {mode!r}")
    if spec is None:
        spec = resolve_spec(ctx.cfg)
    nbuckets = batch.shape[0]
    keys = bucket_keys(ctx.key, nbuckets)
    recorded = False

    def one_bucket(bucket, key, stale_b=None):
        nonlocal recorded
        stats: dict = {}
        out = sync_bucket(bucket, SyncContext(cfg=ctx.cfg, key=key,
                                              stats=stats, stale=stale_b),
                          spec=spec)
        recorded = recorded or ("total" in stats)
        return out, (stats.get("dropped", jnp.zeros(())),
                     stats.get("total", jnp.zeros(())))

    if mode == "pipelined" and nbuckets > 1:
        # capability check up front: a Topology that only overrides
        # all_reduce (the PR-2 protocol) cannot be stage-skewed, and the
        # error should say so rather than surface from deep in the trace
        if type(spec.topology).encode_stage is Topology.encode_stage:
            raise NotImplementedError(
                f"mode='pipelined' needs the encode/exchange/decode stage "
                f"callables; topology {type(spec.topology).__name__} does "
                "not implement them (override the three stages — "
                "all_reduce alone only supports mode='scan'/'vmap')")
        synced, (dropped, total), recorded = _sync_pipelined(
            batch, keys, ctx, spec, stale)
    elif nbuckets == 1:
        synced, (dropped, total) = one_bucket(
            batch[0], keys[0], None if stale is None else stale[0])
        synced = synced[None]
    elif mode == "vmap":
        if stale is None:
            synced, (dropped, total) = jax.vmap(one_bucket)(batch, keys)
        else:
            synced, (dropped, total) = jax.vmap(one_bucket)(batch, keys,
                                                            stale)
        dropped, total = jnp.sum(dropped), jnp.sum(total)
    else:
        def body(carry, inp):
            out, (d, t) = one_bucket(inp[0], inp[1],
                                     inp[2] if stale is not None else None)
            return (carry[0] + d, carry[1] + t), out

        xs = (batch, keys) if stale is None else (batch, keys, stale)
        (dropped, total), synced = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(())), xs)
    if recorded:
        ctx.stats["dropped"] = ctx.stats.get("dropped", 0.0) + dropped
        ctx.stats["total"] = ctx.stats.get("total", 0.0) + total
    return synced


def sync_pytree_unfused(grads, ctx: SyncContext, *,
                        bucket_elems: int = 6_553_600):
    """The seed bucketing loop — kept as the parity oracle for
    :func:`sync_pytree`: flatten leaves, slice fixed-size buckets, trace the
    strategy pipeline once per bucket (O(#buckets) HLO)."""
    spec = resolve_spec(ctx.cfg)
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [leaf.size for leaf in leaves]
    flat = jnp.concatenate([leaf.reshape(-1).astype(jnp.float32)
                            for leaf in leaves])
    total = flat.shape[0]
    out_parts = []
    start = 0
    bucket_idx = 0
    while start < total:
        end = min(start + bucket_elems, total)
        sub = jax.random.fold_in(ctx.key, bucket_idx)
        bucket_ctx = SyncContext(cfg=ctx.cfg, key=sub, stats=ctx.stats)
        out_parts.append(sync_bucket(flat[start:end], bucket_ctx, spec=spec))
        start = end
        bucket_idx += 1
    synced = jnp.concatenate(out_parts) if len(out_parts) > 1 else out_parts[0]
    new_leaves = []
    off = 0
    for leaf, size in zip(leaves, sizes):
        new_leaves.append(synced[off:off + size].reshape(leaf.shape)
                          .astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, new_leaves)


def rs_spec(cfg: OptiReduceConfig, *, with_drops: bool = True) -> CollectiveSpec:
    """The reduce-scatter spec for a config: TAR stage 1 with the rs codec.

    Codec selection mirrors the bucketed strategies but with the rs knobs:
    ``rs_wire_bits`` picks the shared-grid quantizer (rotation implied —
    quantization needs it), otherwise the Hadamard rotation rides along only
    when drops are live (``with_drops`` and a positive ``drop_rate``).  The
    quantizer draws its stochastic-rounding noise from fold_in(key, 9) so
    the rs wire never correlates with the bucketed stage-1 draws.
    """
    quant = cfg.rs_wire_bits
    use_ht = (with_drops and cfg.use_hadamard and cfg.drop_rate > 0) or \
        bool(quant)                                     # quant needs rotation
    if quant:
        codec = HTQuant(bits=quant, noise_salt=9)
    elif use_ht:
        codec = Hadamard()
    else:
        codec = Identity()
    return CollectiveSpec(TarTopology(), Lossy() if with_drops else Reliable(),
                          codec)


def reduce_scatter_axis(g: jnp.ndarray, axis: str, dim: int,
                        ctx: SyncContext, *,
                        with_drops: bool = True) -> jnp.ndarray:
    """OptiReduce as a reduce-scatter: TAR stage 1 + compensated reduce on an
    arbitrary tensor, scattering ``dim`` over ``axis`` (the FSDP/ZeRO grad
    reduction — the all_gather at next use is the deferred stage 2).

    g: full tensor; returns the local shard (dim size / axis size) holding
    the drop-compensated mean over the axis peers.
    """
    return rs_spec(ctx.cfg, with_drops=with_drops).reduce_scatter(
        g, axis, dim, ctx)

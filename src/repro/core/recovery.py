"""Layered gradient-loss recovery beyond zero-fill (DESIGN §8).

The compensated masked mean (core/tar.py) renormalizes over the peers that
*did* arrive, but a wire span no sender delivered is zero-filled — and under
bursty loss (core/drops.py ``pattern="burst"``) whole runs of packets share
that fate. Three escalating mechanisms recover the lost mass, each a
composable option over the CollectiveSpec registry and each collapsing to
the exact seed trace when disabled (``cfg.recovery == "none"`` adds no ops):

  1. **Cross-step prediction** (:class:`StaleFill`, ``recovery="stale"``) —
     a per-bucket stale-value cache: every lost (sender, span) wire entry
     is filled with the *previous step's decoded bucket*, re-encoded under
     the current step's key, and the reduce takes the plain mean over all N
     (instead of renormalizing over survivors). Pure datapath — the cache
     rides the BucketPlan arena as extra scan carry state through the sync
     engine (``sync_packed(..., stale=...)``) and the codec's
     ``Encoded.stale`` slot through the stage pipeline.
  2. **Error feedback** (``recovery="ef"``; implies stale) — each rank
     accumulates the residual between its true contribution and what the
     stale fill applied in its stead (:func:`ef_residual`), and adds it to
     the next step's encode, so dropped gradient mass is eventually applied
     exactly once. State is threaded through ``train/trainer.py`` and
     checkpointed
     with params/optimizer state (``train/checkpoint.py``). Sound because
     the synthetic UBT masks are pure functions of (key, receiver) — every
     rank recomputes exactly which of its wire entries arrived.
  3. **Phase-aware loss budget** (``recovery="ef+budget"``) — a transport-
     layer controller (:class:`repro.core.ubt.LossBudget`) that tightens
     the acceptable drop fraction as training approaches convergence,
     stretching ``AdaptiveTimeout`` deadlines (and the wire peers'
     accept-or-extend decisions) when the observed loss overruns the
     phase's budget.

Scope: mechanisms 1–2 need a full-participation TAR schedule with a linear
codec (Identity/Hadamard) and the synthetic ``Lossy`` transport — the same
preconditions the wire bridge documents. Quantized codecs are rejected
(codes are not linearly decodable, so neither the stale re-encode nor the
residual split applies); so is ``active_peers`` degradation (the residual
reconstruction assumes the full sender set).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import drops as drops_lib
from . import tar as tar_lib
from .hadamard import ht_decode, ht_encode

MODES = ("none", "stale", "ef", "ef+budget")


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Parsed ``cfg.recovery`` knob: which mechanisms are armed."""
    mode: str = "none"

    @property
    def stale(self) -> bool:          # mechanisms are layered: ef ⇒ stale
        return self.mode in ("stale", "ef", "ef+budget")

    @property
    def ef(self) -> bool:
        return self.mode in ("ef", "ef+budget")

    @property
    def budget(self) -> bool:
        return self.mode == "ef+budget"

    @property
    def any(self) -> bool:
        return self.mode != "none"


def parse(mode: str) -> RecoveryPolicy:
    if mode not in MODES:
        raise ValueError(f"unknown recovery mode {mode!r}; one of {MODES}")
    return RecoveryPolicy(mode)


# ------------------------------------------- mechanism 1: stale-value fill
@dataclasses.dataclass(frozen=True)
class StaleFill:
    """Codec wrapper: lost wire spans are *predicted* from the previous
    step's decoded bucket (``ctx.stale``), re-encoded under this step's key.

    Where the compensated mean renormalizes over the senders that arrived
    (high variance when a burst takes out most of a span, zero when it takes
    all), this substitutes the stale value for every lost (sender, span)
    entry and takes the plain mean over all N — cross-step prediction:
    temporally-correlated gradients make last step's mean the best available
    estimate of a lost contribution.  Every entry — arrived or filled —
    then carries weight exactly 1/N, which is what makes the error-feedback
    residual split exact (``decode(m*w + (1-m)*w_stale)/N`` applied now +
    ``decode((1-m)*(w - w_stale))/N`` carried = the full ``bucket/N``
    contribution).

    Delegates every codec hook to ``inner``; only ``reduce`` changes, and
    with no stale cache or no mask the output is bitwise the inner codec's.
    ``inner`` must be linear: the stale bucket is re-encoded with the same
    key as the live data, so wire-space fill equals value-space fill rotated
    — the prediction stays meaningful under HT.
    """
    inner: object

    @property
    def linear(self) -> bool:
        return self.inner.linear

    def block(self, cfg) -> int:
        return self.inner.block(cfg)

    def encode(self, x, ctx, axis):
        enc = self.inner.encode(x, ctx, axis)
        if ctx.stale is None:
            return enc
        # the cache has the bucket's shape; ``x`` is the padded bucket in
        # the TAR view (flat, or rows of a tile-dense slab)
        stale = ctx.stale.astype(x.dtype)
        if x.ndim == 1:
            stale = stale.reshape(-1)
        pad = x.shape[0] - stale.shape[0]
        if pad < 0:
            raise ValueError(f"stale cache ({stale.shape[0]}) longer than "
                             f"the padded bucket ({x.shape[0]})")
        if pad:
            stale = jnp.pad(stale, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        enc_st = self.inner.encode(stale, ctx, axis)
        return dataclasses.replace(enc, stale=enc_st.data)

    def reduce(self, received, mask, shard_index, enc, ctx):
        if mask is None or enc.stale is None:
            return self.inner.reduce(received, mask, shard_index, enc, ctx)
        s = received.shape[1]          # shard elements, or dense rows
        stale_shard = jax.lax.dynamic_slice_in_dim(
            enc.stale, shard_index * s, s, 0).astype(received.dtype)
        filled = mask * received + (1.0 - mask) * stale_shard[None, :]
        ctx.stats["filled"] = ctx.stats.get("filled", 0.0) + \
            jnp.sum(1.0 - mask)
        # plain mean over all N: arrived entries weigh exactly 1/N (the EF
        # residual split relies on this), lost entries carry the prediction
        return self.inner.reduce(filled, None, shard_index, enc, ctx)

    def encode_shard(self, own, shard_index, enc, ctx):
        return self.inner.encode_shard(own, shard_index, enc, ctx)

    def decode_gathered(self, gathered, enc, ctx):
        return self.inner.decode_gathered(gathered, enc, ctx)

    def decode_values(self, vals, enc, ctx):
        return self.inner.decode_values(vals, enc, ctx)


def wrap_codec(codec, cfg):
    """Fold ``cfg.recovery`` into a strategy's codec (registry wiring).

    Returns the codec unchanged for ``recovery="none"`` — the spec, and
    therefore the traced program, is bitwise the seed one. Otherwise wraps
    it in :class:`StaleFill`, validating the composability preconditions.
    """
    pol = parse(cfg.recovery)
    if not pol.stale:
        return codec
    if not codec.linear:
        raise ValueError(
            f"recovery={cfg.recovery!r} needs a linear codec (Identity/"
            f"Hadamard); {type(codec).__name__} codes are not linearly "
            "decodable")
    if cfg.active_peers is not None:
        raise ValueError(
            "recovery does not compose with degraded participation "
            "(the residual/stale reconstruction assumes the full sender "
            "set); clear active_peers or set recovery='none'")
    return StaleFill(inner=codec)


# --------------------------------------- mechanism 2: error feedback (EF)
def sender_arrival_masks(cfg, key: jax.Array, n: int, s: int) -> jnp.ndarray:
    """(n, n*s) sender-major arrival matrix for one bucket's stage 1.

    Row ``i`` concatenates, over owners ``j = 0..n-1``, sender ``i``'s
    arrival mask for the span it sent to owner ``j`` — reconstructing every
    receiver's ``Lossy`` draw (``fold_in(key, j)``, self row forced) so any
    rank knows exactly which of its wire entries were applied this step.
    """
    def one(j):
        return drops_lib.make_mask(cfg.drop_pattern,
                                   jax.random.fold_in(key, j), n, s,
                                   rate=cfg.drop_rate,
                                   packet_elems=cfg.packet_elems,
                                   self_index=j)
    masks = jax.vmap(one)(jnp.arange(n))               # (owner, sender, s)
    return jnp.transpose(masks, (1, 0, 2)).reshape(n, n * s)


def ef_residual(bucket: jnp.ndarray, key: jax.Array, cfg, n: int,
                me, stale: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rank ``me``'s undelivered gradient mass for one bucket.

    ``bucket`` is the rank's contribution (gradient + carried residual);
    ``stale`` the cross-step prediction the receivers substituted for its
    lost wire entries (the previous step's decoded bucket). Returns
    ``decode((1 - arrival_me) * (encode(bucket) - encode(stale)))`` — the
    gap between what this rank owed and what the stale fill already applied
    in its stead, to be added to the next step's encode. Subtracting the
    fill is what makes the split exact for linear codecs:
    ``decode(m*w + (1-m)*w_stale) + residual == bucket`` (the
    mass-conservation property the hypothesis suite pins) — carrying the
    full lost mass on top of the fill would apply it twice.
    """
    if cfg.drop_rate <= 0.0:
        return jnp.zeros_like(bucket)
    basis = bucket if stale is None else bucket - stale.astype(bucket.dtype)
    block = cfg.hadamard_block if cfg.use_hadamard else 1
    # the flat shard geometry: a tile-dense (R, L) bucket's dense view cuts
    # the same shards (tar.tar_view), so its arrival masks are these
    x, length = tar_lib.pad_for_tar(basis.reshape(-1), n, block)
    s = x.shape[0] // n
    arrival = sender_arrival_masks(cfg, key, n, s)
    mine = jax.lax.dynamic_slice_in_dim(arrival, me, 1, 0)[0]
    if cfg.use_hadamard:
        w = ht_encode(x, key, block=block)
        resid = ht_decode((1.0 - mine) * w, key, block=block)
    else:
        resid = (1.0 - mine) * x
    return resid[:length].reshape(bucket.shape).astype(bucket.dtype)


def ef_residual_arena(arena: jnp.ndarray, step_key: jax.Array, cfg, n: int,
                      me, stale: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-bucket :func:`ef_residual` over a packed arena ((B, bucket_elems)
    or the tile-dense (B, R, L)), with the sync engine's per-bucket key
    derivation (fold_in by index). ``stale`` is the arena-shaped prediction
    cache the fill consumed *this* step (pre-update)."""
    from .bucket_plan import bucket_keys
    keys = bucket_keys(step_key, arena.shape[0])
    basis = arena if stale is None else arena - stale.astype(arena.dtype)
    return jax.vmap(lambda g, k: ef_residual(g, k, cfg, n, me))(basis, keys)


def init_state(policy: RecoveryPolicy, arena_shape: tuple[int, ...],
               n_dp: int = 1) -> dict:
    """Zero-initialized recovery state matching the trainer's threading.

    ``arena_shape`` is the packed arena's (``BucketPlan.arena_shape``:
    ``(B, E)`` or the tile-dense ``(B, R, L)``). ``stale`` — previous
    step's decoded arena, of that shape, replicated (every rank decodes
    identical buckets); ``ef`` — the carried residual, the same with a
    leading n_dp axis, sharded over the data axis (each data rank drops
    different wire spans). A zero stale cache makes step 0 behave exactly
    like zero-fill.
    """
    arena_shape = tuple(arena_shape)
    state = {}
    if policy.stale:
        state["stale"] = jnp.zeros(arena_shape, jnp.float32)
    if policy.ef:
        state["ef"] = jnp.zeros((n_dp,) + arena_shape, jnp.float32)
    return state

"""Composable collective pipeline: Topology × Transport × Codec (DESIGN §3).

OptiReduce is inherently layered — a transpose topology (TAR, §3.1), an
unreliable bounded transport (UBT, §3.2), and accuracy-preserving codecs
(randomized Hadamard §3.3, THC-style quantization) — and each axis varies
independently of the others (StragglAR swaps only the schedule; loss-bound
policies swap only the transport).  This module makes every gradient-sync
strategy a :class:`CollectiveSpec` composing three orthogonal protocols:

  Topology  — who exchanges with whom and in what schedule:
              :class:`PsumTopology` (XLA native), :class:`RingTopology`
              (ring / recursive-halving tree / bcube), :class:`TarTopology`
              (all_to_all or the paper's explicit round schedule;
              hierarchical 2D over a ``pod`` axis).
  Transport — what arrives: :class:`Reliable` (everything),
              :class:`Lossy` (the UBT drop-mask model + loss stats), and
              :class:`AdaptiveTransport` (the §3.2 controllers in the loop:
              observed loss feeds ``AdaptiveTimeout.hadamard_active`` and
              ``DynamicIncast`` to pick next-step codec/incast).
  Codec     — what goes on the wire: :class:`Identity`, :class:`Hadamard`
              (blockwise randomized HT), :class:`HTQuant` (shared-grid
              uniform stochastic quantization of the rotated blocks — the
              single implementation both the bucketed strategies and the
              FSDP ``reduce_scatter`` use).  Every codec stage is one
              kernel wrapper call; ``kernels.runtime`` picks the wrapper's
              implementation from the backend.

A strategy *name* resolves through a registry of named specs; new
compositions are one-liners::

    register_strategy("ring_ht",
                      CollectiveSpec(RingTopology("ring"), Reliable(),
                                     Hadamard()))

or, for cfg-dependent composition, a decorated factory::

    @register_strategy("my_strategy")
    def _spec(cfg):
        return CollectiveSpec(TarTopology(), Lossy(),
                              Hadamard() if cfg.use_hadamard else Identity())

``core.allreduce`` keeps the stable entrypoints (``sync_bucket``,
``sync_pytree``, ``reduce_scatter_axis``) as thin wrappers that resolve to
specs; every pre-existing strategy name is bitwise-identical to the seed
monolithic implementations (the ``parity`` pytest suite pins this against
the ``sync_pytree_unfused`` oracle).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from . import drops as drops_lib
from . import ring as ring_lib
from . import tar as tar_lib
from .hadamard import ht_decode, ht_encode, ht_encode_amax, ht_encode_quant
from .ubt import UbtState
from repro.kernels.dequant_reduce import dequant_masked_mean
from repro.kernels.masked_sum.masked_sum import LANES
from repro.kernels.quant import grid_quant
from repro.obs import scopes


# ------------------------------------------------------------- configuration
@dataclasses.dataclass(frozen=True)
class OptiReduceConfig:
    """Static (hashable) configuration for gradient sync."""
    strategy: str = "optireduce"
    data_axis: str = "data"
    pod_axis: str | None = None          # set for multi-pod meshes
    # UBT drop model (stand-in for timeouts/loss on a lossy fabric)
    drop_rate: float = 0.0
    drop_pattern: str = "tail"           # bernoulli | tail | straggler | burst
    packet_elems: int = 256
    # Hadamard transform
    use_hadamard: bool = True
    hadamard_block: int = 4096
    # read by nothing: kernels.runtime picks the codec implementation from
    # the backend. Kept only because the benchmark's workloads still pass it
    use_kernels: bool = False
    # safeguards
    skip_threshold: float = 0.10
    # round-form incast (rounds-scheduled topologies only)
    incast: int = 1
    # quantized TAR exchange (optireduce_q): THC-style shared-grid uniform
    # stochastic quantization of the HT-rotated shards — beyond-paper
    # optimization (the paper notes THC is orthogonal); cuts the wire bytes
    # of both TAR stages by 32/quant_bits
    quant_bits: int = 8
    # quantize the FSDP gradient reduce-scatter wire to this many bits
    # (0 = native dtype). Per-Hadamard-block grids, pmax-shared; §Perf H2.
    rs_wire_bits: int = 0
    # degraded participation (DESIGN §5): the SyncPolicy's active-peer set
    # on the data axis — None (or the full set) means everyone contributes.
    # A proper subset excludes the named-out peers' gradient contributions
    # (compensated by the masked-mean machinery) and, on round-scheduled
    # topologies, regenerates the round schedule over the active peers'
    # virtual ring.  Ejected peers still receive the reduced result (they
    # keep training, so probationary readmission is a pure policy flip).
    active_peers: tuple[int, ...] | None = None
    # straggler-proportional shard rebalancing (DESIGN §10): positive shard
    # units per *active* peer (aligned with the sorted active set; None =
    # uniform).  A slow-but-alive peer owns a smaller contiguous slice of
    # the bucket and fast peers absorb the remainder; a uniform tuple
    # normalizes to None so the full-participation trace stays bitwise
    # identical.  Rounds-scheduled TAR and kind='ring' topologies only.
    shard_weights: tuple[int, ...] | None = None
    # link-fault rewiring (DESIGN §10): directed (src, dst) edges declared
    # dead by the control plane's link-health tracker.  Round schedules
    # relay the affected pair through a live intermediate; the ring
    # topology reorders its virtual ring to avoid the edge — neither
    # endpoint is ejected.
    dead_links: tuple[tuple[int, int], ...] = ()
    # loss recovery beyond zero-fill (DESIGN §8, core/recovery.py):
    # none | stale (cross-step stale-value fill) | ef (stale + error-feedback
    # residual carry) | ef+budget (+ the phase-aware LossBudget controller).
    # "none" resolves to the exact seed spec — zero extra ops, bitwise
    # parity pinned by the parity suites.
    recovery: str = "none"


@dataclasses.dataclass
class SyncContext:
    """Per-step dynamic context threaded into the pipeline."""
    cfg: OptiReduceConfig
    key: jax.Array                        # replicated per-step PRNG key
    stats: dict = dataclasses.field(default_factory=dict)
    # previous step's decoded bucket (value space), set by the sync engine
    # when cross-step stale-fill recovery is armed; None otherwise
    stale: jnp.ndarray | None = None

    def data_axes(self) -> tuple[str, ...]:
        if self.cfg.pod_axis is not None:
            return (self.cfg.pod_axis, self.cfg.data_axis)
        return (self.cfg.data_axis,)

    def loss_fraction(self) -> jnp.ndarray:
        """Observed entry-loss fraction this step, pmean'd across receivers
        (what the §3.4 safeguards and the UBT controller monitor)."""
        if "total" not in self.stats:
            return jnp.zeros(())
        frac = self.stats["dropped"] / jnp.maximum(self.stats["total"], 1.0)
        return jax.lax.pmean(frac, self.data_axes())


def active_subset(cfg: OptiReduceConfig, n: int) -> tuple[int, ...] | None:
    """Normalized degraded-participation set for an ``n``-peer axis.

    Returns the sorted proper-subset tuple, or None when everyone
    participates — the full set normalizes to None so a policy naming all
    peers stays on the exact full-participation trace (what pins the
    bitwise-parity acceptance criterion).
    """
    ap = cfg.active_peers
    if ap is None:
        return None
    ap = tuple(sorted({int(p) for p in ap}))
    if not ap:
        raise ValueError("active_peers must name at least one peer")
    if ap[0] < 0 or ap[-1] >= n:
        raise ValueError(f"active_peers {ap} outside the {n}-peer axis")
    return None if len(ap) == n else ap


def weights_subset(cfg: OptiReduceConfig,
                   n_active: int) -> tuple[int, ...] | None:
    """Normalized shard-weight tuple for an ``n_active``-peer schedule.

    Returns the per-active-peer positive integer units, or None when the
    weights are uniform — a uniform tuple normalizes away so a policy
    assigning everyone equal units stays on the exact uniform-shard trace
    (the same discipline as :func:`active_subset`).
    """
    w = cfg.shard_weights
    if w is None:
        return None
    w = tuple(int(u) for u in w)
    if len(w) != n_active:
        raise ValueError(f"shard_weights {w} do not match the "
                         f"{n_active}-peer active set")
    if any(u < 1 for u in w):
        raise ValueError(f"shard_weights must be positive integers, got {w}")
    return None if all(u == w[0] for u in w) else w


def dead_link_set(cfg: OptiReduceConfig,
                  n: int) -> tuple[tuple[int, int], ...]:
    """Normalized (sorted, deduplicated) dead directed edges."""
    dl = cfg.dead_links or ()
    out = tuple(sorted({(int(s), int(d)) for (s, d) in dl}))
    for (s, d) in out:
        if not (0 <= s < n and 0 <= d < n) or s == d:
            raise ValueError(f"dead link {(s, d)} outside the {n}-peer axis")
    return out


def _mask_for(ctx: SyncContext, n: int, s: int, axis: str,
              self_index: jnp.ndarray | None = None) -> jnp.ndarray | None:
    """Receiver-specific (N, S) arrival mask for TAR stage 1.

    ``self_index`` overrides the row that is never dropped (a degraded
    round schedule indexes rows by virtual ring position, not peer id);
    the PRNG stream stays keyed on the absolute receiver id either way.
    """
    cfg = ctx.cfg
    if cfg.drop_rate <= 0.0:
        return None
    me = jax.lax.axis_index(axis)
    key = jax.random.fold_in(ctx.key, me)
    return drops_lib.make_mask(cfg.drop_pattern, key, n, s,
                               rate=cfg.drop_rate,
                               packet_elems=cfg.packet_elems,
                               self_index=me if self_index is None
                               else self_index)


def arena_lane(spec: "CollectiveSpec", cfg: OptiReduceConfig) -> int:
    """Lane width L of the sync engine's bucket-major ``(B, R, L)`` arena.

    The codec's block when it is whole 128-lane vregs — each arena row is
    then one codec row, so the kernels' ``(rows, block)`` view of a bucket
    is the arena's own slab, with no relayout — 128 for a codec without
    blocks, and 0 (the flat ``(B, E)`` view) for a block that does not fill
    whole vregs.  ``BucketPlan`` keeps it only where it divides the bucket
    length; the TAR stages keep the rows only where the padded shard is
    whole rows (:func:`~repro.core.tar.tar_view`)."""
    block = spec.codec.block(cfg)
    if block <= 1:
        return LANES
    return block if block % LANES == 0 else 0


# ------------------------------------------------------------------- codecs
@dataclasses.dataclass
class Encoded:
    """A codec's wire representation of one flat bucket.

    ``data`` is what travels (fp values or uint8 codes, flat); ``lo`` /
    ``step`` are the per-Hadamard-block quantization grids (pmax-shared
    across the whole DP group) a quantizing codec needs on the receive side.
    ``stale`` is the previous step's bucket re-encoded under this step's
    key — the cross-step prediction a StaleFill recovery codec substitutes
    for zero-arrival wire spans (None whenever recovery is off).
    """
    data: jnp.ndarray
    lo: jnp.ndarray | None = None
    step: jnp.ndarray | None = None
    stale: jnp.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class Codec:
    """Identity codec — also the base class defining the codec protocol.

    Hooks, in pipeline order:
      ``encode``          full-bucket encode before the stage-1 exchange
      ``reduce``          decode + drop-compensated mean of the (N, S)
                          received matrix for this node's shard
      ``encode_shard``    re-encode the aggregated shard for stage 2
      ``decode_gathered`` full-bucket decode after the stage-2 broadcast
      ``decode_values``   value-domain decode of one shard (the deferred-
                          stage-2 ``reduce_scatter`` path)
    ``linear`` marks codecs whose decode commutes with averaging (so they
    compose with topologies that reduce internally, e.g. ring).
    """
    linear: bool = dataclasses.field(default=True, init=False)

    def block(self, cfg: OptiReduceConfig) -> int:
        return 1

    def encode(self, x: jnp.ndarray, ctx: SyncContext, axis: str) -> Encoded:
        return Encoded(x)

    def reduce(self, received: jnp.ndarray, mask: jnp.ndarray | None,
               shard_index: jnp.ndarray, enc: Encoded,
               ctx: SyncContext) -> jnp.ndarray:
        return tar_lib.masked_mean(received, mask)

    def encode_shard(self, own: jnp.ndarray, shard_index: jnp.ndarray,
                     enc: Encoded, ctx: SyncContext) -> jnp.ndarray:
        return own

    def decode_gathered(self, gathered: jnp.ndarray, enc: Encoded,
                        ctx: SyncContext) -> jnp.ndarray:
        return gathered

    def decode_values(self, vals: jnp.ndarray, enc: Encoded,
                      ctx: SyncContext) -> jnp.ndarray:
        return vals


class Identity(Codec):
    """Raw wire bytes: no rotation, no compression."""


class Hadamard(Codec):
    """Blockwise randomized Hadamard transform (§3.3): linear, so drops
    spread across the block and the decoded mean stays unbiased."""

    def block(self, cfg: OptiReduceConfig) -> int:
        return cfg.hadamard_block

    def encode(self, x, ctx, axis):
        return Encoded(ht_encode(x, ctx.key, block=ctx.cfg.hadamard_block))

    def decode_gathered(self, gathered, enc, ctx):
        return ht_decode(gathered, ctx.key, block=ctx.cfg.hadamard_block)

    def decode_values(self, vals, enc, ctx):
        return ht_decode(vals, ctx.key, block=ctx.cfg.hadamard_block)


@dataclasses.dataclass(frozen=True)
class HTQuant(Codec):
    """Hadamard rotation + THC-style shared-grid uniform stochastic
    quantization (beyond-paper §Perf).

    Per-block [−amax_b, amax_b] grids are pmax'd across the *whole* DP group
    (the exchange axis plus every other configured data axis), so all nodes
    derive identical grids locally (no scale exchange) and the codes are
    homomorphic — the THC property, made cheap by the rotation (rotated
    blocks are near-Gaussian with comparable scales).

    Each quantization stage is one fused kernel: rotate+amax (grids),
    sign+FWHT+quantize (stage-1 codes — the rotated fp32 bucket never hits
    HBM), dequant+compensated-mean (receive), and grid-quantize (the
    stage-2 re-quantization of the aggregated shard).

    ``bits=None`` reads ``cfg.quant_bits``; ``reduce_scatter_axis`` passes
    ``bits=cfg.rs_wire_bits`` and its own ``noise_salt``.  Not ``linear``:
    decode does not commute with topologies that reduce internally.
    """
    bits: int | None = None
    noise_salt: int = 3        # stage-1 stochastic-rounding noise fold_in
    stage2_salt: int = 4       # stage-2 (broadcast) noise fold_in
    linear: bool = dataclasses.field(default=False, init=False)

    def _bits(self, cfg: OptiReduceConfig) -> int:
        return cfg.quant_bits if self.bits is None else self.bits

    def block(self, cfg: OptiReduceConfig) -> int:
        return cfg.hadamard_block

    def _grids(self, enc: Encoded, shard_index, nblk: int):
        lo = jax.lax.dynamic_slice_in_dim(enc.lo, shard_index * nblk, nblk, 0)
        step = jax.lax.dynamic_slice_in_dim(enc.step, shard_index * nblk,
                                            nblk, 0)
        return lo, step

    def local_amax(self, x, ctx):
        """Pre-``pmax`` half of :meth:`encode`: this peer's per-block amax
        of the rotated bucket, which is never materialized.

        The host wire datapath calls this, max-shares ``amax`` over the
        wire (an elementwise max is order-free, so the shared grids are
        bitwise identical to the fabric ``pmax``), then
        :meth:`encode_given_amax` with the same un-rotated bucket.
        """
        return ht_encode_amax(x, ctx.key, block=ctx.cfg.hadamard_block)

    def encode_given_amax(self, x, amax, ctx) -> Encoded:
        """Post-``pmax`` half of :meth:`encode`: rotate and quantize the
        un-rotated bucket onto the grids derived from the group-shared
        ``amax``."""
        cfg = ctx.cfg
        block = cfg.hadamard_block
        bits = self._bits(cfg)
        levels = (1 << bits) - 1
        amax = jnp.maximum(amax, 1e-12)
        step = 2.0 * amax / levels                      # (nblocks,)
        lo = -amax
        noise = jax.random.uniform(
            jax.random.fold_in(ctx.key, self.noise_salt),
            (x.size // block, block))
        codes = ht_encode_quant(x, ctx.key, noise, lo, step, block=block,
                                bits=bits)
        # the codes keep the bucket's layout: flat, or its (rows, L) slab
        return Encoded(codes.reshape(x.shape), lo=lo, step=step)

    def encode(self, x, ctx, axis):
        amax = self.local_amax(x, ctx)
        amax = jax.lax.pmax(amax, axis)
        for extra in ctx.data_axes():     # grids shared by the full DP group
            if extra != axis:
                amax = jax.lax.pmax(amax, extra)
        return self.encode_given_amax(x, amax, ctx)

    def reduce(self, received, mask, shard_index, enc, ctx):
        # received: (N, S) flat or (N, S/block, block) tile-dense codes
        block = ctx.cfg.hadamard_block
        nblk = received[0].size // block
        my_lo, my_step = self._grids(enc, shard_index, nblk)
        return dequant_masked_mean(received, my_lo, my_step, mask,
                                   block=block)

    def encode_shard(self, own, shard_index, enc, ctx):
        cfg = ctx.cfg
        block = cfg.hadamard_block
        nblk = own.size // block
        my_lo, my_step = self._grids(enc, shard_index, nblk)
        noise = jax.random.uniform(
            jax.random.fold_in(ctx.key, self.stage2_salt), (nblk, block))
        codes = grid_quant(own.reshape(nblk, block), noise, my_lo, my_step,
                           bits=self._bits(cfg))
        return codes.reshape(own.shape)

    def decode_gathered(self, gathered, enc, ctx):
        cfg = ctx.cfg
        block = cfg.hadamard_block
        out = (gathered.reshape(-1, block).astype(jnp.float32)
               * enc.step[:, None] + enc.lo[:, None]).reshape(gathered.shape)
        return ht_decode(out, ctx.key, block=block)

    def decode_values(self, vals, enc, ctx):
        return ht_decode(vals, ctx.key, block=ctx.cfg.hadamard_block)


# --------------------------------------------------------------- transports
class Reliable:
    """Everything arrives (TCP-class transports): no mask, no loss stats.

    ``payload``, when the topology can offer it, is the stage-1 wire state
    (the (n_shards, S) shard matrix about to be exchanged) — the synthetic
    transports ignore it; :class:`WireTransport` really sends those bytes
    over a host wire backend and masks by what arrived.
    """

    def arrival_mask(self, ctx: SyncContext, n: int, s: int, axis: str,
                     self_index: jnp.ndarray | None = None,
                     payload: jnp.ndarray | None = None
                     ) -> jnp.ndarray | None:
        return None

    def incast(self, ctx: SyncContext) -> int:
        return ctx.cfg.incast


class Lossy(Reliable):
    """UBT best-effort delivery: the drop-mask model (core/drops.py) decides
    per-receiver arrivals and the loss stats feed ``ctx.loss_fraction``."""

    def arrival_mask(self, ctx, n, s, axis, self_index=None, payload=None):
        mask = _mask_for(ctx, n, s, axis, self_index=self_index)
        if mask is not None:
            ctx.stats["dropped"] = ctx.stats.get("dropped", 0.0) + \
                jnp.sum(1.0 - mask)
            ctx.stats["total"] = ctx.stats.get("total", 0.0) + mask.size
        return mask


class AdaptiveTransport(Lossy):
    """§3.2 control plane in the sync loop: a :class:`Lossy` transport whose
    next-step recommendations come from the runtime :class:`ControlPlane`
    (the UBT controllers plus the straggler detector; see repro/runtime/).

    This is now a thin adapter — the controllers are host state (an XLA
    fabric cannot drop or time out), so the loop is: run a step, call
    ``observe(loss_frac, stage_time=...)``, and when it returns True (the
    policy moved) rebuild or cache-switch the step with ``apply(cfg)`` —
    Hadamard toggles on above the §3.2.1 2% threshold, ``DynamicIncast``
    advertises the next incast, and per-peer stage times (when the caller
    can measure them) feed persistent-straggler ejection.
    ``launch/train.py --adaptive`` wires the ControlPlane in directly.
    """

    def __init__(self, control=None, use_hadamard: bool | None = None, *,
                 state: UbtState | None = None):
        from repro.runtime import ControlPlane, StragglerDetector
        if control is None:
            if state is None:
                raise ValueError("AdaptiveTransport needs a ControlPlane "
                                 "(or a UbtState via state=)")
            control = ControlPlane(
                state=state,
                detector=StragglerDetector(state.incast.n_nodes))
        self.control = control
        # only an explicit argument overrides the controller's current
        # codec recommendation (a wrapped ControlPlane may already have
        # crossed the activation threshold)
        if use_hadamard is not None:
            self.control.use_hadamard = bool(use_hadamard)

    @classmethod
    def create(cls, n_nodes: int, **kw) -> "AdaptiveTransport":
        from repro.runtime import ControlPlane
        return cls(control=ControlPlane.create(n_nodes=n_nodes, **kw))

    @property
    def state(self) -> UbtState:
        return self.control.state

    @property
    def use_hadamard(self) -> bool:
        return self.control.use_hadamard

    @use_hadamard.setter
    def use_hadamard(self, value: bool) -> None:
        self.control.use_hadamard = bool(value)

    def incast(self, ctx: SyncContext | None = None) -> int:
        return self.control.policy().incast

    def observe(self, loss_frac: float, *, stage_time: float | None = None,
                timed_out: bool = False,
                peer_stage_times=None) -> bool:
        """Feed one step's observations; True if the recommendation moved."""
        from repro.runtime import StepTelemetry
        return self.control.observe(StepTelemetry(
            step=self.control.steps, loss_frac=float(loss_frac),
            timed_out=timed_out, step_time=stage_time,
            peer_stage_times=(None if peer_stage_times is None
                              else tuple(peer_stage_times))))

    def apply(self, cfg: OptiReduceConfig) -> OptiReduceConfig:
        """Fold the current recommendation into a sync config."""
        return self.control.apply(cfg)


class WireTransport(Lossy):
    """Arrival masks observed from a *real* host wire exchange (DESIGN §7).

    The in-JAX datapath keeps its XLA collectives (a TPU fabric cannot drop
    packets), but the stage-1 shard matrix is also really packetized and
    exchanged between host peers over a :mod:`repro.net` backend (in-memory
    loopback or localhost UDP).  The bridge is an ``io_callback``: each
    device hands its ``(n_shards, S)`` wire state plus its rank out to the
    host ring as a rendezvous-free *deposit*; the ring's worker thread
    runs each exchange off the XLA pool and the callback returns the
    observed arrival mask of the **previous** exchange (all-ones on the
    priming call; bitwise that bucket's own mask when the loss schedule
    ignores the exchange counter, an equal-distribution sample otherwise)
    — the same next-round-from-last-round structure as the
    §3.2 controllers, and deadlock-free under any XLA thunk scheduling
    (see ``HostRing.bridge_exchange`` for why both a callback barrier and
    an in-callback operand read can deadlock an oversubscribed host).
    The bytes cross the wire under the adaptive per-round deadline, and
    the mask — missing, late, duplicated, out-of-order packets already
    resolved — is bit-compatible with a ``core/drops.py`` mask; per
    peer/round stage times, timeout flags, and received fractions
    accumulate on the ring for the launcher to drain into
    :class:`StepTelemetry`.

    Caveats (see DESIGN §7): only stage-1 exchanges on full-participation
    TAR schedules offer the payload hook (degraded round schedules exchange
    over a virtual ring the host bridge does not model), and the callback
    must stay un-vmapped (``sync_packed`` modes scan/pipelined are fine —
    one exchange per bucket per step; ``mode="vmap"`` would batch the
    callback).  ``bridge`` is ``HostRing.bridge_exchange`` or any
    ``(rank, shards) -> mask`` callable.
    """

    def __init__(self, bridge):
        self._bridge = bridge

    def _host_mask(self, me, payload):
        # NOTE: the payload is deliberately NOT materialized here — this
        # runs on an XLA worker thread, and reading the operand can wait on
        # a ready-event whose producer is queued on that same (possibly
        # saturated) pool.  The ring's worker thread materializes it.
        import numpy as np
        return np.asarray(self._bridge(int(me), payload), np.float32)

    def arrival_mask(self, ctx, n, s, axis, self_index=None, payload=None):
        if payload is None or self_index is not None:
            raise NotImplementedError(
                "WireTransport needs the stage-1 payload hook of a "
                "full-participation TAR schedule (degraded virtual-ring "
                "rounds are not bridged to the host wire)")
        from jax.experimental import io_callback
        me = jax.lax.axis_index(axis)
        # The ring pairs deposits by a per-rank call counter, so each
        # rank's callbacks must execute in program order.  ordered=False is
        # sound here because the sync engine emits exactly ONE exchange
        # stage (one callback) per lax.scan iteration in both the scan and
        # pipelined schedules, and iterations are serialized by the loop
        # carry — there is never a second same-rank callback in flight to
        # reorder against.  (ordered=True would express this directly but
        # its token parameter breaks shard_map sharding propagation on this
        # XLA.)  Running several wire-bridged sync calls concurrently in
        # one program WOULD break the pairing; the launcher's fsdp/vmap/tp
        # guards rule those out.
        # the host wire needs each shard's bytes in order: flatten a
        # tile-dense payload to (n, S) here, and only here
        mask = io_callback(self._host_mask,
                           jax.ShapeDtypeStruct((n, s), jnp.float32),
                           me, payload.reshape(n, s), ordered=False)
        ctx.stats["dropped"] = ctx.stats.get("dropped", 0.0) + \
            jnp.sum(1.0 - mask)
        ctx.stats["total"] = ctx.stats.get("total", 0.0) + mask.size
        return mask


# --------------------------------------------------------------- topologies
class Topology:
    """Exchange-schedule protocol: owns padding, the collectives, and the
    placement of the codec/transport hooks between them.

    Execution is split into three stage callables so schedules can be
    software-pipelined across buckets (``sync_pytree(mode="pipelined")``):

      ``encode_stage``    bucket -> wire state (pad + codec encode; the
                          Pallas-kernel-heavy producer side, no collectives)
      ``exchange_stage``  wire state -> gathered state (every collective of
                          the schedule, plus the small per-shard reduce
                          between TAR's two stages)
      ``decode_stage``    gathered state -> synced bucket of the input's
                          shape (codec decode + the unpad slice; kernel-
                          heavy consumer side)

    A bucket is flat ``(E,)`` or a tile-dense ``(R, L)`` slab of the
    ``(B, R, L)`` arena; ``decode_stage`` takes the bucket's shape (or a
    flat bucket's length) and returns that shape.

    Stage state is a flat tuple of arrays-or-None with a bucket-independent
    structure, so it can ride in a ``lax.scan`` carry.  ``all_reduce`` is
    exactly the three stages composed — every mode (scan / vmap / pipelined /
    the unfused oracle) runs the identical per-bucket computation, which is
    what keeps them bitwise-interchangeable.
    """

    def validate(self, transport: Reliable, codec: Codec) -> None:
        pass

    def encode_stage(self, bucket: jnp.ndarray, transport: Reliable,
                     codec: Codec, ctx: SyncContext) -> tuple:
        raise NotImplementedError

    def exchange_stage(self, state: tuple, transport: Reliable,
                       codec: Codec, ctx: SyncContext) -> tuple:
        raise NotImplementedError

    def decode_stage(self, state: tuple, shape, transport: Reliable,
                     codec: Codec, ctx: SyncContext) -> jnp.ndarray:
        raise NotImplementedError

    def all_reduce(self, bucket: jnp.ndarray, transport: Reliable,
                   codec: Codec, ctx: SyncContext) -> jnp.ndarray:
        with jax.named_scope(scopes.ENCODE):
            state = self.encode_stage(bucket, transport, codec, ctx)
        with jax.named_scope(scopes.EXCHANGE):
            state = self.exchange_stage(state, transport, codec, ctx)
        with jax.named_scope(scopes.DECODE):
            return self.decode_stage(state, bucket.shape, transport, codec,
                                     ctx)

    def reduce_scatter(self, g, axis, dim, transport, codec, ctx):
        raise NotImplementedError(
            f"{type(self).__name__} has no reduce_scatter form")


class PsumTopology(Topology):
    """XLA's native all-reduce (what a stock JAX program does)."""

    def validate(self, transport, codec):
        if not isinstance(codec, Identity) or isinstance(transport, Lossy):
            raise ValueError("psum is XLA-native: it bypasses the codec and "
                             "cannot model drops (use a TAR topology)")

    def encode_stage(self, bucket, transport, codec, ctx):
        cfg = ctx.cfg
        n = jax.lax.axis_size(cfg.data_axis)
        if active_subset(cfg, n) is not None:
            raise ValueError(
                "psum is XLA-native: it cannot exclude peers — degraded "
                "participation needs a TAR or ring topology")
        if weights_subset(cfg, n) is not None or dead_link_set(cfg, n):
            raise ValueError(
                "psum is XLA-native: it cannot rebalance shards or route "
                "around links — use a rounds-scheduled TAR or ring topology")
        return (bucket,)

    def exchange_stage(self, state, transport, codec, ctx):
        (bucket,) = state
        return (jax.lax.pmean(bucket, ctx.data_axes()),)

    def decode_stage(self, state, shape, transport, codec, ctx):
        return state[0]


@dataclasses.dataclass(frozen=True)
class RingTopology(Topology):
    """Baseline schedules that reduce internally: Gloo Ring, recursive
    halving-doubling ("NCCL Tree"), Gloo BCube.  Compose with any *linear*
    codec (decode commutes with the internal averaging) and a reliable
    transport; a ``pod`` axis is folded in with a pmean."""
    kind: str = "ring"                   # ring | tree | bcube

    def __post_init__(self):
        if self.kind not in ("ring", "tree", "bcube"):
            raise ValueError(f"unknown ring topology kind {self.kind!r}")

    def validate(self, transport, codec):
        if isinstance(transport, Lossy):
            raise ValueError(
                f"{self.kind} reduces in-flight partial sums; the UBT drop "
                "model needs TAR's receive structure (Lossy -> TarTopology)")
        if not codec.linear:
            raise ValueError(
                f"codec {type(codec).__name__} does not commute with "
                f"{self.kind}'s internal reduction")

    def _active(self, cfg: OptiReduceConfig, n: int):
        active = active_subset(cfg, n)
        if active is not None and self.kind != "ring":
            raise ValueError(
                f"{self.kind} exchanges over a rigid power-of-base "
                "structure; degraded participation supports kind='ring' "
                "(or a TAR topology)")
        return active

    def _geometry(self, cfg: OptiReduceConfig, n: int):
        """(active, order, weights): the degraded set, the (possibly
        link-rewired) virtual ring order, and the per-position shard
        weights — None/None/None on the exact uniform full-participation
        trace (the bitwise-parity fast path).

        A failed (i -> j) edge reroutes the virtual ring around the edge
        (ring hops are all distance-1, so a ``tar.ring_order``-ed tuple
        avoids it completely) rather than ejecting j; weights follow their
        peer through the reordering.
        """
        active = self._active(cfg, n)
        part = active if active is not None else tuple(range(n))
        weights = weights_subset(cfg, len(part))
        dead = dead_link_set(cfg, n)
        if (weights is not None or dead) and self.kind != "ring":
            raise ValueError(
                f"{self.kind} exchanges over a rigid power-of-base "
                "structure; shard weights / dead links support kind='ring' "
                "(or a rounds-scheduled TAR topology)")
        order = tar_lib.ring_order(part, dead) if dead else part
        if weights is not None and order != part:
            weights = tuple(weights[part.index(p)] for p in order)
        if active is None and order == part and weights is None:
            return None, None, None
        return active, order, weights

    def encode_stage(self, bucket, transport, codec, ctx):
        cfg = ctx.cfg
        n = jax.lax.axis_size(cfg.data_axis)
        active, order, weights = self._geometry(cfg, n)
        if weights is not None:
            pad_n = sum(weights)
        elif order is not None:
            pad_n = len(order)
        else:
            pad_n = n
        # the ring schedules cut flat segments: a dense slab is flattened
        x, _ = tar_lib.pad_for_tar(bucket.reshape(-1), pad_n,
                                   codec.block(cfg))
        enc = codec.encode(x, ctx, cfg.data_axis)
        return (enc.data, enc.lo, enc.step)

    def exchange_stage(self, state, transport, codec, ctx):
        data, lo, step = state
        cfg = ctx.cfg
        n = jax.lax.axis_size(cfg.data_axis)
        active, order, weights = self._geometry(cfg, n)
        if order is not None:
            # virtual ring of active peers in link-avoiding order; ejected
            # peers' garbage output is replaced by the graft before it can
            # reach the pod reduction
            out = ring_lib.ring_allreduce(data, cfg.data_axis, active=order,
                                          weights=weights)
            if active is not None:
                out = tar_lib.graft_inactive(out, cfg.data_axis, active)
        elif self.kind == "ring":
            out = ring_lib.ring_allreduce(data, cfg.data_axis)
        elif self.kind == "tree":
            out = ring_lib.tree_allreduce(data, cfg.data_axis)
        else:
            base = 4 if n % 4 == 0 else 2
            out = ring_lib.bcube_allreduce(data, cfg.data_axis, base=base)
        if cfg.pod_axis is not None:
            out = jax.lax.pmean(out, cfg.pod_axis)
        return (out, lo, step)

    def decode_stage(self, state, shape, transport, codec, ctx):
        data, lo, step = state
        # the stage-1 encode output is gone by now — only the grids survive
        # the exchange, so the Encoded carries data=None rather than lying
        out = codec.decode_values(data, Encoded(None, lo=lo, step=step), ctx)
        return tar_lib.unpad_to(out, shape)


@dataclasses.dataclass(frozen=True)
class TarTopology(Topology):
    """Transpose AllReduce (§3.1): stage-1 shard exchange → codec reduce →
    stage-2 broadcast, with the codec/transport hooks between the stages.

    ``schedule``: ``'a2a'`` lowers the stages as tiled all_to_all/all_gather
    (the production path); ``'rounds'`` lowers the paper's explicit
    2*ceil((N-1)/I) ppermute round schedule, taking I from the transport
    (so :class:`AdaptiveTransport` drives it).
    ``outer``: how a configured ``pod`` axis joins — ``'tar'`` nests a TAR
    over the pods between the stages (§3.1.2 hierarchical 2D), ``'pmean'``
    folds them with a plain pmean (what a quantizing codec needs: values,
    not codes, cross the pod boundary).

    Degraded participation (``cfg.active_peers`` a proper subset, DESIGN
    §5): the ``'rounds'`` schedule is regenerated over the *virtual ring of
    active peers* — A = |active| shards, 2(A-1) rounds, ejected peers
    self-loop, plus ceil(E/A) graft rounds routing the result to ejected
    peers.  The ``'a2a'`` schedule keeps its N-shard collectives (an
    all_to_all cannot subset the axis) and instead zeroes ejected senders'
    rows in the arrival mask at *every* receiver — their contributions are
    excluded from the compensated mean, bitwise-identically on all
    replicas.  Either way the synced gradient is the mean over active
    contributions, and ejected peers still receive it.
    """
    schedule: str = "a2a"                # a2a | rounds
    outer: str = "tar"                   # tar | pmean

    def __post_init__(self):
        if self.schedule not in ("a2a", "rounds"):
            raise ValueError(f"unknown TAR schedule {self.schedule!r}")
        if self.outer not in ("tar", "pmean"):
            raise ValueError(f"unknown TAR outer mode {self.outer!r}")

    def _outer_reduce(self, own, codec, ctx):
        cfg = ctx.cfg
        g = jax.lax.axis_size(cfg.pod_axis)
        if g <= 1:
            return own
        if self.outer == "tar" and own.size % g == 0:
            # the pods' split is the flat one: dense rows only if they cut
            # into g whole-row chunks (the same elements)
            x = own if own.shape[0] % g == 0 else own.reshape(-1)
            return tar_lib.tar_allreduce(x, cfg.pod_axis).reshape(own.shape)
        return jax.lax.pmean(own, cfg.pod_axis)

    def _participation(self, cfg: OptiReduceConfig, n: int):
        """(active, n_shards, weights, dead): the rounds schedule shards
        over the active set — straggler-proportionally when ``weights`` is
        set — and relays around ``dead`` links; a2a keeps N uniform shards
        and excludes by mask."""
        active = active_subset(cfg, n)
        part = active if active is not None else tuple(range(n))
        weights = weights_subset(cfg, len(part))
        dead = dead_link_set(cfg, n)
        if (weights is not None or dead) and self.schedule != "rounds":
            raise ValueError(
                "the a2a TAR schedule lowers to all_to_all/all_gather, "
                "which can neither resize its tiles nor avoid an edge — "
                "use schedule='rounds' for shard_weights / dead_links")
        if active is not None and self.schedule == "rounds":
            return active, len(active), weights, dead
        return active, n, weights, dead

    @staticmethod
    def _check_weighted(cfg: OptiReduceConfig, codec) -> None:
        if not codec.linear:
            raise ValueError(
                "shard_weights require a linear codec: a quantizing codec "
                "grids the bucket by uniform shard geometry")
        if cfg.recovery != "none":
            raise ValueError(
                "shard_weights are incompatible with gradient recovery: "
                "stale-fill indexes the bucket by uniform shard geometry")

    def encode_stage(self, bucket, transport, codec, ctx):
        cfg = ctx.cfg
        n = jax.lax.axis_size(cfg.data_axis)
        _, n_shards, weights, _ = self._participation(cfg, n)
        if weights is not None:
            self._check_weighted(cfg, codec)
            # pad so the bucket cuts into sum(weights) block-aligned units
            n_shards = sum(weights)
        # tile-dense rows where the shards are whole rows; weighted shards'
        # unit rows take the flat view
        x = tar_lib.tar_view(bucket, n_shards, codec.block(cfg),
                             flat=weights is not None)
        if hasattr(codec, "local_amax"):
            # split encode (quantizing codec): emit only the pre-collective
            # half here; the grid pmax and the quantize ride the exchange
            # stage, so in the pipelined schedule bucket k's amax collective
            # overlaps bucket k-1's shard exchange instead of serializing
            # after this bucket's rotation.  (StaleFill never wraps a
            # non-linear codec, so local_amax is a safe discriminator.)
            return (x, None, None, None, codec.local_amax(x, ctx))
        enc = codec.encode(x, ctx, cfg.data_axis)
        # 4th slot: the re-encoded stale bucket a recovery codec may attach
        # (None otherwise — an empty pytree leaf, so the disabled path's
        # scan carries and HLO are unchanged); 5th slot: the pre-pmax amax
        # of a split (quantizing) encode
        return (enc.data, enc.lo, enc.step, enc.stale, None)

    def exchange_stage(self, state, transport, codec, ctx):
        data, lo, step, stale, amax = state
        cfg = ctx.cfg
        axis = cfg.data_axis
        if amax is not None:
            # deferred half of the split encode: share the grids across the
            # whole DP group (same collective order as Codec.encode keeps
            # the math bitwise-identical), then quantize
            amax = jax.lax.pmax(amax, axis)
            for extra in ctx.data_axes():
                if extra != axis:
                    amax = jax.lax.pmax(amax, extra)
            enc_q = codec.encode_given_amax(data, amax, ctx)
            data, lo, step = enc_q.data, enc_q.lo, enc_q.step
        n = jax.lax.axis_size(axis)
        active, n_shards, weights, dead = self._participation(cfg, n)
        enc = Encoded(data, lo=lo, step=step, stale=stale)
        if weights is not None:
            self._check_weighted(cfg, codec)
            plan = tar_lib.shard_plan(data.shape[0], weights,
                                      codec.block(cfg))
            if plan.padded != data.shape[0]:
                raise ValueError(
                    f"bucket length {data.shape[0]} not a multiple of "
                    f"sum(shard_weights)={sum(weights)} units")
            shards = tar_lib.weighted_rows(data, plan)
            s = plan.s_max
        else:
            plan = None
            shards = tar_lib.shard_rows(data, n_shards)
            s = shards[0].size
        if self.schedule == "rounds":
            received = tar_lib.tar_exchange_rounds(
                shards, axis, incast=transport.incast(ctx), active=active,
                dead_links=dead)
        else:
            received = jax.lax.all_to_all(shards, axis, split_axis=0,
                                          concat_axis=0, tiled=True)
        i = jax.lax.axis_index(axis)
        if self.schedule == "rounds" and (active is not None
                                          or plan is not None):
            # rows are in virtual-ring order; so are shard ownership and the
            # self row of the drop mask
            if active is not None:
                vpos, _ = tar_lib.peer_lookup(active, n)
                shard_index = jnp.take(vpos, i)
            else:
                shard_index = i        # weighted, full participation
            mask = transport.arrival_mask(ctx, n_shards, s, axis,
                                          self_index=shard_index)
        else:
            shard_index = i
            mask = transport.arrival_mask(ctx, n, s, axis, payload=shards)
        if mask is not None and received.ndim == 3:
            # transports mask (n, S) shards; the tile-dense shard view takes
            # the same values as (n, S/L, L)
            mask = mask.reshape(received.shape)
        if active is not None and self.schedule != "rounds":
            # a2a: exclude ejected senders' rows at EVERY receiver (the
            # ejected peer's own row included, so replicas agree) — the
            # masked-mean machinery compensates exactly like a drop
            _, is_active = tar_lib.peer_lookup(active, n)
            rows = is_active.reshape((n,) + (1,) * (received.ndim - 1))
            mask = jnp.broadcast_to(rows, received.shape) \
                if mask is None else mask * rows
        own = codec.reduce(received, mask, shard_index, enc, ctx)
        if cfg.pod_axis is not None:
            own = self._outer_reduce(own, codec, ctx)
        wire = codec.encode_shard(own, shard_index, enc, ctx)
        if self.schedule == "rounds":
            gathered = tar_lib.tar_broadcast_rounds(
                wire, axis, incast=transport.incast(ctx), active=active,
                dead_links=dead, plan=plan)
            if active is not None:
                gathered = tar_lib.graft_inactive(gathered, axis, active)
        else:
            gathered = jax.lax.all_gather(wire, axis, axis=0, tiled=True)
        return (gathered, lo, step, None, None)  # stale consumed in reduce

    def decode_stage(self, state, shape, transport, codec, ctx):
        data, lo, step, _, _ = state
        # only the quantization grids survive the exchange; data=None marks
        # the stage-1 encode output as unavailable at decode time
        out = codec.decode_gathered(data, Encoded(None, lo=lo, step=step),
                                    ctx)
        return tar_lib.unpad_to(out, shape)

    def reduce_scatter(self, g, axis, dim, transport, codec, ctx):
        """TAR stage 1 + compensated reduce on an arbitrary tensor,
        scattering ``dim`` over ``axis`` — the FSDP/ZeRO grad reduction;
        the all_gather at next use is the deferred stage 2."""
        cfg = ctx.cfg
        n = jax.lax.axis_size(axis)
        _active = active_subset(cfg, n)
        part_n = n if _active is None else len(_active)
        if weights_subset(cfg, part_n) is not None or dead_link_set(cfg, n):
            raise ValueError(
                "reduce_scatter lowers to all_to_all (the FSDP a2a form): "
                "shard_weights / dead_links need the rounds schedule")
        g2 = jnp.moveaxis(g, dim, 0)
        lead = g2.shape[0]
        rest = g2.shape[1:]
        assert lead % n == 0, (lead, n)
        # keep the wire dtype (bf16 grads stay bf16): halves collective
        # bytes and the per-layer transients; reductions accumulate in fp32
        rows = g2.reshape(n, -1)                       # row j -> shard j
        row_len = rows.shape[1]
        pad = (-row_len) % codec.block(cfg)
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)))
        enc = codec.encode(rows.reshape(-1), ctx, axis)
        shards = enc.data.reshape(n, -1)
        received = jax.lax.all_to_all(shards, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        mask = transport.arrival_mask(ctx, n, received.shape[1], axis,
                                      payload=shards)
        active = active_subset(cfg, n)
        if active is not None:           # FSDP reduction: same a2a exclusion
            _, is_active = tar_lib.peer_lookup(active, n)
            rows = is_active[:, None]
            mask = jnp.broadcast_to(rows, received.shape) if mask is None \
                else mask * rows
        i = jax.lax.axis_index(axis)
        own = codec.reduce(received, mask, i, enc, ctx)
        own = codec.decode_values(own, enc, ctx)
        if pad:
            own = own[:row_len]
        out = own.reshape((lead // n,) + rest)
        return jnp.moveaxis(out, 0, dim)


# ------------------------------------------------------------ spec + registry
@dataclasses.dataclass(frozen=True)
class CollectiveSpec:
    """One gradient-sync strategy = Topology × Transport × Codec."""
    topology: Topology
    transport: Reliable
    codec: Codec

    def __post_init__(self):
        self.topology.validate(self.transport, self.codec)

    def all_reduce(self, bucket: jnp.ndarray, ctx: SyncContext) -> jnp.ndarray:
        """Reduce one bucket (flat, or a tile-dense (R, L) slab) to its
        (approximate) DP mean, in the bucket's shape."""
        return self.topology.all_reduce(bucket, self.transport, self.codec,
                                        ctx)

    # stage callables for software-pipelined schedules (sync engine's
    # mode="pipelined"): encode -> exchange -> decode composes to all_reduce
    def encode_stage(self, bucket: jnp.ndarray, ctx: SyncContext) -> tuple:
        return self.topology.encode_stage(bucket, self.transport, self.codec,
                                          ctx)

    def exchange_stage(self, state: tuple, ctx: SyncContext) -> tuple:
        return self.topology.exchange_stage(state, self.transport,
                                            self.codec, ctx)

    def decode_stage(self, state: tuple, shape,
                     ctx: SyncContext) -> jnp.ndarray:
        return self.topology.decode_stage(state, shape, self.transport,
                                          self.codec, ctx)

    def reduce_scatter(self, g: jnp.ndarray, axis: str, dim: int,
                       ctx: SyncContext) -> jnp.ndarray:
        """Scatter ``dim`` over ``axis``, returning this node's reduced
        shard (the deferred-stage-2 / FSDP form)."""
        return self.topology.reduce_scatter(g, axis, dim, self.transport,
                                            self.codec, ctx)


_REGISTRY: dict[str, Callable[[OptiReduceConfig], CollectiveSpec]] = {}


def register_strategy(name: str, spec: CollectiveSpec | None = None):
    """Register a named strategy: either a spec instance
    (``register_strategy("x", spec)``) or, as a decorator, a factory
    ``cfg -> CollectiveSpec`` for cfg-dependent composition."""
    if spec is not None:
        _REGISTRY[name] = lambda cfg: spec
        return spec

    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


def strategy_names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def resolve_spec(cfg: OptiReduceConfig) -> CollectiveSpec:
    try:
        factory = _REGISTRY[cfg.strategy]
    except KeyError:
        raise ValueError(f"unknown strategy {cfg.strategy!r}; "
                         f"one of {strategy_names()}") from None
    return factory(cfg)


def _recovered(codec: Codec, cfg: OptiReduceConfig) -> Codec:
    """Registry wiring for ``cfg.recovery`` (DESIGN §8): fold the loss-
    recovery knob into a lossy strategy's codec. ``"none"`` returns the
    codec untouched without even importing the recovery module — the
    resolved spec, and the traced program, stay bitwise the seed ones."""
    if cfg.recovery == "none":
        return codec
    from . import recovery as recovery_lib
    return recovery_lib.wrap_codec(codec, cfg)


# ------------------------------------------------- the named strategy table
register_strategy("psum",
                  CollectiveSpec(PsumTopology(), Reliable(), Identity()))
register_strategy("gloo_ring",
                  CollectiveSpec(RingTopology("ring"), Reliable(), Identity()))
register_strategy("nccl_tree",
                  CollectiveSpec(RingTopology("tree"), Reliable(), Identity()))
register_strategy("bcube",
                  CollectiveSpec(RingTopology("bcube"), Reliable(),
                                 Identity()))
register_strategy("tar_tcp",
                  CollectiveSpec(TarTopology(), Reliable(), Identity()))
register_strategy("tar_rounds",
                  CollectiveSpec(TarTopology(schedule="rounds", outer="pmean"),
                                 Reliable(), Identity()))


@register_strategy("optireduce")
@register_strategy("optireduce_2d")   # pod_axis in cfg drives the 2D path
def _optireduce_spec(cfg: OptiReduceConfig) -> CollectiveSpec:
    codec = Hadamard() if cfg.use_hadamard else Identity()
    return CollectiveSpec(TarTopology(), Lossy(), _recovered(codec, cfg))


@register_strategy("optireduce_q")    # quantized exchange (beyond-paper)
def _optireduce_q_spec(cfg: OptiReduceConfig) -> CollectiveSpec:
    # _recovered rejects recovery over quantized codes (not linearly
    # decodable) instead of silently ignoring the knob
    return CollectiveSpec(TarTopology(outer="pmean"), Lossy(),
                          _recovered(HTQuant(), cfg))


# new cross-product compositions the layering opens (one-liners):
@register_strategy("optireduce_rounds")  # paper round schedule + drops + HT
def _optireduce_rounds_spec(cfg: OptiReduceConfig) -> CollectiveSpec:
    return CollectiveSpec(TarTopology(schedule="rounds", outer="pmean"),
                          Lossy(), _recovered(Hadamard(), cfg))


@register_strategy("tar_rounds_q")       # round schedule + THC quantization
def _tar_rounds_q_spec(cfg: OptiReduceConfig) -> CollectiveSpec:
    return CollectiveSpec(TarTopology(schedule="rounds", outer="pmean"),
                          Lossy(), _recovered(HTQuant(), cfg))
register_strategy("ring_ht",             # Gloo ring over rotated buckets
                  CollectiveSpec(RingTopology("ring"), Reliable(), Hadamard()))

"""Composable collective-pipeline API: registry semantics, spec validation,
codec/topology/transport protocol behavior, and the AdaptiveTransport
control loop. Multi-device oracle equivalence lives in
tests/test_pipeline_parity.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax.sharding import AxisType
from repro.core import (OptiReduceConfig, SyncContext, strategies,
                        sync_bucket)
from repro.core import pipeline as pl
from repro.core import tar as tar_lib
from repro.core.allreduce import rs_spec

SEED_STRATEGIES = ("psum", "gloo_ring", "nccl_tree", "bcube", "tar_tcp",
                   "tar_rounds", "optireduce", "optireduce_2d",
                   "optireduce_q")


def test_registry_covers_every_seed_strategy():
    names = strategies()
    for s in SEED_STRATEGIES:
        assert s in names, s
    # and the layering opened new cross-product compositions
    for s in ("optireduce_rounds", "tar_rounds_q", "ring_ht"):
        assert s in names, s


def test_resolve_unknown_strategy_raises_with_names():
    with pytest.raises(ValueError, match="unknown strategy"):
        pl.resolve_spec(OptiReduceConfig(strategy="nope"))


def test_stageless_topology_gets_descriptive_pipelined_error():
    """A Topology overriding only ``all_reduce`` (the PR-2 protocol) still
    works under scan/vmap, but mode='pipelined' needs the stage callables —
    and must say so instead of dying with a bare NotImplementedError deep
    in the schedule."""
    from repro.core.allreduce import sync_pytree

    class AllReduceOnly(pl.Topology):
        def all_reduce(self, bucket, transport, codec, ctx):
            return jax.lax.pmean(bucket, ctx.data_axes())

    spec = pl.CollectiveSpec(AllReduceOnly(), pl.Reliable(), pl.Identity())
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tree = {"g": jnp.ones((2048,))}

    def body(t, mode):
        ctx = SyncContext(cfg=OptiReduceConfig(), key=jax.random.PRNGKey(0))
        return sync_pytree(t, ctx, bucket_elems=1024, mode=mode, spec=spec)

    f = jax.shard_map(lambda t: body(t, "scan"), mesh=mesh,
                  in_specs=({"g": P()},), out_specs={"g": P()},
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(f(tree)["g"]),
                                  np.asarray(tree["g"]))
    with pytest.raises(NotImplementedError, match="pipelined.*AllReduceOnly"):
        jax.shard_map(lambda t: body(t, "pipelined"), mesh=mesh,
                  in_specs=({"g": P()},), out_specs={"g": P()},
                  check_vma=False)(tree)


def test_register_strategy_instance_and_decorator():
    spec = pl.CollectiveSpec(pl.RingTopology("tree"), pl.Reliable(),
                             pl.Hadamard())
    try:
        pl.register_strategy("_tmp_instance", spec)
        assert pl.resolve_spec(
            OptiReduceConfig(strategy="_tmp_instance")) is spec

        @pl.register_strategy("_tmp_factory")
        def _factory(cfg):
            return pl.CollectiveSpec(
                pl.TarTopology(), pl.Lossy(),
                pl.HTQuant() if cfg.quant_bits < 8 else pl.Identity())

        got = pl.resolve_spec(OptiReduceConfig(strategy="_tmp_factory",
                                               quant_bits=4))
        assert isinstance(got.codec, pl.HTQuant)
        got = pl.resolve_spec(OptiReduceConfig(strategy="_tmp_factory"))
        assert isinstance(got.codec, pl.Identity)
    finally:
        pl._REGISTRY.pop("_tmp_instance", None)
        pl._REGISTRY.pop("_tmp_factory", None)


def test_invalid_compositions_rejected_at_spec_time():
    # ring reduces partial sums in flight: the UBT drop model needs TAR
    with pytest.raises(ValueError, match="TarTopology"):
        pl.CollectiveSpec(pl.RingTopology("ring"), pl.Lossy(), pl.Identity())
    # a non-linear codec cannot commute with ring's internal reduction
    with pytest.raises(ValueError, match="commute"):
        pl.CollectiveSpec(pl.RingTopology("ring"), pl.Reliable(),
                          pl.HTQuant())
    # psum is XLA-native: no codec, no drops
    with pytest.raises(ValueError, match="psum"):
        pl.CollectiveSpec(pl.PsumTopology(), pl.Lossy(), pl.Identity())
    with pytest.raises(ValueError, match="unknown TAR schedule"):
        pl.TarTopology(schedule="carrier_pigeon")
    with pytest.raises(ValueError, match="unknown ring topology"):
        pl.RingTopology("mobius")


@pytest.mark.parametrize("strategy", SEED_STRATEGIES + (
    "optireduce_rounds", "tar_rounds_q", "ring_ht"))
def test_every_registered_spec_is_identity_at_dp1(strategy):
    """dp=1 degenerates every composition to (approximately) the identity —
    a coherence check of the whole Topology x Transport x Codec dispatch."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cfg = OptiReduceConfig(strategy=strategy, drop_rate=0.0,
                           hadamard_block=256)

    def body(x):
        return sync_bucket(x, SyncContext(cfg=cfg, key=jax.random.PRNGKey(0)))

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    x = jax.random.normal(jax.random.PRNGKey(1), (2048,))
    out = np.asarray(f(x))
    tol = 0.2 if "q" in strategy else 1e-4     # quantization error vs fp
    assert np.max(np.abs(out - np.asarray(x))) < tol


def test_masked_mean_is_public_and_matches_ref():
    from repro.kernels.masked_sum import masked_mean_ref
    key = jax.random.PRNGKey(0)
    received = jax.random.normal(key, (4, 512))
    mask = (jax.random.uniform(jax.random.fold_in(key, 1),
                               (4, 512)) > 0.2).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(tar_lib.masked_mean(received, None)),
        np.asarray(jnp.mean(received, axis=0)))
    np.testing.assert_array_equal(
        np.asarray(tar_lib.masked_mean(received, mask)),
        np.asarray(masked_mean_ref(received, mask)))
    assert not hasattr(tar_lib, "_reduce")     # the private form is gone


def test_rounds_split_composes_to_allreduce():
    """tar_exchange_rounds + mean + tar_broadcast_rounds == the one-shot
    tar_allreduce_rounds wrapper (single device: schedule degenerates)."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,))

    def a(v):
        return tar_lib.tar_allreduce_rounds(v, "data", incast=2)

    def b(v):
        rec = tar_lib.tar_exchange_rounds(v.reshape(1, -1), "data", incast=2)
        return tar_lib.tar_broadcast_rounds(jnp.mean(rec, 0), "data",
                                            incast=2)

    fa = jax.jit(jax.shard_map(a, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    fb = jax.jit(jax.shard_map(b, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    np.testing.assert_array_equal(np.asarray(fa(x)), np.asarray(fb(x)))


def test_rs_spec_codec_selection():
    cfg = OptiReduceConfig(drop_rate=0.0, rs_wire_bits=0)
    assert isinstance(rs_spec(cfg).codec, pl.Identity)
    cfg = OptiReduceConfig(drop_rate=0.05, use_hadamard=True)
    assert isinstance(rs_spec(cfg).codec, pl.Hadamard)
    assert isinstance(rs_spec(cfg, with_drops=False).codec, pl.Identity)
    cfg = OptiReduceConfig(drop_rate=0.0, rs_wire_bits=8)
    codec = rs_spec(cfg).codec
    assert isinstance(codec, pl.HTQuant)
    assert codec.bits == 8 and codec.noise_salt == 9
    assert isinstance(rs_spec(cfg).transport, pl.Lossy)
    assert isinstance(rs_spec(cfg, with_drops=False).transport, pl.Reliable)
    assert not isinstance(rs_spec(cfg, with_drops=False).transport, pl.Lossy)


def test_adaptive_transport_controllers():
    """§3.2 plumbing: loss-free rounds grow the advertised incast, loss
    halves it, and Hadamard activates above the 2% threshold (fn. 6)."""
    at = pl.AdaptiveTransport.create(n_nodes=8)
    assert at.incast() == 1 and not at.use_hadamard
    for _ in range(4):                       # clean rounds: I ramps
        at.observe(0.0, stage_time=0.1)
    assert at.incast() == 5
    assert not at.use_hadamard
    changed = at.observe(0.05)               # 5% loss: halve I, HT on
    assert changed
    assert at.incast() == 2
    assert at.use_hadamard
    cfg = OptiReduceConfig(strategy="optireduce_rounds", use_hadamard=False)
    applied = at.apply(cfg)
    assert applied.use_hadamard and applied.incast == 2
    assert at.observe(0.05) and at.incast() == 1
    assert not at.observe(0.05)              # I floors at 1, HT stays: no-op
    # and it is still a Lossy transport (drop masks + stats in the graph)
    assert isinstance(at, pl.Lossy)


def test_sync_pytree_accepts_explicit_spec():
    """An unregistered ad-hoc spec can drive sync_pytree directly."""
    from repro.core import sync_pytree
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    spec = pl.CollectiveSpec(pl.TarTopology(), pl.Reliable(), pl.Hadamard())
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (2048,))}
    cfg = OptiReduceConfig(strategy="does_not_matter_with_spec",
                           hadamard_block=256)

    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        return sync_pytree(t, ctx, bucket_elems=1024, spec=spec)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=({"w": P()},),
                          out_specs={"w": P()}, check_vma=False))
    out = f(tree)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(tree["w"]), atol=1e-4)

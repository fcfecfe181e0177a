"""Oracle-equivalence (``parity``) suite for the composable collective
pipeline: every registered strategy routed through CollectiveSpec must be
bitwise-identical between the fused ``sync_pytree`` engine — in all three
schedules, ``scan`` / ``vmap`` / the stage-skewed ``pipelined`` software
pipeline (including B=1/B=2, where the skew is deeper than the bucket
count) — and the ``sync_pytree_unfused`` seed-oracle loop on an 8-device
mesh, with drops, kernels, and quantization enabled — plus the 2D
(pod, data) reduce-scatter replica-consistency guarantees.

Runs in ONE subprocess (8 forced host devices, same pattern as
test_collectives.py); the parametrized tests assert per-strategy markers
from its cached output.  Select with ``-m parity``.
"""
import os
import subprocess
import sys

import pytest

# seed names + register_strategy'd cross-product compositions, with the
# knob set each is exercised under: (drop_rate, use_kernels)
STRATEGIES = [
    ("psum", 0.0, False),
    ("gloo_ring", 0.0, False),
    ("nccl_tree", 0.0, False),
    ("bcube", 0.0, False),
    ("tar_tcp", 0.0, True),
    ("tar_rounds", 0.0, False),
    ("optireduce", 0.1, True),
    ("optireduce_2d", 0.1, True),
    ("optireduce_q", 0.05, True),
    ("optireduce_rounds", 0.1, False),
    ("tar_rounds_q", 0.05, True),
    ("ring_ht", 0.0, True),
]

CHILD = r"""
import functools
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core import (OptiReduceConfig, SyncContext, sync_pytree,
                        sync_pytree_unfused)
from repro.core.allreduce import reduce_scatter_axis, rs_spec

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(0)
tree = {"w": jax.random.normal(key, (2, 1024)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (1024,)),
        "v": jax.random.normal(jax.random.fold_in(key, 2), (1024,))}
spec = jax.tree.map(lambda _: P(), tree)
sync_pipelined = functools.partial(sync_pytree, mode="pipelined")

def run(fn, cfg, bucket_elems=1024):
    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        out = fn(t, ctx, bucket_elems=bucket_elems)
        return out, ctx.loss_fraction()
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                          out_specs=(spec, P()), check_vma=False))
    return f(tree)

for item in %(strategies)r:
    strat, dr, uk = item
    cfg = OptiReduceConfig(strategy=strat, drop_rate=dr, hadamard_block=256,
                           use_kernels=uk, quant_bits=8, incast=3)
    ref, ref_frac = run(sync_pytree_unfused, cfg)
    out, out_frac = run(sync_pytree, cfg)
    for k in tree:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), \
            (strat, k)
    np.testing.assert_allclose(float(ref_frac), float(out_frac), atol=1e-6)
    print("PARITY %%s OK loss_frac=%%.4f" %% (strat, float(out_frac)))
    # every engine schedule must hit the same bits: vmap (batched
    # collectives) and the stage-skewed software pipeline (B=4 here:
    # prologue + a 2-step lax.scan steady state + epilogue all execute)
    for mode in ("vmap", "pipelined"):
        alt, alt_frac = run(functools.partial(sync_pytree, mode=mode), cfg)
        for k in tree:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(alt[k])), \
                (mode, strat, k)
        np.testing.assert_allclose(float(ref_frac), float(alt_frac),
                                   atol=1e-6)
        print("MODE %%s %%s OK" %% (mode, strat))
    print("PIPELINED %%s OK" %% strat)

# ---- skew deeper than the bucket count: B=1 and B=2 edge cases -----------
# (tree total is 4096, so bucket_elems 4096/2048 give full tail buckets and
# the quantized strategies stay bitwise vs the oracle)
for strat, dr, uk in (("optireduce", 0.1, True),
                      ("optireduce_q", 0.05, True),
                      ("optireduce_rounds", 0.1, False)):
    cfg = OptiReduceConfig(strategy=strat, drop_rate=dr, hadamard_block=256,
                           use_kernels=uk, quant_bits=8, incast=3)
    for be, nb in ((4096, 1), (2048, 2)):
        ref, ref_frac = run(sync_pytree_unfused, cfg, bucket_elems=be)
        for fn in (sync_pytree, sync_pipelined):
            out, out_frac = run(fn, cfg, bucket_elems=be)
            for k in tree:
                assert np.array_equal(np.asarray(ref[k]),
                                      np.asarray(out[k])), (strat, be, k)
            np.testing.assert_allclose(float(ref_frac), float(out_frac),
                                       atol=1e-6)
    print("PIPELINE_EDGE %%s OK" %% strat)

# ---- policy-driven dispatch: a full active set is a bitwise no-op --------
# (the acceptance pin for the runtime control plane: with no stragglers
# detected the SyncPolicy names every peer, active_subset normalizes that
# to None, and every strategy stays on the exact full-participation trace)
import dataclasses
from repro.runtime import SyncPolicy
for item in %(strategies)r:
    strat, dr, uk = item
    cfg = OptiReduceConfig(strategy=strat, drop_rate=dr, hadamard_block=256,
                           use_kernels=uk, quant_bits=8, incast=3)
    policy = SyncPolicy(use_hadamard=cfg.use_hadamard, incast=cfg.incast,
                        active_peers=tuple(range(8)),
                        shard_weights=(4,) * 8, dead_links=())
    ref, ref_frac = run(sync_pytree, cfg)
    out, out_frac = run(sync_pytree, policy.apply(cfg))
    for k in tree:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), \
            ("policy", strat, k)
    np.testing.assert_allclose(float(ref_frac), float(out_frac), atol=1e-6)
    print("POLICY_FULLSET %%s OK" %% strat)

# ---- degraded participation: ejected peers excluded, replicas bitwise ----
# per-node distinct gradients (scaled by 1 + peer id) so exclusion is
# visible; with drop_rate=0 the synced value must equal the mean over the
# ACTIVE peers' contributions exactly (up to codec noise for quantizers)
ACTIVE = (0, 1, 2, 4, 5, 7)
xflat = jax.random.normal(key, (4096,))
def run_scaled(cfg):
    def body(xx):
        i = jax.lax.axis_index("data")
        local = {"w": xx * (1.0 + i.astype(jnp.float32))}
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        out = sync_pytree(local, ctx, bucket_elems=1024)
        return out["w"][None], ctx.loss_fraction()[None]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                          out_specs=(P("data"), P("data")), check_vma=False))
    return f(xflat)

expected = np.asarray(xflat) * np.mean([1.0 + p for p in ACTIVE])
for strat, uk, tol in (("optireduce", True, 1e-4),      # a2a: mask exclusion
                       ("optireduce_rounds", False, 1e-4),  # subset schedule
                       ("ring_ht", False, 1e-4),        # virtual ring
                       ("optireduce_q", True, 5e-2)):   # quantized subset
    cfg = OptiReduceConfig(strategy=strat, drop_rate=0.0, hadamard_block=256,
                           use_kernels=uk, quant_bits=8, incast=3,
                           active_peers=ACTIVE)
    out, _ = run_scaled(cfg)
    out = np.asarray(out)
    assert np.array_equal(out, np.broadcast_to(out[0:1], out.shape)), \
        ("participation replica divergence", strat)
    err = np.max(np.abs(out[0] - expected)) / np.max(np.abs(expected))
    assert err < tol, (strat, err)
    # with transport drops on top, replicas must still agree bitwise
    if strat != "ring_ht":                       # ring rejects Lossy
        cfgd = dataclasses.replace(cfg, drop_rate=0.1)
        outd, _ = run_scaled(cfgd)
        outd = np.asarray(outd)
        assert np.array_equal(outd, np.broadcast_to(outd[0:1], outd.shape)), \
            ("participation+drops divergence", strat)
    print("PARTICIPATION %%s OK err=%%.2e" %% (strat, err))

# the subset round schedule must genuinely shrink: 2(A-1) rounds + 1 graft
# vs 2(N-1) collective-permute sites in the lowered HLO
def _n_perms(cfg):
    def body(xx):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        return sync_pytree({"w": xx}, ctx, bucket_elems=4096)["w"]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                          out_specs=P(), check_vma=False))
    return f.lower(xflat).as_text().count("stablehlo.collective_permute")
full_perms = _n_perms(OptiReduceConfig(strategy="optireduce_rounds",
                                       incast=1, hadamard_block=256))
sub_perms = _n_perms(OptiReduceConfig(strategy="optireduce_rounds",
                                      incast=1, hadamard_block=256,
                                      active_peers=ACTIVE))
assert full_perms == 14, full_perms              # 2*(8-1)
assert sub_perms == 11, sub_perms                # 2*(6-1) + 1 graft
print("PARTICIPATION_SCHEDULE OK %%d -> %%d" %% (full_perms, sub_perms))

# ---- weighted shards: straggler-proportional ownership, same bits --------
# a non-uniform plan re-cuts the bucket into weight-proportional contiguous
# slices; at drop 0 the masked mean reduces the SAME elements in the SAME
# row order, so both rounds strategies must stay bitwise vs uniform
for strat in ("tar_rounds", "optireduce_rounds"):
    cfg0 = OptiReduceConfig(strategy=strat, drop_rate=0.0,
                            hadamard_block=256, incast=3)
    ref, _ = run(sync_pytree, cfg0)
    out, _ = run(sync_pytree,
                 dataclasses.replace(cfg0, shard_weights=(2,) * 7 + (1,)))
    for k in tree:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), \
            ("weighted", strat, k)
    print("WEIGHTED %%s OK" %% strat)

# weighted composes with a degraded active set: 6 peers, the last one at
# half weight, distinct per-node gradients — replicas bitwise-identical and
# the synced value is exactly the mean over the ACTIVE contributions
cfgws = OptiReduceConfig(strategy="optireduce_rounds", drop_rate=0.0,
                         hadamard_block=256, incast=3, active_peers=ACTIVE,
                         shard_weights=(2, 2, 2, 2, 2, 1))
outs, _ = run_scaled(cfgws)
outs = np.asarray(outs)
assert np.array_equal(outs, np.broadcast_to(outs[0:1], outs.shape)), \
    "weighted subset replica divergence"
errw = np.max(np.abs(outs[0] - expected)) / np.max(np.abs(expected))
assert errw < 1e-4, errw
print("WEIGHTED_SUBSET OK err=%%.2e" %% errw)

# ---- dead-link rewiring: relayed rounds, same bits -----------------------
# a dead directed edge reroutes that round's transfer through a 2-hop relay
# instead of ejecting the endpoint; unnamed ppermute destinations receive
# zeros and recv = direct + relayed, so the received matrix — and with it
# the arrival-mask PRNG stream — is unchanged: bitwise even UNDER drops
cfg0 = OptiReduceConfig(strategy="optireduce_rounds", drop_rate=0.1,
                        hadamard_block=256, incast=3)
ref, ref_frac = run(sync_pytree, cfg0)
out, out_frac = run(sync_pytree,
                    dataclasses.replace(cfg0, dead_links=((2, 5),)))
for k in tree:
    assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), \
        ("deadlink", k)
np.testing.assert_allclose(float(ref_frac), float(out_frac), atol=1e-6)
# ...and composes with weighted shards (pinned to weighted-only bits)
cfgw1 = dataclasses.replace(cfg0, shard_weights=(2,) * 7 + (1,))
refw, _ = run(sync_pytree, cfgw1)
outwd, _ = run(sync_pytree,
               dataclasses.replace(cfgw1, dead_links=((2, 5),)))
for k in tree:
    assert np.array_equal(np.asarray(refw[k]), np.asarray(outwd[k])), \
        ("weighted+deadlink", k)
# the relay is really in the lowered schedule: 2 extra permute sites per
# stage (src->relay, relay->dst) on top of the 2(N-1) round permutes
dead_perms = _n_perms(OptiReduceConfig(strategy="optireduce_rounds",
                                       incast=1, hadamard_block=256,
                                       dead_links=((2, 5),)))
assert dead_perms == 18, dead_perms              # 14 + 2 relays * 2 stages
print("DEADLINK OK %%d perms" %% dead_perms)

# ---- 2D (pod, data) reduce-scatter: cross-pod replica consistency --------
mesh2 = jax.make_mesh((2, 4), ("pod", "data"),
                      axis_types=(AxisType.Auto,) * 2)
g = jax.random.normal(key, (4, 64, 48))        # same gradient on every node
cfg2 = OptiReduceConfig(drop_rate=0.05, pod_axis="pod", hadamard_block=256,
                        rs_wire_bits=8, use_kernels=True)

def rs_body(x):
    ctx = SyncContext(cfg=cfg2, key=jax.random.PRNGKey(1))
    i = jax.lax.axis_index("data")
    local = jnp.take(x, i, axis=0)             # pod-replicated input
    return reduce_scatter_axis(local, "data", 0, ctx)[None]
f2 = jax.jit(jax.shard_map(rs_body, mesh=mesh2, in_specs=P(None, None, None),
                       out_specs=P(("pod", "data"), None, None),
                       check_vma=False))
out2 = np.asarray(f2(g))                       # (8, 16, 48): pod-major rows
assert np.array_equal(out2[:4], out2[4:]), \
    np.max(np.abs(out2[:4] - out2[4:]))
print("RS2D replica-consistency OK")

# the quantization grids themselves must be pmax-shared across pods (not
# just the inner axis) when a pod axis is configured: encode with inputs
# that VARY per pod and check every node derives identical grids
enc_codec = rs_spec(cfg2).codec
def grid_body(x):
    ctx = SyncContext(cfg=cfg2, key=jax.random.PRNGKey(1))
    p = jax.lax.axis_index("pod")
    local = x * (1.0 + p)                      # pod-dependent scale
    return enc_codec.encode(local.reshape(-1), ctx, "data").lo[None]
f3 = jax.jit(jax.shard_map(grid_body, mesh=mesh2, in_specs=P(None),
                       out_specs=P(("pod", "data"), None), check_vma=False))
lo = np.asarray(f3(jax.random.normal(key, (2048,))))
assert np.all(lo == lo[0:1]), "quant grids differ across pods"
print("RS2D grids-shared OK")
"""


@pytest.fixture(scope="module")
def parity_output():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD % {"strategies": STRATEGIES}],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy,drop_rate,use_kernels", STRATEGIES)
def test_spec_bitwise_matches_seed_oracle(parity_output, strategy, drop_rate,
                                          use_kernels):
    assert f"PARITY {strategy} OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy,drop_rate,use_kernels", STRATEGIES)
def test_pipelined_mode_bitwise(parity_output, strategy, drop_rate,
                                use_kernels):
    """Every engine schedule — mode='vmap' and the stage-skewed software
    pipeline (mode='pipelined') — is pinned bitwise to mode='scan' and the
    sync_pytree_unfused oracle for every registered strategy on 8 devices,
    drops + kernels + quantized exchange included."""
    assert f"MODE vmap {strategy} OK" in parity_output, parity_output
    assert f"MODE pipelined {strategy} OK" in parity_output, parity_output
    assert f"PIPELINED {strategy} OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy",
                         ["optireduce", "optireduce_q", "optireduce_rounds"])
def test_pipelined_skew_deeper_than_bucket_count(parity_output, strategy):
    """B=1 and B=2 edge cases: the depth-2 skew exceeds the bucket count, so
    the whole schedule unrolls into prologue/epilogue — still bitwise vs the
    oracle and scan mode."""
    assert f"PIPELINE_EDGE {strategy} OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy,drop_rate,use_kernels", STRATEGIES)
def test_policy_full_set_is_bitwise_noop(parity_output, strategy, drop_rate,
                                         use_kernels):
    """Acceptance: policy-driven dispatch with a full active-peer set (no
    stragglers detected), UNIFORM shard weights, and no dead links keeps
    every registered strategy bitwise-identical to its current output —
    SyncPolicy.apply naming all 8 peers at equal weight normalizes to the
    exact full-participation uniform-shard trace."""
    assert f"POLICY_FULLSET {strategy} OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["tar_rounds", "optireduce_rounds"])
def test_weighted_shards_bitwise(parity_output, strategy):
    """Straggler-proportional shard weights on the rounds schedules: a
    non-uniform plan (one peer at half weight) re-cuts ownership but stays
    bitwise-identical to the uniform exchange at zero drops, and composes
    with a degraded active set (replica-consistent, exact active mean)."""
    assert f"WEIGHTED {strategy} OK" in parity_output, parity_output
    assert "WEIGHTED_SUBSET OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
def test_dead_link_rewiring_bitwise(parity_output):
    """Link-fault rewiring: a dead (2, 5) edge relays through a live peer
    — bitwise-identical output even under transport drops (alone and
    stacked on weighted shards), with the 2-hop relay visible as 2 extra
    collective-permute sites per stage in the lowered HLO (14 -> 18)."""
    assert "DEADLINK OK 18 perms" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["optireduce", "optireduce_rounds",
                                      "ring_ht", "optireduce_q"])
def test_degraded_participation_semantics(parity_output, strategy):
    """Degraded participation on 8 devices: ejected peers' contributions
    are excluded (the synced bucket equals the mean over ACTIVE peers'
    distinct gradients), replicas stay bitwise-identical — including the
    ejected peers, which still receive the result — and transport drops
    compose with the exclusion."""
    assert f"PARTICIPATION {strategy} OK" in parity_output, parity_output


@pytest.mark.parity
@pytest.mark.slow
def test_degraded_round_schedule_shrinks(parity_output):
    """The rounds schedule is regenerated over the active set: 2(A-1)
    collective-permute sites plus one graft round in the lowered HLO,
    against 2(N-1) at full participation."""
    assert "PARTICIPATION_SCHEDULE OK 14 -> 11" in parity_output, \
        parity_output


@pytest.mark.parity
@pytest.mark.slow
def test_reduce_scatter_2d_replica_consistent(parity_output):
    """Satellite: quantized reduce_scatter on a (pod, data) mesh — pod-
    replicated inputs reduce to bitwise-identical shards in every pod, and
    the shared quantization grids are pmax'd across pods, not just the
    inner axis."""
    assert "RS2D replica-consistency OK" in parity_output, parity_output
    assert "RS2D grids-shared OK" in parity_output, parity_output

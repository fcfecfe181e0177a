"""BucketPlan / fused sync_pytree: layout bookkeeping, bitwise regression
against the seed bucketing loop, and the constant-HLO-in-B property the
scan rewrite exists for. Multi-worker bitwise equivalence runs in a
subprocess (same pattern as test_collectives.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BucketPlan, OptiReduceConfig, SyncContext,
                        sync_pytree, sync_pytree_unfused)
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P


def _tree(key, sizes):
    ks = jax.random.split(key, len(sizes))
    return {f"leaf{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def test_plan_layout_and_hashability():
    tree = _tree(jax.random.PRNGKey(0), [(3, 500), (700,), (9, 100)])
    plan = BucketPlan.for_tree(tree, 1000)
    assert plan.total == 3100
    assert plan.num_buckets == 4
    assert plan.padded == 4000
    assert plan.sizes == (1500, 700, 900)
    # hashable + stable across rebuilds from the same shapes
    assert hash(plan) == hash(BucketPlan.for_tree(tree, 1000))
    assert plan == BucketPlan.for_tree(jax.tree.map(jnp.zeros_like, tree),
                                       1000)


def test_plan_single_bucket_has_no_tail_padding():
    tree = _tree(jax.random.PRNGKey(1), [(40,), (60,)])
    plan = BucketPlan.for_tree(tree, 6_553_600)
    assert plan.num_buckets == 1 and plan.bucket_elems == 100
    assert plan.padded == plan.total


def test_pack_unpack_roundtrip_preserves_dtype():
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (17, 13),
                                   jnp.float32),
            "b": jax.random.normal(jax.random.PRNGKey(1),
                                   (300,)).astype(jnp.bfloat16)}
    plan = BucketPlan.for_tree(tree, 128)
    out = plan.unpack(plan.pack(tree))
    assert out["b"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(
        np.asarray(out["b"].astype(jnp.float32)),
        np.asarray(tree["b"].astype(jnp.float32)))


def _sync(fn, tree, cfg, bucket_elems, **kw):
    """Run a sync function under a dp=1 shard_map (single device)."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    spec = jax.tree.map(lambda _: P(), tree)

    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        return fn(t, ctx, bucket_elems=bucket_elems, **kw)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
    return f, f(tree)


@pytest.mark.parametrize("strategy", ["psum", "optireduce", "optireduce_q"])
def test_bitwise_matches_seed_bucketing(strategy):
    """Fused (scan) sync_pytree == seed loop, bitwise, on a multi-leaf
    pytree spanning >= 3 buckets.

    psum/optireduce are deterministic and elementwise across peers, so the
    identity holds even with a zero-padded tail bucket; optireduce_q draws
    shape-dependent stochastic-rounding noise, so it is exercised on a
    layout whose tail bucket is full (the padded-tail case is equivalent in
    distribution, not bitwise)."""
    sizes = ([(3, 500), (600,), (9, 100)] if strategy == "optireduce_q"
             else [(3, 500), (700,), (9, 100)])
    tree = _tree(jax.random.PRNGKey(2), sizes)
    cfg = OptiReduceConfig(strategy=strategy, drop_rate=0.0,
                           hadamard_block=256)
    _, ref = _sync(sync_pytree_unfused, tree, cfg, 1000)
    _, out = _sync(sync_pytree, tree, cfg, 1000)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(out[k]))


@pytest.mark.parametrize("accum_dtype", [jnp.float32, jnp.bfloat16])
def test_arena_accumulation_bitwise_vs_per_leaf(accum_dtype):
    """The trainer's packed gradient arena — a ``lax.scan`` accumulating
    micro-batch grads directly into the (B, bucket_elems) batch, the pack
    concat fused into the add — is bitwise-identical to the seed per-leaf
    ``zeros`` + ``tree.map`` scan accumulator followed by a final pack (the
    cast-then-concatenate commutes with the adds elementwise), and the full
    microbatch pipeline (accumulate, fp32 cast, /n_micro mean) matches the
    per-leaf formulation of the same math.

    (The mean is taken in fp32 wire space on both sides: a divide in a
    non-fp32 accum dtype is not XLA-stable across formulations — the
    simplifier rewrites divide->convert chains and reciprocal multiplies
    differently per fusion context — which is why the trainer casts before
    dividing.)"""
    n_micro = 3
    sizes = [(3, 500), (700,), (9, 100)]
    micro_list = [_tree(jax.random.PRNGKey(10 + i), sizes)
                  for i in range(n_micro)]
    gs = jax.tree.map(lambda *xs: jnp.stack(xs), *micro_list)
    plan = BucketPlan.for_tree(micro_list[0], 1000)

    @jax.jit
    def seed_path(gs):
        def micro(acc, g):
            return jax.tree.map(
                lambda a, b: a + b.astype(accum_dtype), acc, g), None
        zeros = jax.tree.map(
            lambda g: jnp.zeros(g.shape[1:], accum_dtype), gs)
        acc, _ = jax.lax.scan(micro, zeros, gs)
        return plan.pack(acc), plan.pack(acc) / n_micro

    @jax.jit
    def arena_path(gs):
        def micro(acc, g):
            return acc + plan.pack(g, dtype=accum_dtype), None
        arena0 = jnp.zeros((plan.num_buckets, plan.bucket_elems),
                           accum_dtype)
        arena, _ = jax.lax.scan(micro, arena0, gs)
        return arena.astype(jnp.float32), arena.astype(jnp.float32) / n_micro

    seed_acc, seed_mean = seed_path(gs)
    arena_acc, arena_mean = arena_path(gs)
    np.testing.assert_array_equal(np.asarray(seed_acc), np.asarray(arena_acc))
    np.testing.assert_array_equal(np.asarray(seed_mean),
                                  np.asarray(arena_mean))


def test_plan_offsets_cover_stream():
    tree = _tree(jax.random.PRNGKey(4), [(3, 500), (700,), (9, 100)])
    plan = BucketPlan.for_tree(tree, 1000)
    assert plan.offsets == (0, 1500, 2200)
    assert plan.offsets[-1] + plan.sizes[-1] == plan.total


@pytest.mark.parametrize("mode", ["vmap", "pipelined"])
def test_alternate_modes_match_scan(mode):
    tree = _tree(jax.random.PRNGKey(3), [(2048,), (2048,)])
    cfg = OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                           hadamard_block=256)
    _, a = _sync(sync_pytree, tree, cfg, 1024)
    _, b = _sync(sync_pytree, tree, cfg, 1024, mode=mode)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.mark.parametrize("nbuckets", [1, 2, 3, 4, 8])
def test_pipelined_mode_every_pipeline_shape(nbuckets):
    """Depth-2 skew across every scheduling shape: B=1/2 (skew deeper than
    bucket count, fully unrolled), B=3 (empty steady-state window), B=4
    (single-step scan), B=8 (steady state) — all bitwise vs scan mode."""
    tree = {"g": jax.random.normal(jax.random.PRNGKey(6), (nbuckets * 1024,))}
    cfg = OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                           hadamard_block=256)
    _, a = _sync(sync_pytree, tree, cfg, 1024)
    _, b = _sync(sync_pytree, tree, cfg, 1024, mode="pipelined")
    np.testing.assert_array_equal(np.asarray(a["g"]), np.asarray(b["g"]))


def test_hlo_size_constant_in_bucket_count():
    """The strategy body is traced once: the lowered module carries ONE
    collective (inside the scan) regardless of B, where the seed loop
    emits one per bucket — and overall HLO size stays ~flat in B."""
    cfg = OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                           hadamard_block=256)

    def lowered(fn, nbuckets):
        tree = {"a": jnp.zeros((nbuckets * 1024,))}
        f, _ = _sync(fn, tree, cfg, 1024)
        return f.lower(tree).as_text()

    def n_a2a(txt):
        return txt.count("all_to_all")

    assert n_a2a(lowered(sync_pytree, 8)) == n_a2a(lowered(sync_pytree, 2))
    assert (n_a2a(lowered(sync_pytree_unfused, 8))
            == 4 * n_a2a(lowered(sync_pytree_unfused, 2)))
    fused_growth = (len(lowered(sync_pytree, 8))
                    / len(lowered(sync_pytree, 2)))
    assert fused_growth < 1.35, fused_growth


CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core import OptiReduceConfig, SyncContext, sync_pytree, \
    sync_pytree_unfused

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(0)
tree = {"w": jax.random.normal(key, (4, 1024)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (2048,)),
        "v": jax.random.normal(jax.random.fold_in(key, 2), (2048,))}
spec = jax.tree.map(lambda _: P(), tree)

def run(fn, cfg):
    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(5))
        out = fn(t, ctx, bucket_elems=1024)
        return out, ctx.loss_fraction()
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                          out_specs=(spec, P()), check_vma=False))
    return f(tree)

# 8 full 1024-elem buckets: drops + kernels + quantized exchange, bitwise
for strat, dr, uk in (("optireduce", 0.1, False), ("optireduce", 0.1, True),
                      ("optireduce_q", 0.05, True)):
    cfg = OptiReduceConfig(strategy=strat, drop_rate=dr, hadamard_block=256,
                           use_kernels=uk)
    ref, ref_frac = run(sync_pytree_unfused, cfg)
    out, out_frac = run(sync_pytree, cfg)
    for k in tree:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), \
            (strat, uk, k)
    np.testing.assert_allclose(float(ref_frac), float(out_frac), atol=1e-6)
    print(strat, "uk=%s" % uk, "bitwise OK, loss_frac %.4f" % float(out_frac))
"""


@pytest.mark.slow
def test_bucket_plan_multidevice_bitwise():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert proc.stdout.count("bitwise OK") == 3, proc.stdout

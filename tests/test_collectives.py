"""Multi-device collective equivalence tests.

These need >1 XLA device, so they run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps the default single device, per the dry-run-only-512 rule).
"""
import os
import subprocess
import sys

import pytest

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core import OptiReduceConfig, SyncContext, sync_bucket
from repro.core.allreduce import reduce_scatter_axis

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
L = 10_000
key = jax.random.PRNGKey(0)
xs = jax.random.normal(key, (8, L), jnp.float32)
expected = np.asarray(jnp.mean(xs, axis=0))

def run(strategy, drop_rate=0.0, block=1024):
    cfg = OptiReduceConfig(strategy=strategy, drop_rate=drop_rate,
                           hadamard_block=block)
    def body(x):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(42))
        return sync_bucket(x.reshape(-1), ctx)[None, :]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                              out_specs=P("data", None), check_vma=False))
    return np.asarray(f(xs))

# 1) every lossless strategy computes the exact mean, replica-consistent
for s in ("psum", "gloo_ring", "nccl_tree", "bcube", "tar_tcp",
          "tar_rounds", "optireduce"):
    out = run(s)
    err = np.max(np.abs(out - expected[None]))
    spread = np.max(np.abs(out - out[0:1]))
    assert err < 1e-5, (s, err)
    assert spread == 0.0, (s, spread)
print("lossless-equivalence OK")

# 2) drops: bounded error, replicas stay identical (stage-1-only drops)
out = run("optireduce", drop_rate=0.05)
rmse = np.sqrt(np.mean((out[0] - expected) ** 2))
spread = np.max(np.abs(out - out[0:1]))
assert 0 < rmse < 0.3, rmse
assert spread == 0.0, spread
print("drop-consistency OK")

# 3) reduce_scatter_axis == sliced mean (the FSDP/ZeRO reduction)
g = jax.random.normal(key, (8, 64, 48))
def rs_body2(x):
    ctx = SyncContext(cfg=OptiReduceConfig(drop_rate=0.0),
                      key=jax.random.PRNGKey(1))
    i = jax.lax.axis_index("data")
    local = jnp.take(x, i, axis=0)     # worker i's gradient (64, 48)
    return reduce_scatter_axis(local, "data", 0, ctx, with_drops=False)
f2 = jax.jit(jax.shard_map(rs_body2, mesh=mesh,
                           in_specs=P(None, None, None),
                           out_specs=P("data", None),
                           check_vma=False))
out2 = np.asarray(f2(g))              # (64, 48): stacked shards
np.testing.assert_allclose(out2, np.asarray(jnp.mean(g, 0)), atol=1e-5)
print("reduce-scatter OK")

# 4) 2D TAR on a (2, 2, 2) pod mesh
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(AxisType.Auto,) * 3)
cfg2 = OptiReduceConfig(strategy="optireduce", pod_axis="pod",
                        drop_rate=0.0, hadamard_block=256)
xs2 = jax.random.normal(key, (4, 2048), jnp.float32)   # per (pod,data)
def body2(x):
    ctx = SyncContext(cfg=cfg2, key=jax.random.PRNGKey(3))
    return sync_bucket(x.reshape(-1), ctx)[None]
f3 = jax.jit(jax.shard_map(
    body2, mesh=mesh3, in_specs=P(("pod", "data"), None),
    out_specs=P(("pod", "data"), None), check_vma=False))
out3 = np.asarray(f3(xs2))           # (4, 2048): identical rows
assert np.max(np.abs(out3 - np.asarray(jnp.mean(xs2, 0))[None])) < 1e-5
assert np.max(np.abs(out3 - out3[0:1])) == 0.0
print("2d-tar OK")

# 5) trainer integration: fsdp == replicated (lossless), losses decrease
from repro.configs.base import ModelConfig
from repro.optim.optimizers import OptimizerConfig
from repro.train.trainer import TrainConfig, build_train_step
from repro.models import init_params
cfg_m = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                    param_dtype=jnp.float32)
mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
batch = {"tokens": jax.random.randint(key, (8, 16), 0, 128),
         "labels": jax.random.randint(key, (8, 16), 0, 128)}
losses = {}
for mode in ("replicated", "fsdp"):
    tc = TrainConfig(sync=OptiReduceConfig(strategy="optireduce",
                                           drop_rate=0.0,
                                           hadamard_block=256),
                     optimizer=OptimizerConfig(lr=1e-2),
                     dp_mode=mode, seq_chunk=16)
    make_step, opt, _ = build_train_step(cfg_m, tc, mesh2)
    params = init_params(key, cfg_m)
    step_fn, sh = make_step(jax.eval_shape(opt.init, params), batch)
    params = jax.device_put(params, sh["params"])
    opt_state = jax.jit(opt.init, out_shardings=sh["opt"])(params)
    b = jax.device_put(batch, sh["batch"])
    jf = jax.jit(step_fn)
    ls = []
    for i in range(4):
        params, opt_state, m = jf(params, opt_state, b,
                                  jnp.asarray(i, jnp.int32), key)
        ls.append(float(m["loss"]))
    losses[mode] = ls
    assert ls[-1] < ls[0], (mode, ls)
# identical math when lossless: fsdp path == replicated path
np.testing.assert_allclose(losses["fsdp"], losses["replicated"], rtol=2e-3)
print("trainer-equivalence OK")
"""


@pytest.mark.slow
def test_collectives_multidevice():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    for marker in ("lossless-equivalence OK", "drop-consistency OK",
                   "reduce-scatter OK", "2d-tar OK",
                   "trainer-equivalence OK"):
        assert marker in proc.stdout, proc.stdout

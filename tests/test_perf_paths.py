"""§Perf hillclimb code paths: pure_dp remap, weights-stationary MoE,
quantized TAR / reduce-scatter wires. Multi-device equivalence runs in a
subprocess (same pattern as test_collectives.py)."""
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
from repro.configs.base import ModelConfig
from repro.models import init_params, init_decode_state, decode_step, param_specs
from repro.models.parallel import ParallelCtx

key = jax.random.PRNGKey(0)

# 1) optireduce_q (quantized TAR): bounded error, replica-consistent
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
xs = jax.random.normal(key, (8, 20000), jnp.float32)
expected = np.asarray(jnp.mean(xs, 0))
cfg = OptiReduceConfig(strategy="optireduce_q", drop_rate=0.0,
                       hadamard_block=1024, quant_bits=8)
def body(x):
    ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(7))
    return sync_bucket(x.reshape(-1), ctx)[None]
f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                          out_specs=P("data", None), check_vma=False))
out = np.asarray(f(xs))
rel = np.sqrt(np.mean((out[0]-expected)**2)) / np.std(expected)
assert rel < 0.10, rel
assert np.max(np.abs(out - out[0:1])) == 0.0
print("optireduce_q OK")

# 2) quantized reduce-scatter wire
g = jax.random.normal(key, (8, 64, 48))
cfg_rs = OptiReduceConfig(drop_rate=0.0, rs_wire_bits=8, hadamard_block=256)
def rs_body(x):
    ctx = SyncContext(cfg=cfg_rs, key=jax.random.PRNGKey(1))
    i = jax.lax.axis_index("data")
    return reduce_scatter_axis(jnp.take(x, i, 0), "data", 0, ctx,
                               with_drops=False)
fr = jax.jit(jax.shard_map(rs_body, mesh=mesh, in_specs=P(None, None, None),
                           out_specs=P("data", None), check_vma=False))
rs_out = np.asarray(fr(g))
true = np.asarray(jnp.mean(g, 0))
rel = np.sqrt(np.mean((rs_out - true)**2)) / true.std()
assert rel < 0.10, rel
print("rs_wire_q8 OK")

# 3) weights-stationary MoE decode == gathered decode (exact)
mcfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_ff=96, vocab_size=128, n_experts=8,
                   top_k=2, param_dtype=jnp.float32)
mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
params = init_params(key, mcfg)
tok = jax.random.randint(key, (8, 1), 0, 128)
def run(moe_stat):
    def gather(w, dim, k):
        del k
        return jax.lax.all_gather(w, "data", axis=dim, tiled=True)
    pctx = ParallelCtx(tp_axis="model", dp_axis="data", fsdp=True,
                       gather=gather, moe_stationary=moe_stat)
    p_specs = param_specs(mcfg, tp=2, fsdp_axes=("data",))
    state = init_decode_state(params, mcfg, batch=8, max_seq=4, tp=1,
                              dtype=jnp.float32)
    from repro.models.layers import KVCache
    st_specs = [KVCache(k=P(None, "data", None, "model", None),
                        v=P(None, "data", None, "model", None))]
    def b(p, st, t):
        return decode_step(p, st, t, jnp.int32(0), mcfg, pctx,
                           key=jax.random.PRNGKey(1))
    fj = jax.jit(jax.shard_map(b, mesh=mesh2,
                 in_specs=(p_specs, st_specs, P("data", None)),
                 out_specs=(P("data", None), st_specs), check_vma=False))
    nxt, _ = fj(params, state, tok)
    return np.asarray(nxt)
assert np.array_equal(run(False), run(True))
print("moe_stationary OK")

# 4) pure_dp trainer remap: loss decreases, matches tp-trainer direction
from repro.optim.optimizers import OptimizerConfig
from repro.train.trainer import TrainConfig, build_train_step
tcfg = ModelConfig(name="t2", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                   param_dtype=jnp.float32)
batch = {"tokens": jax.random.randint(key, (8, 16), 0, 128),
         "labels": jax.random.randint(key, (8, 16), 0, 128)}
tc = TrainConfig(sync=OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                                       hadamard_block=256),
                 optimizer=OptimizerConfig(lr=1e-2),
                 dp_mode="replicated", seq_chunk=16, pure_dp=True)
make_step, opt, _ = build_train_step(tcfg, tc, mesh2)
params2 = init_params(key, tcfg)
step_fn, sh = make_step(jax.eval_shape(opt.init, params2), batch)
params2 = jax.device_put(params2, sh["params"])
opt_state = jax.jit(opt.init, out_shardings=sh["opt"])(params2)
b2 = jax.device_put(batch, sh["batch"])
jf = jax.jit(step_fn)
ls = []
for i in range(4):
    params2, opt_state, m = jf(params2, opt_state, b2,
                               jnp.asarray(i, jnp.int32), key)
    ls.append(float(m["loss"]))
assert ls[-1] < ls[0], ls
print("pure_dp OK")

# 5) sequence parallelism: first-step loss matches the non-SP path exactly
# (forward identical); later steps drift only by fp32 reduction order
losses = {}
for sp in (False, True):
    tc = TrainConfig(sync=OptiReduceConfig(strategy="psum", drop_rate=0.0),
                     optimizer=OptimizerConfig(lr=1e-2), seq_chunk=16,
                     seq_parallel=sp)
    make_step, opt, _ = build_train_step(tcfg, tc, mesh2)
    p = init_params(key, tcfg)
    step_fn, sh = make_step(jax.eval_shape(opt.init, p), batch)
    p = jax.device_put(p, sh["params"])
    o = jax.jit(opt.init, out_shardings=sh["opt"])(p)
    b3 = jax.device_put(batch, sh["batch"])
    jf2 = jax.jit(step_fn)
    ls = []
    for i in range(3):
        p, o, m = jf2(p, o, b3, jnp.asarray(i, jnp.int32), key)
        ls.append(float(m["loss"]))
    losses[sp] = ls
assert losses[True][0] == losses[False][0], (losses)    # fwd exact
np.testing.assert_allclose(losses[True], losses[False], rtol=1e-2)
print("seq_parallel OK")
"""


@pytest.mark.slow
def test_perf_paths_multidevice():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    for marker in ("optireduce_q OK", "rs_wire_q8 OK", "moe_stationary OK",
                   "pure_dp OK", "seq_parallel OK"):
        assert marker in proc.stdout, proc.stdout

"""The LM head and loss of ``lm_loss`` are one custom-VJP function: its
forward pass forms the head's gradient from each chunk's logits, so the
logits matmul runs once a chunk.

* Loss and every gradient leaf equal ``jax.grad`` of a plain float32
  full-logits cross-entropy written here, on every chunking path (scan,
  Python loop, one chunk, a chunk that does not divide the sequence),
  tied and untied heads, a frontend prefix and masked labels.
* The undifferentiated call returns the loss the differentiated one does.
* Under a tensor-parallel ``shard_map`` (vocabulary-sharded head) the
  gradients are what autodiff of the same loss gives there.
* The differentiated loss holds three vocabulary-wide matmuls a chunk
  (logits, dX, dW), in the jaxpr and in a step compiled for a described
  TPU v5e: the logits are not recomputed in the backward pass.
"""
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import SINGLE, init_params, lm_loss
from repro.models.transformer import forward_hidden

KEY = jax.random.PRNGKey(0)


def _oracle_loss(params, batch, cfg, pctx=SINGLE, *, remat=True,
                 unroll=False):
    """Mean cross-entropy over the whole (vocabulary-sharded) logits,
    differentiated by autodiff."""
    x = forward_hidden(params, batch, cfg, pctx, key=KEY, remat=remat,
                       unroll=unroll)
    labels = batch["labels"]
    x = x[:, x.shape[1] - labels.shape[1]:]
    head = params.get("lm_head")
    w = params["embed"].T if head is None else head
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    v_local = logits.shape[-1]
    local = labels - pctx.tp_index() * v_local
    m = pctx.pmax_tp(jnp.max(jax.lax.stop_gradient(logits), axis=-1))
    lse = jnp.log(pctx.psum_tp(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))) + m
    onehot = jnp.arange(v_local) == local[..., None]
    picked = pctx.psum_tp(jnp.sum(jnp.where(onehot, logits, 0.0), -1))
    weight = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * weight) / jnp.maximum(jnp.sum(weight),
                                                          1.0)


def _batch(cfg, b, s, *, masked=False, prefix=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    labels = jax.random.randint(k2, (b, s), 0, cfg.vocab_size)
    if masked:
        labels = jnp.where(jax.random.bernoulli(k3, 0.3, (b, s)), -1,
                           labels)
        labels = labels.at[0].set(-1)            # a row with no target
    out = {"tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size),
           "labels": labels}
    if prefix:
        out["prefix_embeds"] = jax.random.normal(
            k3, (b, prefix, cfg.frontend_dim))
    return out


# name -> (arch, tokens per row, seq_chunk, lm_loss options, batch options)
CASES = {
    "tied": ("gpt2-paper", 32, 8, {}, {}),
    "untied": ("stablelm-1.6b", 32, 8, {}, {}),
    "frontend-prefix": ("llava-next-mistral-7b", 24, 8, {},
                        {"prefix": 8}),
    "masked-labels": ("gpt2-paper", 32, 8, {}, {"masked": True}),
    "chunk-not-dividing": ("gpt2-paper", 24, 16, {}, {}),
    "one-chunk": ("stablelm-1.6b", 32, 32, {}, {}),
    "no-remat": ("stablelm-1.6b", 32, 8, {"remat": False}, {}),
    "unroll": ("gpt2-paper", 32, 8, {"unroll": True}, {}),
}


def _assert_leaves_close(got, want, rtol):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.dtype == w.dtype, (jax.tree_util.keystr(path), g.dtype)
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        scale = max(np.abs(w).max(), 1e-30)
        assert np.abs(g - w).max() <= rtol * scale, \
            (jax.tree_util.keystr(path), np.abs(g - w).max() / scale)


@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_gradient_match_plain_cross_entropy(case):
    arch, s, seq_chunk, opts, bopts = CASES[case]
    cfg = get_smoke(arch)
    assert (cfg.frontend is not None) == bool(bopts.get("prefix"))
    params = init_params(KEY, cfg)
    batch = _batch(cfg, 2, s, **bopts)

    def loss(p):
        return lm_loss(p, batch, cfg, SINGLE, key=KEY, seq_chunk=seq_chunk,
                       **opts)

    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: _oracle_loss(p, batch, cfg, **opts)))(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    _assert_leaves_close(got, want, rtol=1e-5)
    # the primal (no differentiation) is the fwd rule's loss
    np.testing.assert_allclose(float(jax.jit(loss)(params)), float(got_loss),
                               rtol=1e-6)


def test_bfloat16_gradient_keeps_the_parameter_dtypes():
    """bf16 weights: dX comes back in the hidden state's dtype and dW in
    the head's, within bf16 rounding of the float32 oracle."""
    cfg = dataclasses.replace(get_smoke("stablelm-1.6b"),
                              param_dtype=jnp.bfloat16)
    params = init_params(KEY, cfg)
    batch = _batch(cfg, 2, 32, masked=True)
    got = jax.jit(jax.grad(lambda p: lm_loss(
        p, batch, cfg, SINGLE, key=KEY, seq_chunk=8)))(params)
    want = jax.jit(jax.grad(
        lambda p: _oracle_loss(p, batch, cfg)))(params)
    assert got["lm_head"].dtype == jnp.bfloat16
    _assert_leaves_close(got, want, rtol=3e-2)


def _vocab_matmuls(jaxpr, vocab):
    """Output shapes of the dot_generals of a jaxpr (and every jaxpr inside
    it) that have a vocabulary-wide operand or output, in program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            avals = [v.aval for v in (*eqn.invars, *eqn.outvars)]
            if any(vocab in a.shape for a in avals):
                found.append(tuple(eqn.outvars[0].aval.shape))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else (param,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _vocab_matmuls(inner, vocab)
    return found


# name -> (tokens per row, seq_chunk, unroll, chunks whose matmuls appear)
MATMUL_CASES = {"scan": (32, 8, False, 1), "unroll": (32, 8, True, 4),
                "one-chunk": (32, 32, False, 1)}


@pytest.mark.parametrize("case", list(MATMUL_CASES))
def test_three_vocabulary_matmuls_a_chunk(case):
    """The gradient's jaxpr holds the logits, dX and dW matmuls once per
    chunk (a scan body counts once): no recomputed logits. The loss alone
    holds the logits matmul only."""
    s, seq_chunk, unroll, n = MATMUL_CASES[case]
    cfg = get_smoke("stablelm-1.6b")
    b, d, v = 2, cfg.d_model, cfg.vocab_size
    c = min(seq_chunk, s)
    params = init_params(KEY, cfg)
    batch = _batch(cfg, b, s)

    def loss(p):
        return lm_loss(p, batch, cfg, SINGLE, key=KEY, seq_chunk=seq_chunk,
                       unroll=unroll)

    grad_mm = _vocab_matmuls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, v)
    assert sorted(grad_mm) == sorted([(b, c, v), (b, c, d), (d, v)] * n), \
        grad_mm
    loss_mm = _vocab_matmuls(jax.make_jaxpr(loss)(params).jaxpr, v)
    assert loss_mm == [(b, c, v)] * n, loss_mm


TP_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig
from repro.models import SINGLE, init_params, lm_loss, param_specs
from repro.models.parallel import ParallelCtx
import test_lm_head_vjp as t

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
for tied in (True, False):
    for sp in (False, True):
        cfg = ModelConfig(name="tp", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                          tie_embeddings=tied, param_dtype=jnp.float32)
        params = init_params(t.KEY, cfg)
        batch = t._batch(cfg, 4, 16, masked=True)
        pctx = ParallelCtx(tp_axis="model", dp_axis="data", sp=sp)
        specs = param_specs(cfg, tp=2)

        def sharded(fn):
            body = lambda p, b: jax.value_and_grad(fn)(p, b)
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(specs, P("data")),
                out_specs=(P(), specs), check_vma=False))(params, batch)

        loss, grads = sharded(lambda p, b: lm_loss(
            p, b, cfg, pctx, key=t.KEY, seq_chunk=8))
        want_loss, want = sharded(
            lambda p, b: t._oracle_loss(p, b, cfg, pctx))
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
        t._assert_leaves_close(grads, want, rtol=1e-5)
        # the loss is the single-device loss of the first data shard's
        # rows; the vocabulary-sharded head's gradient is tp times it, as
        # the transposed psums of autodiff under check_vma=False give it
        first = jax.tree.map(lambda a: a[:2], batch)
        one_loss, one = jax.jit(jax.value_and_grad(lambda p: lm_loss(
            p, first, cfg, SINGLE, key=t.KEY, seq_chunk=8)))(params)
        np.testing.assert_allclose(float(loss), float(one_loss), rtol=1e-6)
        leaf = "embed" if tied else "lm_head"
        t._assert_leaves_close(grads[leaf], 2 * one[leaf], rtol=1e-5)
        print("tp OK", tied, sp)
"""


def test_tensor_parallel_gradient_matches_autodiff():
    """tp = 2 over a ("data", "model") mesh of virtual CPU devices, with
    and without sequence parallelism, tied and untied: the loss and every
    gradient leaf inside ``shard_map(check_vma=False)`` are those of
    autodiff of the vocabulary-sharded oracle there (so each shard's dX is
    its vocabulary slice's partial product, summed across shards where
    autodiff sums it)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", TP_CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert proc.stdout.count("tp OK") == 4, proc.stdout


@pytest.fixture(scope="module")
def v5e_device():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


CONV = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ convolution\(")


@pytest.mark.parametrize("arch", ["gpt2-paper", "stablelm-1.6b"],
                         ids=["tied", "untied"])
def test_v5e_gradient_compiles_three_head_matmuls(arch, v5e_device):
    """The bf16 smoke model's loss and gradient (as the trainer takes
    them), two chunks in a scan, compiled for a described v5e: three
    ``convolution``s under ``lm_head``, one for each of logits, dX and
    dW."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    cfg = dataclasses.replace(get_smoke(arch), param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: init_params(KEY, cfg))
    rep = NamedSharding(Mesh([v5e_device], ("data",)), P())
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
    batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=rep)
             for k in ("tokens", "labels")}
    text = jax.jit(jax.value_and_grad(lambda p, b: lm_loss(
        p, b, cfg, SINGLE, key=KEY, seq_chunk=32))).lower(
            jax.tree.map(spec, params), batch).compile().as_text()
    head = [line for line in text.splitlines()
            if CONV.match(line) and "lm_head" in line]
    assert len(head) == 3, head

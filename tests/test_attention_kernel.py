"""The fused causal attention kernel (kernels/attention) against the dense
oracle, in the Pallas interpreter at small shapes, and the dispatch of
models/layers.py ``attention_train``: dense on the CPU, bitwise as before.

Tolerances are for bf16 operands: an 8-bit significand, so one rounding is
at most 2^-9 of a value. The kernel rounds the unnormalized probabilities
and the oracle the normalized ones, and both round their bf16 results, so
an element may differ by a few ulps of the largest value; both paths are
also held to the same error against an f32 oracle.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import runtime
from repro.kernels.attention import (attention, attention_ref, block_size,
                                     causal_attention_pallas, kernel_applies)
from repro.models.layers import attention_train
from repro.models.parallel import ParallelCtx


def _qkvg(b, s, h, dh, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (b, s, h, dh), jnp.float32).astype(dtype)
            for k in ks]


def _value_and_grads(fn, q, k, v, do):
    o, vjp = jax.vjp(fn, q, k, v)
    return (o,) + vjp(do)


def _f32(x):
    return np.asarray(x, np.float32)


# (B, S, H, dh, block): the wrapper's own block, and 128-row blocks that
# give the kernel several blocks on and below the diagonal
CASES = [(2, 256, 2, 64, None), (2, 512, 4, 64, None),
         (2, 256, 2, 128, None), (2, 512, 2, 128, None),
         (2, 512, 2, 64, 128), (2, 512, 3, 128, 128),
         (2, 256, 4, 64, 128)]


@pytest.mark.parametrize("b, s, h, dh, blk", CASES,
                         ids=[f"b{c[0]}-s{c[1]}-h{c[2]}-d{c[3]}-blk{c[4]}"
                              for c in CASES])
def test_kernel_matches_dense_output_and_grads(b, s, h, dh, blk):
    q, k, v, do = _qkvg(b, s, h, dh, seed=s + h + dh)
    scale = 1.0 / math.sqrt(dh)
    blk = blk or block_size(s)
    got = _value_and_grads(
        lambda q, k, v: causal_attention_pallas(q, k, v, scale, blk,
                                                True), q, k, v, do)
    want = _value_and_grads(
        lambda q, k, v: attention_ref(q, k, v, scale=scale), q, k, v, do)
    exact = _value_and_grads(
        lambda q, k, v: attention_ref(q, k, v, scale=scale),
        *[x.astype(jnp.float32) for x in (q, k, v, do)])
    for name, g, w, x in zip(("out", "dq", "dk", "dv"), got, want, exact):
        g, w, x = _f32(g), _f32(w), _f32(x)
        assert g.dtype == w.dtype and g.shape == w.shape
        top = np.abs(w).max()
        np.testing.assert_allclose(g, w, rtol=2 ** -6, atol=2 ** -6 * top,
                                   err_msg=name)
        # the same precision as the dense path: mean error against f32
        err_k = np.abs(g - x).mean()
        err_d = np.abs(w - x).mean()
        assert err_k <= 1.5 * err_d + 2 ** -12 * top, (name, err_k, err_d)


def test_wrapper_runs_the_kernel_when_asked():
    q, k, v, _ = _qkvg(1, 256, 2, 64)
    got = attention(q, k, v, scale=0.125, interpret=True)
    want = causal_attention_pallas(q, k, v, 0.125, 256, True)
    np.testing.assert_array_equal(_f32(got), _f32(want))


@pytest.mark.parametrize("blk", [128, 256])
def test_later_tokens_leave_earlier_outputs_bitwise_unchanged(blk):
    b, s, h, dh, t = 1, 256, 2, 64, 150
    q, k, v, _ = _qkvg(b, s, h, dh, seed=3)
    q2, k2, v2, _ = _qkvg(b, s, h, dh, seed=4)
    later = (jnp.arange(s) >= t)[None, :, None, None]
    q2, k2, v2 = (jnp.where(later, y, x) for x, y in
                  ((q, q2), (k, k2), (v, v2)))
    run = lambda q, k, v: _f32(causal_attention_pallas(q, k, v, 0.125, blk,
                                                       True))
    o, o2 = run(q, k, v), run(q2, k2, v2)
    np.testing.assert_array_equal(o[:, :t], o2[:, :t])
    assert not np.array_equal(o[:, t:], o2[:, t:])


def _old_attention_train(x, w, *, positions, rope_theta=10000.0):
    """attention_train's dense path as it was before the kernel existed."""
    from repro.models.layers import _map_kv, apply_rope
    b, s, _ = x.shape
    dh = w["head_dim"]
    q = jnp.einsum("bsd,dh->bsh", x, w["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, w["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, w["wv"].astype(x.dtype))
    hq = q.shape[-1] // dh
    hkv = k.shape[-1] // dh
    q = apply_rope(q.reshape(b, s, hq, dh), positions, theta=rope_theta)
    k = apply_rope(k.reshape(b, s, hkv, dh), positions, theta=rope_theta)
    v = v.reshape(b, s, hkv, dh)
    k = _map_kv(k, hq, None)
    v = _map_kv(v, hq, None)
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, hq * dh)
    return jnp.einsum("bsh,hd->bsd", ctx, w["wo"].astype(x.dtype))


@pytest.mark.parametrize("hq, hkv", [(4, 4), (4, 2)], ids=["mha", "gqa"])
def test_attention_train_on_cpu_is_the_dense_path_bitwise(hq, hkv):
    """On the CPU the kernel mode resolves to interpret, so the shape the
    kernel would take runs dense, and the layer's output and gradients
    are bitwise what they were."""
    assert runtime.resolve() == "interpret"
    b, s, d, dh = 2, 256, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (b, s, d)).astype(bf)
    w = {"wq": (jax.random.normal(ks[1], (d, hq * dh)) * 0.05).astype(bf),
         "wk": (jax.random.normal(ks[2], (d, hkv * dh)) * 0.05).astype(bf),
         "wv": (jax.random.normal(ks[3], (d, hkv * dh)) * 0.05).astype(bf),
         "wo": (jax.random.normal(ks[4], (hq * dh, d)) * 0.05).astype(bf),
         "head_dim": dh, "attn_chunk": 4096}
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    assert kernel_applies((b, s, hq, dh), bf, causal=True)

    def new(x, w):
        return attention_train(x, w, ParallelCtx(), positions=pos)

    def old(x, w):
        return _old_attention_train(x, w, positions=pos)

    params = {n: w[n] for n in ("wq", "wk", "wv", "wo")}
    meta = {n: w[n] for n in ("head_dim", "attn_chunk")}
    for fn in (new, old):
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda x, p: fn(x, {**p, **meta}))(x, params))
    out_new, out_old = (jax.jit(lambda x, p: fn(x, {**p, **meta}))(x, params)
                        for fn in (new, old))
    np.testing.assert_array_equal(_f32(out_new), _f32(out_old))
    g_new, g_old = (jax.jit(jax.grad(
        lambda x, p: fn(x, {**p, **meta}).astype(jnp.float32).sum(),
        argnums=(0, 1)))(x, params) for fn in (new, old))
    for a, c in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_array_equal(_f32(a), _f32(c))


def _dispatches_kernel(shape, dtype=jnp.bfloat16, causal=True,
                       monkeypatch=None):
    monkeypatch.setattr(runtime, "resolve", lambda: "compile")
    q = jax.ShapeDtypeStruct(shape, dtype)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: attention(q, k, v, scale=0.125, causal=causal))(
            q, q, q)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("shape, dtype, causal, kernel", [
    ((2, 1024, 12, 64), jnp.bfloat16, True, True),
    ((2, 384, 2, 128), jnp.bfloat16, True, True),
    ((2, 200, 2, 64), jnp.bfloat16, True, False),    # S not a block multiple
    ((2, 256, 2, 96), jnp.bfloat16, True, False),    # head size
    ((2, 256, 15, 64), jnp.bfloat16, True, False),   # heads not whole lanes
    ((2, 256, 2, 64), jnp.float32, True, False),     # dtype
    ((2, 256, 2, 64), jnp.bfloat16, False, False),   # not causal
], ids=["gpt2", "s384", "s200", "dh96", "h15", "f32", "noncausal"])
def test_dispatch_on_a_compiling_backend(shape, dtype, causal, kernel,
                                         monkeypatch):
    """With the kernel mode resolving to compile, the kernel runs exactly
    where it applies; every other shape keeps the dense path."""
    assert _dispatches_kernel(shape, dtype, causal,
                              monkeypatch=monkeypatch) is kernel


def test_unfit_shape_falls_back_to_dense_and_refuses_explicit_kernel():
    q, k, v, _ = _qkvg(1, 200, 2, 64)
    assert block_size(200) is None
    np.testing.assert_array_equal(
        _f32(attention(q, k, v, scale=0.125)),
        _f32(attention_ref(q, k, v, scale=0.125)))
    with pytest.raises(ValueError, match="does not take"):
        attention(q, k, v, scale=0.125, interpret=True)


@pytest.mark.tpu
def test_compiled_kernel_matches_dense_on_tpu():
    """Mosaic-compiled at a gpt2-paper layer's shape, 8 rows of 1024."""
    b, s, h, dh = 8, 1024, 12, 64
    q, k, v, do = _qkvg(b, s, h, dh, seed=11)
    with runtime.kernel_mode_scope("auto"):
        got = jax.jit(lambda *a: _value_and_grads(
            lambda q, k, v: attention(q, k, v, scale=0.125), *a))(
                q, k, v, do)
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda q, k, v: attention(q, k, v, scale=0.125))(q, k, v))
    want = jax.jit(lambda *a: _value_and_grads(
        lambda q, k, v: attention_ref(q, k, v, scale=0.125), *a))(
            q, k, v, do)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g, w = _f32(g), _f32(w)
        np.testing.assert_allclose(g, w, rtol=2 ** -6,
                                   atol=2 ** -6 * np.abs(w).max(),
                                   err_msg=name)

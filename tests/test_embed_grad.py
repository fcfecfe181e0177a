"""The token embedding's gradient is summed in float32 (``embed_tokens``
gathers from a float32 view of the table), and the forward pass is what
the plain bf16 gather gave."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import init_params
from repro.models.parallel import ParallelCtx
from repro.models.transformer import (TpLayout, embed_tokens, forward_hidden,
                                      lm_loss)


def _lookup_vjp(table, tokens, cot, cfg):
    lay = TpLayout.build(cfg, 1)
    _, vjp = jax.vjp(
        lambda t: embed_tokens({"embed": t}, tokens, cfg, lay,
                               ParallelCtx()), table)
    return vjp(cot)[0]


def test_repeated_token_gradient_is_the_float32_sum():
    """1,000 correlated contributions to one row of a bf16 table: the
    row's gradient is their float32 sum, rounded once to bf16. (A bf16
    scatter-add stalls once the sum's spacing outgrows each addend: it
    returns 512 for a sum near 1,000.)"""
    cfg = dataclasses.replace(get_smoke("stablelm-1.6b"),
                              param_dtype=jnp.bfloat16)
    table = (jax.random.normal(jax.random.PRNGKey(0), (16, 8)) * 0.02
             ).astype(jnp.bfloat16)
    tokens = jnp.full((1, 1000), 3, jnp.int32)
    cot = jax.random.uniform(jax.random.PRNGKey(1), (1, 1000, 8),
                             minval=0.5, maxval=1.5).astype(jnp.bfloat16)
    got = _lookup_vjp(table, tokens, cot, cfg)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(cot, np.float64)[0].sum(0)
    # one rounding to bf16 (8 significant bits) of a float32 sum
    np.testing.assert_allclose(np.asarray(got[3], np.float64), want,
                               rtol=2.0 ** -8)
    assert not np.asarray(jnp.delete(got, 3, axis=0), np.float32).any()


def _old_embed(params, tokens, cfg, lay, pctx, key=None):
    """The lookup as it was: a gather from the table in its own dtype."""
    emb = params["embed"]
    out = jnp.take(emb, jnp.clip(tokens, 0, emb.shape[0] - 1), axis=0)
    return out.astype(cfg.param_dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("arch", ["gpt2-paper", "stablelm-1.6b"],
                         ids=["tied", "untied"])
def test_forward_is_bitwise_the_plain_gather(arch, dtype, monkeypatch):
    """Hidden states, logits and loss of the tied and untied smoke models
    are bitwise those of a gather from the table in its own dtype: a bf16
    row is exact in float32."""
    from repro.models import transformer

    cfg = dataclasses.replace(get_smoke(arch), param_dtype=jnp.dtype(dtype))
    assert cfg.tie_embeddings == (arch == "gpt2-paper")
    params = init_params(jax.random.PRNGKey(2), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    key = jax.random.PRNGKey(4)

    def run():
        x = forward_hidden(params, batch, cfg, ParallelCtx(), key=key)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                            head.astype(jnp.float32))
        loss = lm_loss(params, batch, cfg, ParallelCtx(), key=key,
                       seq_chunk=16)
        return [np.asarray(a) for a in (x.astype(jnp.float32), logits,
                                        loss)]

    new = run()
    monkeypatch.setattr(transformer, "embed_tokens", _old_embed)
    old = run()
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)

"""Weights and token batches of a cell, made from ``--seed`` on the device.

Both the program and the plain reference take their weights from here, so
the reference never reads anything the program made. Leaf keys come from
the leaf's path, not its position in a flattened tree, so the two sides
agree on every weight as long as they agree on the layout.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: both 32-bit halves of it count
    (``PRNGKey`` alone would fold seeds that differ above bit 32)."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def param_shapes(model: dict) -> dict:
    """Shapes of the dense family's parameters, layers stacked on dim 0.

    embed (V, D); final_ln (D,); stages[0]: ln1/ln2 (L, D), wq/wk/wv
    (L, D, H*dh), wo (L, H*dh, D), w_gate/w_up (L, D, F), w_down (L, F, D);
    lm_head (D, V) when the head is not tied to the embedding.
    """
    L, D, F = model["n_layers"], model["d_model"], model["d_ff"]
    V, H, KV = model["vocab_size"], model["n_heads"], model["n_kv_heads"]
    dh = model["head_dim"]
    layer = {
        "ln1": (L, D), "wq": (L, D, H * dh), "wk": (L, D, KV * dh),
        "wv": (L, D, KV * dh), "wo": (L, H * dh, D), "ln2": (L, D),
        "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
    }
    shapes = {"embed": (V, D), "final_ln": (D,), "stages": [layer]}
    if not model["tie_embeddings"]:
        shapes["lm_head"] = (D, V)
    return shapes


def path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _leaf(key, name: str, shape, dtype, scale: float):
    if name.rsplit("/", 1)[-1] in ("ln1", "ln2", "final_ln"):
        return jnp.ones(shape, dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = min(scale, 1.0 / math.sqrt(fan_in))
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make_params(seed: int, model: dict, dtype, *, out_shardings=None,
                scale: float = 0.02):
    """All weights in one jitted call on the device, in ``dtype``."""
    shapes = param_shapes(model)
    is_shape = lambda x: isinstance(x, tuple)
    named = jax.tree_util.tree_map_with_path(
        lambda p, s: (path_name(p), s), shapes, is_leaf=is_shape)

    def build(key):
        return jax.tree.map(lambda ns: _leaf(key, ns[0], ns[1], dtype, scale),
                            named, is_leaf=lambda x: isinstance(x, tuple)
                            and isinstance(x[0], str))

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def zipf_cdf(vocab: int, a: float) -> jnp.ndarray:
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    p = ranks ** (-a)
    return jnp.cumsum(p) / jnp.sum(p)


def batch_maker(traffic: dict, vocab: int, *, out_shardings=None):
    """``make(seed_key, step) -> {"tokens", "labels"}``: ``global_batch``
    rows of ``seq_len`` next-token pairs, ids drawn from a Zipf marginal
    with exponent ``zipf_a`` over the vocabulary. Every (seed, step) gives
    new rows; a row never repeats within a run."""
    b, s = traffic["global_batch"], traffic["seq_len"]
    a = float(traffic["zipf_a"])

    def make(key, step):
        cdf = zipf_cdf(vocab, a)
        u = jax.random.uniform(jax.random.fold_in(key, step), (b, s + 1))
        toks = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        toks = toks.astype(jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return jax.jit(make, out_shardings=out_shardings)

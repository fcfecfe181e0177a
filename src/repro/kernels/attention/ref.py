"""Dense causal attention: the oracle, and the path wherever the fused
kernel does not apply (CPU, interpret mode, shapes it does not take).

It writes the whole (B, H, S, S) score matrix: f32 scores scaled by
``scale``, the causal mask, an f32 softmax, probabilities cast to the
operands' dtype for P·V.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  scale: float, causal: bool = True) -> jnp.ndarray:
    """q, k, v: (B, S, H, dh) with H already expanded to the query heads
    -> (B, S, H, dh)."""
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

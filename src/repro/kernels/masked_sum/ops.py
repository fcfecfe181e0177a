"""Jit'd public wrapper for the drop-compensated shard reduction.

It runs the implementation ``kernels.runtime.impl()`` names -- the Pallas
kernel, compiled or interpreted, or its ``ref.py`` oracle -- read outside
the jit boundary, so the choice is part of the cache key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime

from .masked_sum import masked_mean_pallas
from .ref import masked_mean_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def _masked_mean(shards: jnp.ndarray, mask: jnp.ndarray, *,
                 impl: str) -> jnp.ndarray:
    if impl == "jnp":
        return masked_mean_ref(shards, mask)
    return masked_mean_pallas(shards, mask, interpret=impl == "interpret")


def masked_mean(shards: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Drop-compensated mean over N peer shards. (N, L) x (N, L) -> (L,);
    the tile-dense (N, rows, L) shard view reduces to (rows, L)."""
    return _masked_mean(shards, mask, impl=runtime.impl())

"""Jit'd public wrapper for the drop-compensated shard reduction.

The Pallas path's interpret/compile flag resolves through the process
kernel-mode policy (kernels/runtime) outside the jit boundary, so the
resolved flag is part of the cache key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime

from .masked_sum import masked_mean_pallas
from .ref import masked_mean_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def _masked_mean(shards: jnp.ndarray, mask: jnp.ndarray, *,
                 use_kernel: bool, interpret: bool) -> jnp.ndarray:
    if use_kernel:
        return masked_mean_pallas(shards, mask, interpret=interpret)
    return masked_mean_ref(shards, mask)


def masked_mean(shards: jnp.ndarray, mask: jnp.ndarray, *,
                use_kernel: bool = False) -> jnp.ndarray:
    """Drop-compensated mean over N peer shards. (N, L) x (N, L) -> (L,);
    the tile-dense (N, rows, L) shard view reduces to (rows, L)."""
    return _masked_mean(
        shards, mask, use_kernel=use_kernel,
        interpret=runtime.interpret_flag() if use_kernel else True)

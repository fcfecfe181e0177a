"""Jit'd public wrapper for the fused dequant + compensated reduction.

Accepts the per-Hadamard-block grids the collective layer carries
((nblk,)-shaped ``lo``/``step``) and views the codes one block a row,
``(N, nblk, block)``, with ``(nblk, 1)`` grids, before dispatching to the
Pallas kernel (compiled or interpreted) or the jnp oracle, as
``kernels.runtime.impl()`` names, read outside the jit boundary so the
choice is part of the cache key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime

from .dequant_reduce import dequant_masked_mean_pallas
from .ref import dequant_masked_mean_ref


@functools.partial(jax.jit, static_argnames=("block", "impl"))
def _dequant_masked_mean(codes: jnp.ndarray, lo: jnp.ndarray,
                         step: jnp.ndarray,
                         mask: jnp.ndarray | None = None, *, block: int,
                         impl: str) -> jnp.ndarray:
    # one Hadamard block a row: a flat (N, S) is a free reshape, and the
    # tile-dense shard view already has this shape
    n = codes.shape[0]
    nblk = codes[0].size // block
    rows = codes.reshape(n, nblk, block)
    mask_rows = None if mask is None else mask.reshape(rows.shape)
    lo_col = lo.reshape(nblk, 1)
    step_col = step.reshape(nblk, 1)
    if impl == "jnp":
        out = dequant_masked_mean_ref(rows, lo_col, step_col, mask_rows)
    else:
        out = dequant_masked_mean_pallas(rows, lo_col, step_col, mask_rows,
                                         interpret=impl == "interpret")
    return out.reshape(codes.shape[1:])


def dequant_masked_mean(codes: jnp.ndarray, lo: jnp.ndarray,
                        step: jnp.ndarray,
                        mask: jnp.ndarray | None = None, *,
                        block: int) -> jnp.ndarray:
    """Drop-compensated mean over N peers' dequantized codes.

    codes: (N, S) with S = nblk*block, or the tile-dense shard view
    (N, nblk, block); lo/step: (nblk,) or (nblk, 1) per-block grids; mask:
    arrivals of the codes' shape or None. Returns (S,) or (nblk, block)
    fp32, the shape of one peer's codes.
    """
    return _dequant_masked_mean(codes, lo, step, mask, block=block,
                                impl=runtime.impl())

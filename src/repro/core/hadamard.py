"""Randomized Hadamard Transform over gradient buckets (paper §3.3).

The bucket is processed in 2^k-element blocks (default 4096). Blockwise HT
commutes with TAR sharding as long as shard boundaries are block-aligned
(guaranteed by ``core.tar.pad_for_tar``), and the transform is linear, so

    decode(mean_i(encode(g_i))) == mean_i(g_i)        (exact, no drops)

while under drops the decoded error is spread across the whole block —
the paper's unbiased-estimate property (Fig 9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fwht import randomized_fwht
from repro.kernels.ht_quant import ht_amax, ht_encode_fused, ht_quant


def rademacher_sign(key: jax.Array, block: int) -> jnp.ndarray:
    """The random +-1 diagonal D shared by all workers for one step."""
    return jnp.where(jax.random.bernoulli(key, 0.5, (block,)), 1.0, -1.0).astype(
        jnp.float32)


def ht_encode(x: jnp.ndarray, key: jax.Array, *,
              block: int = 4096) -> jnp.ndarray:
    """Encode a flat, block-aligned bucket: per-block H @ (d * x).

    Routes through the fused engine's unquantized encode stage
    (``ht_encode_fused``: sign-flip + FWHT in one pass in the kernel) —
    the bits=0 configuration of kernels/ht_quant.
    """
    n = x.shape[-1]
    if n % block:
        raise ValueError(f"bucket length {n} not a multiple of block {block}")
    sign = rademacher_sign(key, block)
    y = ht_encode_fused(x.reshape(-1, block), sign)
    return y.reshape(x.shape)


def ht_decode(y: jnp.ndarray, key: jax.Array, *,
              block: int = 4096) -> jnp.ndarray:
    """Inverse of ``ht_encode`` with the same key: per-block d * (H @ y)."""
    n = y.shape[-1]
    if n % block:
        raise ValueError(f"bucket length {n} not a multiple of block {block}")
    sign = rademacher_sign(key, block)
    x = randomized_fwht(y.reshape(-1, block), sign, mode="decode")
    return x.reshape(y.shape)


# ------------------------------------------------- fused encode-side stages
# Same key->sign derivation as ht_encode, but the rotated bucket is never
# materialized: the kernels rotate in VMEM and emit only the reduction
# (per-block amax) or the uint8 codes (see kernels/ht_quant).

def ht_encode_amax(x: jnp.ndarray, key: jax.Array, *,
                   block: int = 4096) -> jnp.ndarray:
    """Per-block amax of ``ht_encode(x)`` without materializing it.

    x: flat block-aligned bucket -> (nblocks,) fp32 — the quantization-grid
    pass of the fused sync engine (pmax these across workers, then call
    :func:`ht_encode_quant` with the shared grids).
    """
    if x.shape[-1] % block:
        raise ValueError(f"bucket length {x.shape[-1]} not a multiple of "
                         f"block {block}")
    sign = rademacher_sign(key, block)
    return ht_amax(x.reshape(-1, block), sign)


def ht_encode_quant(x: jnp.ndarray, key: jax.Array, noise: jnp.ndarray,
                    lo: jnp.ndarray, step: jnp.ndarray, *,
                    block: int = 4096, bits: int = 8) -> jnp.ndarray:
    """Fused ``ht_encode`` + shared-grid stochastic quantization.

    x/noise: flat block-aligned; lo/step: (nblocks,) pmax-shared grids.
    Returns (nblocks, block) uint8 codes — one VMEM-resident pass.
    """
    sign = rademacher_sign(key, block)
    return ht_quant(x.reshape(-1, block), sign, noise.reshape(-1, block),
                    lo, step, bits=bits)

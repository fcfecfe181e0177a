"""Jit'd public wrappers for the fused HT-encode + quantize kernels.

``ht_amax`` / ``ht_quant`` operate on (rows, block) -- one Hadamard block
per row, the layout ``core.allreduce`` already uses. Each runs the
implementation ``kernels.runtime.impl()`` names -- the Pallas kernel,
compiled or interpreted, or its ``ref.py`` oracle (identical math) -- read
outside the jit boundary, so the choice is part of the cache key.

The unquantized fused variant of the engine is the existing sign+FWHT
single-pass kernel (``randomized_fwht``); ``ht_encode_fused`` re-exports it
here so the sync engine has one dispatch surface for both the quantized
(bits>0) and unquantized (bits=0) encode stages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.kernels.fwht import randomized_fwht

from .ht_quant import ht_amax_pallas, ht_quant_pallas
from .ref import ht_amax_ref, ht_quant_ref, ht_rotate_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def _ht_amax(x: jnp.ndarray, sign: jnp.ndarray, *, block_rows: int,
             impl: str) -> jnp.ndarray:
    if impl == "jnp":
        return ht_amax_ref(x, sign)
    return ht_amax_pallas(x, sign, block_rows=block_rows,
                          interpret=impl == "interpret")


def ht_amax(x: jnp.ndarray, sign: jnp.ndarray, *,
            block_rows: int = 64) -> jnp.ndarray:
    """Per-block amax of the rotated blocks, without materializing them.

    x: (rows, block) -> (rows,) fp32.
    """
    return _ht_amax(x, sign, block_rows=block_rows, impl=runtime.impl())


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "impl"))
def _ht_quant(x: jnp.ndarray, sign: jnp.ndarray, noise: jnp.ndarray,
              lo: jnp.ndarray, step: jnp.ndarray, *, bits: int,
              block_rows: int, impl: str) -> jnp.ndarray:
    if impl == "jnp":
        return ht_quant_ref(x, sign, noise, lo.reshape(-1),
                            step.reshape(-1), bits=bits)
    return ht_quant_pallas(x, sign, noise, lo, step, bits=bits,
                           block_rows=block_rows,
                           interpret=impl == "interpret")


def ht_quant(x: jnp.ndarray, sign: jnp.ndarray, noise: jnp.ndarray,
             lo: jnp.ndarray, step: jnp.ndarray, *, bits: int = 8,
             block_rows: int = 64) -> jnp.ndarray:
    """Fused sign-flip + FWHT + stochastic uniform quantization.

    x/noise: (rows, block); lo/step: (rows,) shared grids -> uint8 codes.
    """
    return _ht_quant(x, sign, noise, lo, step, bits=bits,
                     block_rows=block_rows, impl=runtime.impl())


def ht_encode_fused(x: jnp.ndarray, sign: jnp.ndarray) -> jnp.ndarray:
    """Unquantized fused encode (sign+FWHT one-pass): the bits=0 stage."""
    return randomized_fwht(x, sign, mode="encode")

"""Jit'd public wrappers for the THC quantization kernel.

Each runs the implementation ``kernels.runtime.impl()`` names -- the
Pallas kernel, compiled or interpreted, or its ``ref.py`` oracle -- read
outside the jit boundary, so the choice is part of the cache key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime

from .quant import grid_quant_pallas, uniform_quant_pallas
from .ref import grid_quant_ref, uniform_dequant_ref, uniform_quant_ref


@functools.partial(jax.jit, static_argnames=("bits", "impl"))
def _uniform_quant(x: jnp.ndarray, noise: jnp.ndarray, lohi: jnp.ndarray, *,
                   bits: int, impl: str) -> jnp.ndarray:
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim > 2 else x.reshape(1, -1) if x.ndim == 1 else x
    n2 = noise.reshape(x2.shape)
    if impl == "jnp":
        out = uniform_quant_ref(x2, n2, lohi[0], lohi[1], bits=bits)
    else:
        out = uniform_quant_pallas(x2, n2, lohi, bits=bits,
                                   interpret=impl == "interpret")
    return out.reshape(shape)


def uniform_quant(x: jnp.ndarray, noise: jnp.ndarray, lohi: jnp.ndarray, *,
                  bits: int = 8) -> jnp.ndarray:
    return _uniform_quant(x, noise, lohi, bits=bits, impl=runtime.impl())


@functools.partial(jax.jit, static_argnames=("bits", "impl"))
def _grid_quant(x: jnp.ndarray, noise: jnp.ndarray, lo: jnp.ndarray,
                step: jnp.ndarray, *, bits: int, impl: str) -> jnp.ndarray:
    if impl == "jnp":
        return grid_quant_ref(x, noise, lo, step, bits=bits)
    return grid_quant_pallas(x, noise, lo, step, bits=bits,
                             interpret=impl == "interpret")


def grid_quant(x: jnp.ndarray, noise: jnp.ndarray, lo: jnp.ndarray,
               step: jnp.ndarray, *, bits: int = 8) -> jnp.ndarray:
    """Quantize (rows, C) onto per-row [lo_r, lo_r + levels*step_r] grids.

    The shard-side (TAR stage-2) quantization stage of the fused sync
    engine: one Hadamard block per row, grids already pmax-shared. Kernel
    and jnp paths are bit-identical.
    """
    return _grid_quant(x, noise, lo, step, bits=bits, impl=runtime.impl())


def uniform_dequant(codes: jnp.ndarray, lohi: jnp.ndarray, *, bits: int = 8,
                    nsum: int = 1) -> jnp.ndarray:
    """Elementwise dequant — XLA fuses this; no kernel needed."""
    return uniform_dequant_ref(codes, lohi[0], lohi[1], bits=bits, nsum=nsum)

"""Lossy/compression baselines the paper compares against (Fig 16).

  * Top-K sparsification (Stich et al.) with error feedback memory.
  * TernGrad (Wen et al.): stochastic ternarization onto {-s, 0, +s}.
  * THC (Li et al.): Hadamard rotation + shared-grid uniform stochastic
    quantization; codes are *homomorphic* — they are summed across workers
    and dequantized once (reuses the FWHT and quant kernels).

These all decide statically how much to send before transmission; the paper's
point (reproduced in bench_compression) is that this does not remove tails.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.fwht import randomized_fwht
from repro.kernels.quant import uniform_dequant, uniform_quant
from .hadamard import rademacher_sign


# --------------------------------------------------------------------- Top-K
class TopKState(NamedTuple):
    error: jnp.ndarray  # error-feedback memory, same shape as the bucket


def topk_init(length: int) -> TopKState:
    return TopKState(error=jnp.zeros((length,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("k",))
def topk_compress(x: jnp.ndarray, state: TopKState, *, k: int):
    """Keep the k largest-|.| entries of (x + error); the rest feed back."""
    corrected = x + state.error
    _, idx = jax.lax.top_k(jnp.abs(corrected), k)
    vals = corrected[idx]
    sparse = jnp.zeros_like(corrected).at[idx].set(vals)
    new_state = TopKState(error=corrected - sparse)
    return sparse, new_state


# ------------------------------------------------------------------ TernGrad
@jax.jit
def terngrad_compress(x: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
    """Unbiased stochastic ternarization: E[out] == x (scale s = max|x|)."""
    s = jnp.max(jnp.abs(x))
    p = jnp.where(s > 0, jnp.abs(x) / s, 0.0)
    b = jax.random.bernoulli(key, p, x.shape)
    return s * jnp.sign(x) * b.astype(x.dtype)


# ----------------------------------------------------------------------- THC
class THCCompressed(NamedTuple):
    codes: jnp.ndarray   # uint8 (rows, block)
    lohi: jnp.ndarray    # shared (2,) quantization range


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def thc_compress(x: jnp.ndarray, key: jax.Array, lohi: jnp.ndarray, *,
                 bits: int = 4, block: int = 4096) -> THCCompressed:
    """Rotate (randomized HT) then quantize onto the shared [lo, hi] grid.

    ``lohi`` must be agreed across workers (THC pre-negotiates the range;
    we compute it from a profiling step). x: flat, length % block == 0.
    """
    sign = rademacher_sign(key, block)
    rot = randomized_fwht(x.reshape(-1, block), sign, mode="encode")
    noise = jax.random.uniform(jax.random.fold_in(key, 1), rot.shape)
    codes = uniform_quant(rot, noise, lohi, bits=bits)
    return THCCompressed(codes=codes, lohi=lohi)


@functools.partial(jax.jit, static_argnames=("bits", "block", "nsum"))
def thc_decompress_sum(code_sum: jnp.ndarray, key: jax.Array,
                       lohi: jnp.ndarray, *, bits: int = 4, block: int = 4096,
                       nsum: int = 1) -> jnp.ndarray:
    """Dequantize a *sum* of nsum workers' codes, un-rotate, divide by nsum."""
    sign = rademacher_sign(key, block)
    rot_sum = uniform_dequant(code_sum, lohi, bits=bits, nsum=nsum)
    mean_rot = rot_sum / nsum
    out = randomized_fwht(mean_rot, sign, mode="decode")
    return out.reshape(-1)

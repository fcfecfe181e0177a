"""Pallas TPU kernel: fused uniform stochastic quantization (THC baseline).

Fuses the subtract/scale/stochastic-round/clip chain into one VMEM pass so the
compression epilogue after the FWHT rotation costs a single HBM round-trip.

Grid: one program per (TILE_R, C) row-tile. lo/hi are scalars broadcast as a
(1, 1) operand (shared quantization range across workers — the property THC
needs for homomorphic aggregation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime

# independent row tiles: no cross-iteration state, Mosaic may parallelize
_ROW_GRID = pltpu.CompilerParams(dimension_semantics=("parallel",))


def to_codes(q, dtype):
    """Integral fp32 levels -> integer codes of ``dtype`` inside a kernel.
    Mosaic has no direct float -> uint8 cast, so the codes pass through
    int32 (exact: every level fits)."""
    return q.astype(jnp.int32).astype(dtype)


def from_codes(c):
    """Integer codes -> fp32 inside a kernel, through int32 (Mosaic has no
    direct uint8 -> float cast)."""
    return c.astype(jnp.int32).astype(jnp.float32)


def _quant_kernel(x_ref, n_ref, r_ref, o_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    u = n_ref[...].astype(jnp.float32)
    lo = r_ref[0, 0]
    hi = r_ref[0, 1]
    step = (hi - lo) / levels
    q = jnp.floor((x - lo) / step + u)
    o_ref[...] = to_codes(jnp.clip(q, 0, levels), o_ref.dtype)


def _grid_quant_kernel(x_ref, n_ref, lo_ref, step_ref, o_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    u = n_ref[...].astype(jnp.float32)
    lo = lo_ref[...]                                   # (rows, 1) per-row
    step = step_ref[...]
    q = jnp.floor((x - lo) / step + u)
    o_ref[...] = to_codes(jnp.clip(q, 0, levels), o_ref.dtype)


def grid_quant_pallas(x: jnp.ndarray, noise: jnp.ndarray, lo: jnp.ndarray,
                      step: jnp.ndarray, *, bits: int = 8,
                      block_rows: int = 128,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Quantize (rows, C) onto per-row [lo_r, lo_r + levels*step_r] grids.

    The grid-aware sibling of :func:`uniform_quant_pallas`: lo/step are
    (rows,) operands tiled alongside the data, so one pass covers every
    Hadamard block of a shard (TAR stage-2 re-quantization).
    ``interpret=None`` resolves the process kernel mode (kernels/runtime)."""
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _grid_quant_call(x, noise, lo, step, bits=bits,
                            block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret"))
def _grid_quant_call(x: jnp.ndarray, noise: jnp.ndarray, lo: jnp.ndarray,
                     step: jnp.ndarray, *, bits: int = 8,
                     block_rows: int = 128,
                     interpret: bool = True) -> jnp.ndarray:
    if x.ndim != 2 or noise.shape != x.shape:
        raise ValueError("x and noise must both be (rows, C)")
    rows, c = x.shape
    levels = (1 << bits) - 1
    br = min(block_rows, rows)
    pad = (-rows) % br
    lo2 = lo.reshape(rows, 1).astype(jnp.float32)
    step2 = step.reshape(rows, 1).astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        noise = jnp.pad(noise, ((0, pad), (0, 0)))
        lo2 = jnp.pad(lo2, ((0, pad), (0, 0)))
        step2 = jnp.pad(step2, ((0, pad), (0, 0)),
                        constant_values=1.0)           # avoid 0-div pad rows
    out = pl.pallas_call(
        functools.partial(_grid_quant_kernel, levels=levels),
        grid=(x.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint8),
        compiler_params=_ROW_GRID,
        interpret=interpret,
    )(x, noise, lo2, step2)
    if pad:
        out = out[:rows]
    return out


def uniform_quant_pallas(x: jnp.ndarray, noise: jnp.ndarray,
                         lohi: jnp.ndarray, *, bits: int = 8,
                         block_rows: int = 128,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Quantize (rows, C) onto the shared [lo, hi] grid. lohi: shape (2,).
    ``interpret=None`` resolves the process kernel mode (kernels/runtime)."""
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _uniform_quant_call(x, noise, lohi, bits=bits,
                               block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret"))
def _uniform_quant_call(x: jnp.ndarray, noise: jnp.ndarray,
                        lohi: jnp.ndarray, *, bits: int = 8,
                        block_rows: int = 128,
                        interpret: bool = True) -> jnp.ndarray:
    if x.ndim != 2 or noise.shape != x.shape:
        raise ValueError("x and noise must both be (rows, C)")
    rows, c = x.shape
    levels = (1 << bits) - 1
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        noise = jnp.pad(noise, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        grid=(x.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint8),
        compiler_params=_ROW_GRID,
        interpret=interpret,
    )(x, noise, lohi.reshape(1, 2).astype(jnp.float32))
    if pad:
        out = out[:rows]
    return out

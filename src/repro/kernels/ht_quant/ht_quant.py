"""Pallas TPU kernels: fused randomized-Hadamard encode + THC quantization.

The unfused OptiReduce-Q encode path costs three full HBM round trips per
bucket: FWHT encode (read x, write rotated), per-block amax (read rotated),
quantize (read rotated + noise, write codes) — the rotated fp32 copy is
materialized purely to be re-read twice. The fused engine never writes it:

  ht_amax   — sign-flip + blocked MXU FWHT + per-block |.|max in one
              VMEM-resident pass (reads x once, writes one scalar per block).
  ht_quant  — sign-flip + blocked MXU FWHT + shared-grid stochastic uniform
              quantization in one VMEM-resident pass (reads x + noise once,
              writes uint8 codes). The rotation is recomputed (MXU FLOPs are
              free next to HBM here), so per bucket the encode side touches
              HBM exactly twice per input byte instead of four times and
              emits 1/4 the bytes.

Both kernels are *double-buffered*: the operands sit in ``ANY`` (HBM) memory
space and each grid iteration's row block is streamed into a two-slot
revolving VMEM buffer with explicit async copies, so block i+1's HBM loads
are in flight while block i rotates on the MXU — the codec kernels overlap
their own HBM traffic instead of serializing load → rotate → store per
block.  One shared pipeline body (``kernels/dma.py``) carries the DMA
schedule for both kernels; the per-kernel difference is only the epilogue
consuming the rotated block (amax-reduce vs quantize), so there is exactly
one copy of the revolving-buffer logic and of the rotation math
(``mxu_rotate_block``, which yields the rotation as 128-lane chunks) on the
Pallas side.

The grids arrive as per-row (= per-Hadamard-block) ``lo``/``step`` operands
because THC needs them pmax-shared across workers *between* the amax and the
quantization — that collective now rides the pipelined schedule's exchange
stage (core/pipeline.py) instead of splitting the encode.

VMEM per program (fp32, block_rows=64, n=4096): two x slots (2 MB) + two
noise slots (2 MB, ht_quant only) + the factor matrix + the pipelined
output block — ~5 MB, well within ~16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.dma import SEQUENTIAL_GRID, revolving_pipeline, row_loads
from repro.kernels.fwht.fwht import mxu_rotate_block, row_block
from repro.kernels.fwht.ref import hadamard_matrix, lane_factors
from repro.kernels.quant.quant import to_codes


def _rotate(x, sign, hl):
    """sign-flip + blocked FWHT of (rows, n) as 128-lane chunks, sharing the
    fwht kernel's rotation body (single copy of the MXU math on the Pallas
    side)."""
    return mxu_rotate_block(x.astype(jnp.float32) * sign, hl)


def _rotation_pipeline(nblk: int, streams, sem, epilogue):
    """Two-slot revolving-buffer schedule over row blocks (kernels/dma)."""
    revolving_pipeline(
        nblk, functools.partial(row_loads, streams, sem), epilogue)


def _ht_amax_kernel(x_hbm, sign_ref, hl_ref, o_ref, xbuf, sem, *,
                    nblk: int, rows: int):
    def epilogue(slot):
        chunks = _rotate(xbuf[slot], sign_ref[...].astype(jnp.float32),
                         hl_ref[...])
        amax = [jnp.max(jnp.abs(yj), axis=1, keepdims=True) for yj in chunks]
        o_ref[...] = functools.reduce(jnp.maximum, amax)

    _rotation_pipeline(nblk, [(x_hbm, xbuf, rows)], sem, epilogue)


def _ht_quant_kernel(x_hbm, sign_ref, noise_hbm, lo_ref, step_ref,
                     hl_ref, o_ref, xbuf, nbuf, sem,
                     *, nblk: int, rows: int, levels: int):
    def epilogue(slot):
        chunks = _rotate(xbuf[slot], sign_ref[...].astype(jnp.float32),
                         hl_ref[...])
        lo = lo_ref[...]                             # (rows, 1)
        step = step_ref[...]                         # (rows, 1)
        lanes = chunks[0].shape[1]
        for j, yj in enumerate(chunks):
            cols = slice(j * lanes, (j + 1) * lanes)
            u = nbuf[slot, :, cols].astype(jnp.float32)
            q = jnp.floor((yj - lo) / step + u)
            o_ref[:, cols] = to_codes(jnp.clip(q, 0, levels), o_ref.dtype)

    _rotation_pipeline(
        nblk, [(x_hbm, xbuf, rows), (noise_hbm, nbuf, rows)], sem, epilogue)


def _factor(n: int):
    """The +-1 (l, l) lane factor of H_n (the kernels scale by 1/sqrt(n))."""
    _, lanes = lane_factors(n)
    return lanes, hadamard_matrix(lanes, orthonormal=False)


def ht_amax_pallas(x: jnp.ndarray, sign: jnp.ndarray, *,
                   block_rows: int = 64,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Per-block amax of the rotated blocks. x: (rows, n) -> (rows,) fp32.

    ``interpret=None`` resolves the process kernel mode (kernels/runtime).
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _ht_amax_call(x, sign, block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _ht_amax_call(x: jnp.ndarray, sign: jnp.ndarray, *,
                  block_rows: int = 64,
                  interpret: bool = True) -> jnp.ndarray:
    if x.ndim != 2:
        raise ValueError("ht_amax_pallas expects (rows, n)")
    rows, n = x.shape
    lanes, hl = _factor(n)
    br = row_block(rows, block_rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nblk = x.shape[0] // br
    out = pl.pallas_call(
        functools.partial(_ht_amax_kernel, nblk=nblk, rows=br),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # x: streamed manually
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((lanes, lanes), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, br, n), x.dtype),
                        pltpu.SemaphoreType.DMA((1, 2))],
        compiler_params=SEQUENTIAL_GRID,
        interpret=interpret,
    )(x, sign.reshape(1, n).astype(jnp.float32), hl)
    return out[:rows, 0]


def ht_quant_pallas(x: jnp.ndarray, sign: jnp.ndarray, noise: jnp.ndarray,
                    lo: jnp.ndarray, step: jnp.ndarray, *, bits: int = 8,
                    block_rows: int = 64,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Fused encode: codes = clip(floor((H(d*x) - lo)/step + noise)).

    x/noise: (rows, n); lo/step: (rows,) per-block grid bounds (already
    pmax-shared across workers). Returns (rows, n) uint8 codes.
    ``interpret=None`` resolves the process kernel mode (kernels/runtime).
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _ht_quant_call(x, sign, noise, lo, step, bits=bits,
                          block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_rows", "interpret"))
def _ht_quant_call(x: jnp.ndarray, sign: jnp.ndarray, noise: jnp.ndarray,
                   lo: jnp.ndarray, step: jnp.ndarray, *, bits: int = 8,
                   block_rows: int = 64,
                   interpret: bool = True) -> jnp.ndarray:
    if x.ndim != 2 or noise.shape != x.shape:
        raise ValueError("x and noise must both be (rows, n)")
    rows, n = x.shape
    lanes, hl = _factor(n)
    levels = (1 << bits) - 1
    br = row_block(rows, block_rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        noise = jnp.pad(noise, ((0, pad), (0, 0)))
        lo = jnp.pad(lo.reshape(-1), (0, pad))
        step = jnp.pad(step.reshape(-1), (0, pad), constant_values=1.0)
    nblk = x.shape[0] // br
    out = pl.pallas_call(
        functools.partial(_ht_quant_kernel, nblk=nblk, rows=br,
                          levels=levels),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # x: streamed manually
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),    # noise: streamed
            # the (rows, 1) grids ride Pallas' own block pipeline: a manual
            # one-lane DMA slice is not tile-aligned for Mosaic
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((lanes, lanes), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint8),
        scratch_shapes=[pltpu.VMEM((2, br, n), x.dtype),
                        pltpu.VMEM((2, br, n), noise.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=SEQUENTIAL_GRID,
        interpret=interpret,
    )(x, sign.reshape(1, n).astype(jnp.float32), noise,
      lo.reshape(-1, 1).astype(jnp.float32),
      step.reshape(-1, 1).astype(jnp.float32), hl)
    return out[:rows]

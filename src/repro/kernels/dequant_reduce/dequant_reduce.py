"""Pallas TPU kernel: fused per-block dequantization + drop-compensated mean.

The receive side of OptiReduce-Q dequantizes the (N, S) uint8 peer codes into
an (N, S) float32 intermediate and then reduces it — 5 bytes of HBM traffic
per received byte plus a full-size transient. This kernel fuses both: each
program loads an (N, TILE) slab of codes (+ mask), dequantizes in VMEM with
the per-column grid rows, and emits the compensated mean — one HBM read per
operand byte, no (N, S) float32 ever materialized.

The kernel is *double-buffered* (kernels/dma.py): operands live in ``ANY``
(HBM) memory space and each grid iteration's column slab is streamed into
two-slot revolving VMEM buffers with explicit async copies, so slab i+1's
HBM loads overlap slab i's dequant + reduction.

``lo``/``step`` arrive pre-broadcast as (1, S) rows (a per-Hadamard-block
value repeated ``block`` times — S fp32, negligible next to N*S codes), so
tile boundaries need no alignment with quantization blocks.

VMEM per program: 2 slots of N*TILE (codes u8) + N*TILE*4 (mask) + 2*TILE*4
(grids); N=16, TILE=2048 -> ~360 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.dma import SEQUENTIAL_GRID, col_loads, revolving_pipeline
from repro.kernels.masked_sum.masked_sum import compensated_mean_cols
from repro.kernels.quant.quant import from_codes

# uint8 arrays in HBM are tiled four rows deep and a DMA slice must cover
# whole tiles, so the peer axis of the codes is padded to this many rows
CODE_ROW_TILE = 4

def _slab_pipeline(nblk: int, streams, sem, epilogue):
    """Two-slot revolving-buffer schedule over column slabs (kernels/dma)."""
    revolving_pipeline(
        nblk, functools.partial(col_loads, streams, sem), epilogue)


def _dequant_masked_mean_kernel(c_hbm, lo_hbm, step_hbm, m_hbm, o_ref,
                                cbuf, lobuf, stepbuf, mbuf, sem, *,
                                nblk: int, tile: int, n: int):
    def epilogue(slot):
        x = from_codes(cbuf[slot])[:n]              # (N, TILE)
        x = x * stepbuf[slot] + lobuf[slot]         # grids broadcast over rows
        m = mbuf[slot][:n].astype(jnp.float32)      # (N, TILE)
        out = compensated_mean_cols(x, m)
        o_ref[...] = out[None, :].astype(o_ref.dtype)

    _slab_pipeline(
        nblk,
        [(c_hbm, cbuf, tile), (lo_hbm, lobuf, tile),
         (step_hbm, stepbuf, tile), (m_hbm, mbuf, tile)],
        sem, epilogue)


def _dequant_mean_kernel(c_hbm, lo_hbm, step_hbm, o_ref,
                         cbuf, lobuf, stepbuf, sem, *, nblk: int, tile: int,
                         n: int):
    def epilogue(slot):
        x = from_codes(cbuf[slot])[:n]
        x = x * stepbuf[slot] + lobuf[slot]
        o_ref[...] = jnp.mean(x, axis=0, keepdims=True).astype(o_ref.dtype)

    _slab_pipeline(
        nblk,
        [(c_hbm, cbuf, tile), (lo_hbm, lobuf, tile), (step_hbm, stepbuf, tile)],
        sem, epilogue)


def dequant_masked_mean_pallas(codes: jnp.ndarray, lo_row: jnp.ndarray,
                               step_row: jnp.ndarray,
                               mask: jnp.ndarray | None = None, *,
                               tile: int = 2048,
                               interpret: bool | None = None) -> jnp.ndarray:
    """Compensated mean of dequantized peer codes.

    codes: (N, S) uint; lo_row/step_row: (S,) per-column grids;
    mask: (N, S) 0/1 arrivals or None (lossless). Returns (S,) fp32.
    ``interpret=None`` resolves the process kernel mode (kernels/runtime).
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _dequant_masked_mean_call(codes, lo_row, step_row, mask,
                                     tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _dequant_masked_mean_call(codes: jnp.ndarray, lo_row: jnp.ndarray,
                              step_row: jnp.ndarray,
                              mask: jnp.ndarray | None = None, *,
                              tile: int = 2048,
                              interpret: bool = True) -> jnp.ndarray:
    if codes.ndim != 2:
        raise ValueError("codes must be (N, S)")
    n, length = codes.shape
    t = min(tile, length)
    pad = (-length) % t
    rows = -(-n // CODE_ROW_TILE) * CODE_ROW_TILE   # pad rows: never read
    lo2 = lo_row.reshape(1, length).astype(jnp.float32)
    step2 = step_row.reshape(1, length).astype(jnp.float32)
    if pad or rows != n:
        codes = jnp.pad(codes, ((0, rows - n), (0, pad)))
        lo2 = jnp.pad(lo2, ((0, 0), (0, pad)))
        step2 = jnp.pad(step2, ((0, 0), (0, pad)))
        if mask is not None:
            mask = jnp.pad(mask, ((0, rows - n), (0, pad)))
    padded = codes.shape[1]
    nblk = padded // t
    col = pl.BlockSpec((1, t), lambda i: (0, i))
    hbm = pl.BlockSpec(memory_space=pl.ANY)       # streamed manually
    grid_bufs = [pltpu.VMEM((2, 1, t), jnp.float32),
                 pltpu.VMEM((2, 1, t), jnp.float32)]
    if mask is None:
        kernel, args = _dequant_mean_kernel, (codes, lo2, step2)
        in_specs = [hbm, hbm, hbm]
        scratch = [pltpu.VMEM((2, rows, t), codes.dtype), *grid_bufs,
                   pltpu.SemaphoreType.DMA((3, 2))]
    else:
        kernel = _dequant_masked_mean_kernel
        args = (codes, lo2, step2, mask)
        in_specs = [hbm, hbm, hbm, hbm]
        scratch = [pltpu.VMEM((2, rows, t), codes.dtype), *grid_bufs,
                   pltpu.VMEM((2, rows, t), mask.dtype),
                   pltpu.SemaphoreType.DMA((4, 2))]
    out = pl.pallas_call(
        functools.partial(kernel, nblk=nblk, tile=t, n=n),
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=SEQUENTIAL_GRID,
        interpret=interpret,
    )(*args)
    out = out[0]
    if pad:
        out = out[:length]
    return out

"""Pallas TPU kernel: fused per-block dequantization + drop-compensated mean.

The receive side of OptiReduce-Q dequantizes the N peers' uint8 codes into a
float32 intermediate and then reduces it — 5 bytes of HBM traffic per
received byte plus a full-size transient. This kernel fuses both: each
program loads a slab of codes (+ mask), dequantizes in VMEM with the
per-block grids, and emits the compensated mean — one HBM read per operand
byte, no float32 codes ever materialized.

The codes arrive as ``(N, rows, L)`` with each row one Hadamard block
(L = block): the grids are then one value a row, ``(rows, 1)``, broadcast
over the lanes in VMEM, and the codes, mask and result move as
``(N, TR, L)`` / ``(TR, L)`` slabs of whole tiles through Pallas's own
double-buffered block pipeline (the peer axis is a major axis, so no row
padding of the codes is needed). TR is sized to ~1 MB a codes block, a
multiple of the 32-row uint8 tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.masked_sum.masked_sum import (compensated_mean_cols,
                                                 pad_rows, row_tile)
from repro.kernels.quant.quant import from_codes


def _dequant_rows_kernel(c_ref, lo_ref, step_ref, *refs, masked: bool):
    o_ref = refs[-1]
    x = from_codes(c_ref[...])                      # (N, TR, L)
    x = x * step_ref[...] + lo_ref[...]             # (TR, 1) grids per row
    if masked:
        out = compensated_mean_cols(x, refs[0][...].astype(jnp.float32))
    else:
        out = jnp.mean(x, axis=0)
    o_ref[...] = out.astype(o_ref.dtype)


def dequant_masked_mean_pallas(codes: jnp.ndarray, lo_col: jnp.ndarray,
                               step_col: jnp.ndarray,
                               mask: jnp.ndarray | None = None, *,
                               block_rows: int | None = None,
                               interpret: bool | None = None) -> jnp.ndarray:
    """Compensated mean of dequantized peer codes.

    codes: (N, rows, L) uint, each row one quantization block; lo_col /
    step_col: (rows, 1) per-row grids; mask: (N, rows, L) 0/1 arrivals or
    None (lossless). Returns (rows, L) fp32.
    ``block_rows`` overrides the rows a grid block takes.
    ``interpret=None`` takes ``runtime.interpret_flag()``.
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _dequant_masked_mean_call(codes, lo_col, step_col, mask,
                                     block_rows=block_rows,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _dequant_masked_mean_call(codes: jnp.ndarray, lo_col: jnp.ndarray,
                              step_col: jnp.ndarray,
                              mask: jnp.ndarray | None = None, *,
                              block_rows: int | None = None,
                              interpret: bool = True) -> jnp.ndarray:
    if codes.ndim != 3:
        raise ValueError("codes must be (N, rows, L)")
    n, rows, lane = codes.shape
    # uint8 sublane tiles are 32 rows deep
    tr = block_rows or row_tile(rows, n * lane, mult=32)
    codes, lo_col, step_col, mask = pad_rows(tr, codes, lo_col, step_col,
                                             mask)
    padded = codes.shape[1]
    slab = pl.BlockSpec((n, tr, lane), lambda i: (0, i, 0))
    col = pl.BlockSpec((tr, 1), lambda i: (i, 0))
    args = [codes, lo_col.astype(jnp.float32), step_col.astype(jnp.float32)]
    in_specs = [slab, col, col]
    if mask is not None:
        args.append(mask)
        in_specs.append(slab)
    out = pl.pallas_call(
        functools.partial(_dequant_rows_kernel, masked=mask is not None),
        grid=(padded // tr,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tr, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, lane), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return out[:rows] if padded != rows else out

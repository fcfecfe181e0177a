"""Pallas TPU kernel: fused drop-compensated shard reduction.

The paper (§6a) identifies the reduction stage as the next bottleneck and
proposes SmartNIC offload; the TPU-native answer is a single VMEM-resident
fused kernel: load a slab of peer shards + masks, compute the
received-count, the masked sum and the compensated mean in one pass — one
HBM read per operand byte, no intermediate (N, L) products materialized.

The kernel takes the tile-dense ``(N, rows, L)`` shard view the sync engine
hands it: each program reads ``(N, TR, L)`` slabs of whole (8, 128) tiles
(the peer axis is a major axis, so N = 1 and N = 4 fill tiles alike) and
writes ``(TR, L)``; TR is sized to ~1 MB of f32 per operand block. A flat
``(N, S)`` input is zero-padded to whole 128-lane rows and viewed as
``(N, S'/128, 128)``: every column's mean is computed alone, so the view
changes no bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime

# TPU vreg lane width: the row of a flat operand's tile-dense view
LANES = 128

# independent row blocks: no cross-iteration state, Mosaic may parallelize
_ROW_GRID = pltpu.CompilerParams(dimension_semantics=("parallel",))


def compensated_mean_cols(x, m):
    """Drop-compensated mean over peers (axis 0) of an (N, ...) slab.
    The single copy of the compensation rule on the Pallas side — the fused
    dequant_reduce kernel reuses it."""
    cnt = jnp.sum(m, axis=0)                    # (TR, L)
    s = jnp.sum(x * m, axis=0)                  # (TR, L)
    return jnp.where(cnt > 0, s / jnp.maximum(cnt, 1.0), 0.0)


def row_tile(rows: int, per_row: int, mult: int = 8,
             target: int = 1 << 18) -> int:
    """Rows per block of a tile-dense ``(..., rows, L)`` operand: about
    ``target`` elements a block (``per_row`` elements a row brings over
    every peer), a multiple of ``mult`` (the dtype's sublane tile) that
    divides ``rows``, or all the rows. Returns a non-divisor only when
    ``rows`` has no such divisor; the caller then pads."""
    want = max(mult, target // per_row // mult * mult)
    if rows <= want:
        return rows
    for tr in range(want, mult - 1, -mult):
        if rows % tr == 0:
            return tr
    return want


def pad_rows(tr: int, *arrays):
    """Zero-pad axis -2 of each array up to a multiple of ``tr`` (None
    passes through)."""
    def one(a):
        if a is None:
            return None
        pad = (-a.shape[-2]) % tr
        if not pad:
            return a
        widths = [(0, 0)] * a.ndim
        widths[-2] = (0, pad)
        return jnp.pad(a, widths)
    return [one(a) for a in arrays]


def _masked_mean_rows_kernel(x_ref, m_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, TR, L)
    m = m_ref[...].astype(jnp.float32)          # (N, TR, L)
    o_ref[...] = compensated_mean_cols(x, m).astype(o_ref.dtype)


def _masked_mean_rows(shards, mask, *, block_rows: int | None,
                      interpret: bool) -> jnp.ndarray:
    """(N, rows, L) x (N, rows, L) -> (rows, L)."""
    n, rows, lane = shards.shape
    tr = block_rows or row_tile(rows, n * lane)
    shards, mask = pad_rows(tr, shards, mask)
    padded = shards.shape[1]
    slab = pl.BlockSpec((n, tr, lane), lambda i: (0, i, 0))
    out = pl.pallas_call(
        _masked_mean_rows_kernel,
        grid=(padded // tr,),
        in_specs=[slab, slab],
        out_specs=pl.BlockSpec((tr, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, lane), shards.dtype),
        compiler_params=_ROW_GRID,
        interpret=interpret,
    )(shards, mask)
    return out[:rows] if padded != rows else out


def masked_mean_pallas(shards: jnp.ndarray, mask: jnp.ndarray, *,
                       block_rows: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Mean over received contributions. shards/mask: the tile-dense
    (N, rows, L) -> (rows, L), or flat (N, S) -> (S,).

    ``block_rows`` overrides the rows a grid block takes (default ~1 MB of
    f32 per operand block). ``interpret=None`` takes
    ``runtime.interpret_flag()``.
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _masked_mean_call(shards, mask, block_rows=block_rows,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _masked_mean_call(shards: jnp.ndarray, mask: jnp.ndarray, *,
                      block_rows: int | None = None,
                      interpret: bool = True) -> jnp.ndarray:
    if shards.ndim not in (2, 3) or mask.shape != shards.shape:
        raise ValueError("shards and mask must both be (N, S) or "
                         "(N, rows, L)")
    if shards.ndim == 3:
        return _masked_mean_rows(shards, mask, block_rows=block_rows,
                                 interpret=interpret)
    n, length = shards.shape
    pad = (-length) % LANES
    rows = [jnp.pad(a, ((0, 0), (0, pad))).reshape(n, -1, LANES)
            for a in (shards, mask)]
    out = _masked_mean_rows(*rows, block_rows=block_rows,
                            interpret=interpret)
    return out.reshape(-1)[:length]

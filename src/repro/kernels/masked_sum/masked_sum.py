"""Pallas TPU kernel: fused drop-compensated shard reduction.

The paper (§6a) identifies the reduction stage as the next bottleneck and
proposes SmartNIC offload; the TPU-native answer is a single VMEM-resident
fused kernel: load a (N, TILE) slab of peer shards + masks, compute the
received-count, the masked sum and the compensated mean in one pass — one
HBM read per operand byte, no intermediate (N, L) products materialized.

Grid: one program per TILE columns. VMEM per program (fp32):
N * TILE * 4 * 2 (shards + mask) + TILE * 4; N=16, TILE=2048 -> ~260 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime

# independent column tiles: no cross-iteration state, Mosaic may parallelize
_COL_GRID = pltpu.CompilerParams(dimension_semantics=("parallel",))


def compensated_mean_cols(x, m):
    """Drop-compensated mean over peers for an (N, TILE) slab -> (TILE,).
    The single copy of the compensation rule on the Pallas side — the fused
    dequant_reduce kernel reuses it."""
    cnt = jnp.sum(m, axis=0)                    # (TILE,)
    s = jnp.sum(x * m, axis=0)                  # (TILE,)
    return jnp.where(cnt > 0, s / jnp.maximum(cnt, 1.0), 0.0)


def _masked_mean_kernel(x_ref, m_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, TILE)
    m = m_ref[...].astype(jnp.float32)          # (N, TILE)
    out = compensated_mean_cols(x, m)
    o_ref[...] = out[None, :].astype(o_ref.dtype)


def masked_mean_pallas(shards: jnp.ndarray, mask: jnp.ndarray, *,
                       tile: int = 2048,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Mean over received contributions. shards/mask: (N, L) -> (L,).

    ``interpret=None`` resolves the process kernel mode (kernels/runtime).
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _masked_mean_call(shards, mask, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _masked_mean_call(shards: jnp.ndarray, mask: jnp.ndarray, *,
                      tile: int = 2048,
                      interpret: bool = True) -> jnp.ndarray:
    if shards.ndim != 2 or mask.shape != shards.shape:
        raise ValueError("shards and mask must both be (N, L)")
    n, length = shards.shape
    t = min(tile, length)
    pad = (-length) % t
    if pad:
        shards = jnp.pad(shards, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    padded = shards.shape[1]
    out = pl.pallas_call(
        _masked_mean_kernel,
        grid=(padded // t,),
        in_specs=[
            pl.BlockSpec((n, t), lambda i: (0, i)),
            pl.BlockSpec((n, t), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, t), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), shards.dtype),
        compiler_params=_COL_GRID,
        interpret=interpret,
    )(shards, mask)
    out = out[0]
    if pad:
        out = out[:length]
    return out

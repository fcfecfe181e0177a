"""Pallas TPU kernel: blocked fast Walsh-Hadamard transform (MXU, lane chunks).

TPU adaptation of the paper's randomized Hadamard Transform (§3.3, implemented
on GPU via HazyResearch's CUDA butterfly). A warp-shuffle butterfly does not
map to the TPU; instead we exploit H_n = H_c (x) H_l with l = 128 lanes: each
128-lane chunk of a row block is one (rows, 128) @ (128, 128) matmul on the
MXU, and H_c is a butterfly of whole-vreg adds across the c = n/128 chunks.
Every slice is lane-aligned, so Mosaic never has to split the lane axis.

Grid: one program per tile of ``block_rows`` rows; each program holds
(block_rows, n) of the input plus the (l, l) factor matrix in VMEM.

VMEM budget per program (fp32): block_rows*n*4*2 (in+out) + l^2*4,
e.g. block_rows=64, n=4096 -> 2.1 MB, well within ~16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime

from .ref import chunk_butterfly, fwht_scale, hadamard_matrix, lane_factors

# one program per independent row tile: Mosaic may run grid iterations in
# any order / in parallel windows (no cross-iteration scratch state)
_ROW_GRID = pltpu.CompilerParams(dimension_semantics=("parallel",))


def mxu_rotate_block(x, hl):
    """The blocked-FWHT body shared by every kernel that rotates: (rows, n)
    fp32 -> the c (rows, l) lane chunks of the orthonormal transform. The
    fused ht_quant kernels reuse this so there is exactly one copy of the
    rotation math on the Pallas side (``fwht_mxu_ref`` runs the same ops)."""
    n = x.shape[1]
    lanes = hl.shape[0]
    t = [jax.lax.dot(x[:, j * lanes:(j + 1) * lanes], hl,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
         for j in range(n // lanes)]
    scale = fwht_scale(n)
    return [tj * scale for tj in chunk_butterfly(t)]


def row_block(rows: int, block_rows: int) -> int:
    """Rows per program for the rotating kernels: ``block_rows``, or all the
    rows rounded up to whole 8-row sublane tiles. Every rotation matmul then
    has M >= 8, so no row count sends it down a matrix-vector path whose
    summation order differs (prefix invariance and jnp parity stay bitwise).
    """
    return min(block_rows, -(-rows // 8) * 8)


def store_chunks(o_ref, chunks):
    """Write lane chunks back into their column slices of ``o_ref``."""
    for j, yj in enumerate(chunks):
        lanes = yj.shape[1]
        o_ref[:, j * lanes:(j + 1) * lanes] = yj.astype(o_ref.dtype)


def _fwht_kernel(x_ref, hl_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (rows, n)
    store_chunks(o_ref, mxu_rotate_block(x, hl_ref[...]))


def _fwht_sign_kernel(x_ref, sign_ref, hl_ref, o_ref, *, sign_mode: str):
    x = x_ref[...].astype(jnp.float32)
    if sign_mode == "pre":
        x = x * sign_ref[...].astype(jnp.float32)    # (1, n)
    chunks = mxu_rotate_block(x, hl_ref[...])
    if sign_mode == "post":
        # per-chunk loads of the sign row: slicing a loaded (1, n) value
        # gives a layout Mosaic cannot broadcast
        lanes = chunks[0].shape[1]
        chunks = [yj * sign_ref[:, j * lanes:(j + 1) * lanes].astype(
                      jnp.float32) for j, yj in enumerate(chunks)]
    store_chunks(o_ref, chunks)


def fwht_pallas(x: jnp.ndarray,
                sign: jnp.ndarray | None = None,
                *,
                block_rows: int = 64,
                sign_mode: str = "none",
                interpret: bool | None = None) -> jnp.ndarray:
    """Orthonormal FWHT over the last axis of ``x`` (rows, n), n a power of 2.

    sign_mode: 'none' | 'pre' (encode: H @ (d*x)) | 'post' (decode: d * (H@y)).
    ``sign`` is required unless sign_mode == 'none'; shape (n,).
    ``interpret=None`` resolves the process kernel mode (kernels/runtime);
    the resolved flag is a static jit argument, so mode flips retrace.
    """
    if interpret is None:
        interpret = runtime.interpret_flag()
    return _fwht_call(x, sign, block_rows=block_rows, sign_mode=sign_mode,
                      interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "sign_mode", "interpret"))
def _fwht_call(x: jnp.ndarray,
               sign: jnp.ndarray | None = None,
               *,
               block_rows: int = 64,
               sign_mode: str = "none",
               interpret: bool = True) -> jnp.ndarray:
    if x.ndim != 2:
        raise ValueError("fwht_pallas expects (rows, n)")
    rows, n = x.shape
    _, lanes = lane_factors(n)
    hl = hadamard_matrix(lanes, orthonormal=False)   # +-1; 1/sqrt(n) after

    br = row_block(rows, block_rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (x.shape[0] // br,)

    if sign_mode == "none":
        kernel = _fwht_kernel
        in_specs = [
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((lanes, lanes), lambda i: (0, 0)),
        ]
        args = (x, hl)
    else:
        if sign is None:
            raise ValueError("sign required for sign_mode != 'none'")
        kernel = functools.partial(_fwht_sign_kernel, sign_mode=sign_mode)
        in_specs = [
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((lanes, lanes), lambda i: (0, 0)),
        ]
        args = (x, sign.reshape(1, n).astype(jnp.float32), hl)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_ROW_GRID,
        interpret=interpret,
    )(*args)
    if pad:
        out = out[:rows]
    return out

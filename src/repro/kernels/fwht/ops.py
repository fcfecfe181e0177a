"""Jit'd public wrappers for the FWHT kernel.

``fwht(x)`` operates on the last axis (any leading shape); the Pallas kernel
is used when requested, the lane-chunk jnp form otherwise (identical math, so
the dry-run HLO carries the kernel's FLOP structure).  Whether the Pallas
path runs interpreted or Mosaic-compiled resolves through the process
kernel-mode policy (kernels/runtime) outside the jit boundary, so the
resolved flag is part of the cache key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime

from .fwht import fwht_pallas
from .ref import fwht_mxu_ref


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "block_rows", "interpret"))
def _fwht(x: jnp.ndarray, *, use_kernel: bool, block_rows: int,
          interpret: bool) -> jnp.ndarray:
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    if use_kernel:
        y = fwht_pallas(x2, block_rows=block_rows, interpret=interpret)
    else:
        y = fwht_mxu_ref(x2)
    return y.reshape(shape)


def fwht(x: jnp.ndarray, *, use_kernel: bool = False,
         block_rows: int = 64) -> jnp.ndarray:
    """Orthonormal FWHT over the last axis. Involution: fwht(fwht(x)) == x."""
    return _fwht(x, use_kernel=use_kernel, block_rows=block_rows,
                 interpret=runtime.interpret_flag() if use_kernel else True)


def _randomized_fwht_impl(x: jnp.ndarray, sign: jnp.ndarray, *, mode: str,
                          use_kernel: bool, block_rows: int,
                          interpret: bool) -> jnp.ndarray:
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    if use_kernel:
        sign_mode = {"encode": "pre", "decode": "post"}[mode]
        y = fwht_pallas(x2, sign, sign_mode=sign_mode, block_rows=block_rows,
                        interpret=interpret)
    else:
        if mode == "encode":
            y = fwht_mxu_ref(x2 * sign[None, :])
        elif mode == "decode":
            y = fwht_mxu_ref(x2) * sign[None, :]
        else:
            raise ValueError(f"unknown mode {mode!r}")
    return y.reshape(shape)


# keep the nested-jit lowering name: the schedule tests identify the codec
# kernels in lowered HLO by their "randomized_fwht*" callee specializations
_randomized_fwht_impl.__name__ = "randomized_fwht"
_randomized_fwht = functools.partial(
    jax.jit, static_argnames=("mode", "use_kernel", "block_rows", "interpret"),
)(_randomized_fwht_impl)


def randomized_fwht(x: jnp.ndarray, sign: jnp.ndarray, *, mode: str,
                    use_kernel: bool = False,
                    block_rows: int = 64) -> jnp.ndarray:
    """Randomized HT: encode = H @ (d*x); decode = d * (H @ y) (exact inverse)."""
    return _randomized_fwht(
        x, sign, mode=mode, use_kernel=use_kernel, block_rows=block_rows,
        interpret=runtime.interpret_flag() if use_kernel else True)

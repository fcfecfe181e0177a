"""Pure-jnp oracle for the (randomized) fast Walsh-Hadamard transform.

Two reference implementations:

* ``fwht_ref``      — classic O(n log n) butterfly, the ground-truth oracle.
* ``fwht_mxu_ref``  — the lane-chunk MXU formulation (H_n = H_c (x) H_l with
  l = 128 lanes: one (l, l) matmul per lane chunk, then a butterfly across
  the c chunks). These are the *same ops the Pallas kernel runs*, so the two
  agree bitwise off-chip; it is the sync engine's jnp codec path.

Both are orthonormal: ``fwht(fwht(x)) == x``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def _log2(n: int) -> int:
    k = int(n).bit_length() - 1
    if (1 << k) != n:
        raise ValueError(f"block size must be a power of two, got {n}")
    return k


@functools.lru_cache(maxsize=32)
def hadamard_matrix_np(n: int) -> np.ndarray:
    """Unnormalized n x n Hadamard (Sylvester construction), float32."""
    _log2(n)
    h = np.array([[1.0]], dtype=np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def hadamard_matrix(n: int, *, orthonormal: bool = True) -> jnp.ndarray:
    h = hadamard_matrix_np(n)
    if orthonormal:
        h = h / np.sqrt(n).astype(np.float32)
    return jnp.asarray(h)


def lane_factors(n: int) -> tuple[int, int]:
    """n = c * l: l = min(n, 128) lanes per chunk, c chunks."""
    _log2(n)
    lanes = min(n, LANES)
    return n // lanes, lanes


def fwht_scale(n: int) -> np.float32:
    """The orthonormal 1/sqrt(n), applied once after the unnormalized
    transform (the +-1 factor matrix is exact in every MXU input dtype)."""
    return np.float32(1.0 / np.sqrt(n))


def fwht_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal FWHT over the last axis (butterfly oracle)."""
    orig_shape = x.shape
    orig_dtype = x.dtype
    n = orig_shape[-1]
    _log2(n)
    y = x.astype(jnp.float32).reshape(-1, n)
    h = 1
    while h < n:
        y = y.reshape(-1, n // (2 * h), 2, h)
        a = y[:, :, 0, :]
        b = y[:, :, 1, :]
        y = jnp.stack([a + b, a - b], axis=2).reshape(-1, n)
        h *= 2
    y = y / jnp.sqrt(jnp.float32(n))
    return y.reshape(orig_shape).astype(orig_dtype)


def chunk_butterfly(t: list) -> list:
    """In-place-order butterfly applying the unnormalized H_c across a list
    of c equally shaped chunks (the kernel's cross-chunk stage)."""
    c = len(t)
    h = 1
    while h < c:
        for i in range(0, c, 2 * h):
            for k in range(i, i + h):
                t[k], t[k + h] = t[k] + t[k + h], t[k] - t[k + h]
        h *= 2
    return t


def fwht_mxu_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal FWHT over the last axis, in the kernel's lane-chunk form.

    H_n = H_c (x) H_l (Sylvester ordering: index j*l + k). Each 128-lane
    chunk is multiplied by the +-1 H_l on the MXU, a butterfly across the
    chunks applies H_c, and one 1/sqrt(n) scale makes it orthonormal.
    """
    orig_shape = x.shape
    orig_dtype = x.dtype
    n = orig_shape[-1]
    c, lanes = lane_factors(n)
    hl = hadamard_matrix(lanes, orthonormal=False)
    t = jnp.einsum("rjl,lk->rjk", x.astype(jnp.float32).reshape(-1, c, lanes),
                   hl, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    h = 1
    while h < c:                     # chunk_butterfly, vectorized over pairs
        t = t.reshape(-1, c // (2 * h), 2, h, lanes)
        a, b = t[:, :, 0], t[:, :, 1]
        t = jnp.stack([a + b, a - b], axis=2).reshape(-1, c, lanes)
        h *= 2
    y = t * fwht_scale(n)
    return y.reshape(orig_shape).astype(orig_dtype)


def randomized_fwht_ref(
    x: jnp.ndarray, sign: jnp.ndarray, *, mode: str
) -> jnp.ndarray:
    """Randomized HT oracle. mode='encode': H @ (d * x); mode='decode': d * (H @ y).

    With orthonormal H, (H D)^-1 = D H, so decode inverts encode exactly.
    """
    if mode == "encode":
        return fwht_ref(x * sign)
    if mode == "decode":
        return fwht_ref(x) * sign
    raise ValueError(f"unknown mode {mode!r}")

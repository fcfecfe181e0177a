"""Pure-jnp oracle for the fused dequant + drop-compensated mean.

Composes the THC dequant (codes * step + lo on per-column grids) with the
``masked_sum`` compensated-mean estimator — the exact unfused pipeline the
kernel replaces, kept as its parity reference.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.masked_sum import masked_mean_ref


def dequant_masked_mean_ref(codes: jnp.ndarray, lo_row: jnp.ndarray,
                            step_row: jnp.ndarray,
                            mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """codes (N, S) with (S,) grid rows, or (N, rows, L) with (rows, 1)
    grid columns (both broadcast against a leading peer axis)."""
    vals = (codes.astype(jnp.float32) * step_row[None, :].astype(jnp.float32)
            + lo_row[None, :].astype(jnp.float32))
    if mask is None:
        return jnp.mean(vals, axis=0)
    return masked_mean_ref(vals, mask)

"""Fused sync-engine kernels: ht_quant (sign+FWHT+quantize) and
dequant_masked_mean (dequant+compensated mean) vs the composed unfused
oracle pipelines they replace — the parity contract of the fused engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, strategies as st
except ImportError:
    from _hypothesis_fallback import given, strategies as st

from repro.core.hadamard import (ht_encode, ht_encode_amax, ht_encode_quant,
                                 rademacher_sign)
from repro.kernels import runtime
from repro.kernels.dequant_reduce import (dequant_masked_mean,
                                          dequant_masked_mean_ref)
from repro.kernels.ht_quant import ht_amax, ht_quant
from repro.kernels.masked_sum import masked_mean_ref
from repro.kernels.quant import uniform_quant_ref


@pytest.mark.parametrize("rows,block", [(4, 256), (37, 1024), (64, 4096)])
def test_ht_amax_matches_composed(rows, block, impl):
    key = jax.random.PRNGKey(rows)
    x = jax.random.normal(key, (rows, block))
    sign = rademacher_sign(key, block)
    with runtime.impl_scope(impl):
        fused = ht_amax(x, sign)
    # composed: materialize the rotation, then reduce
    rot = ht_encode(x.reshape(-1), key, block=block).reshape(rows, block)
    composed = jnp.max(jnp.abs(rot), axis=1)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(composed))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("rows,block", [(8, 256), (37, 1024)])
def test_ht_quant_matches_composed(bits, rows, block, impl):
    """fused ht_quant == ht_encode -> uniform_quant_ref on per-block grids
    (bit-exact: same MXU rotation math, same grid arithmetic)."""
    key = jax.random.PRNGKey(bits * 100 + rows)
    x = jax.random.normal(key, (rows, block))
    sign = rademacher_sign(key, block)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    rot = ht_encode(x.reshape(-1), key, block=block).reshape(rows, block)
    amax = jnp.maximum(jnp.max(jnp.abs(rot), axis=1), 1e-12)
    levels = (1 << bits) - 1
    lo, step = -amax, 2.0 * amax / levels
    with runtime.impl_scope(impl):
        fused = ht_quant(x, sign, noise, lo, step, bits=bits)
    composed = jnp.stack([
        uniform_quant_ref(rot[r:r + 1], noise[r:r + 1], lo[r],
                          lo[r] + levels * step[r], bits=bits)[0]
        for r in range(rows)])
    np.testing.assert_array_equal(np.asarray(fused.astype(jnp.int32)),
                                  np.asarray(composed.astype(jnp.int32)))


def test_ht_quant_kernel_matches_jnp_path():
    """The interpreted kernel and the jnp oracle path agree bit-exactly."""
    key = jax.random.PRNGKey(3)
    rows, block = 19, 512
    x = jax.random.normal(key, (rows, block))
    sign = rademacher_sign(key, block)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    amax = jnp.maximum(ht_amax(x, sign), 1e-12)
    lo, step = -amax, 2.0 * amax / 255
    with runtime.impl_scope("interpret"):
        a = ht_quant(x, sign, noise, lo, step, bits=8)
    b = ht_quant(x, sign, noise, lo, step, bits=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 2**31 - 1))
def test_ht_encode_amax_never_materializes_mismatch(seed):
    """hadamard-layer wrappers (key -> sign derivation) match ht_encode."""
    key = jax.random.PRNGKey(seed)
    block = 256
    x = jax.random.normal(key, (8 * block,))
    with runtime.impl_scope("interpret"):
        fused = ht_encode_amax(x, key, block=block)
    rot = ht_encode(x, key, block=block).reshape(-1, block)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(jnp.max(jnp.abs(rot), axis=1)))


def test_ht_encode_quant_roundtrip_error_bound(impl):
    """dequant(fused codes) stays within one grid step of the rotation."""
    key = jax.random.PRNGKey(9)
    block, bits = 1024, 8
    x = jax.random.normal(key, (4 * block,))
    amax = jnp.maximum(ht_encode_amax(x, key, block=block), 1e-12)
    levels = (1 << bits) - 1
    lo, step = -amax, 2.0 * amax / levels
    noise = jax.random.uniform(jax.random.fold_in(key, 1), (4, block))
    with runtime.impl_scope(impl):
        codes = ht_encode_quant(x, key, noise, lo, step, block=block,
                                bits=bits)
    deq = codes.astype(jnp.float32) * step[:, None] + lo[:, None]
    rot = ht_encode(x, key, block=block).reshape(4, block)
    assert float(jnp.max(jnp.abs(deq - rot) / step[:, None])) <= 1.0 + 1e-5


@pytest.mark.parametrize("with_mask", [False, True])
@pytest.mark.parametrize("n,nblk,block", [(2, 3, 128), (8, 5, 256),
                                          (16, 2, 1024)])
def test_dequant_masked_mean_matches_composed(with_mask, n, nblk, block,
                                              impl):
    """fused dequant+reduce == dequant -> masked_mean_ref composed."""
    key = jax.random.PRNGKey(n + nblk)
    s = nblk * block
    codes = jax.random.randint(key, (n, s), 0, 256).astype(jnp.uint8)
    lo = jax.random.normal(key, (nblk,))
    step = jax.random.uniform(jax.random.fold_in(key, 1), (nblk,)) * 0.1 + 1e-3
    mask = None
    if with_mask:
        mask = (jax.random.uniform(jax.random.fold_in(key, 2), (n, s))
                > 0.1).astype(jnp.float32)
    with runtime.impl_scope(impl):
        fused = dequant_masked_mean(codes, lo, step, mask, block=block)
    vals = (codes.reshape(n, nblk, block).astype(jnp.float32)
            * step[None, :, None] + lo[None, :, None]).reshape(n, s)
    composed = (jnp.mean(vals, axis=0) if mask is None
                else masked_mean_ref(vals, mask))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(composed),
                               atol=1e-5)


def test_dequant_masked_mean_kernel_matches_ref_path():
    key = jax.random.PRNGKey(4)
    n, nblk, block = 8, 7, 128
    s = nblk * block
    codes = jax.random.randint(key, (n, s), 0, 256).astype(jnp.uint8)
    lo = jax.random.normal(key, (nblk,))
    step = jax.random.uniform(key, (nblk,)) * 0.05 + 1e-3
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), (n, s))
            > 0.3).astype(jnp.float32)
    with runtime.impl_scope("interpret"):
        a = dequant_masked_mean(codes, lo, step, mask, block=block)
    b = dequant_masked_mean(codes, lo, step, mask, block=block)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_dequant_masked_mean_all_dropped_column_is_zero(impl):
    """Columns nobody delivered reduce to 0 (skip-coordinate semantics)."""
    n, block = 4, 128
    codes = jnp.full((n, block), 200, jnp.uint8)
    lo = jnp.array([-1.0])
    step = jnp.array([0.01])
    mask = jnp.zeros((n, block))
    with runtime.impl_scope(impl):
        out = dequant_masked_mean(codes, lo, step, mask, block=block)
    assert float(jnp.max(jnp.abs(out))) == 0.0

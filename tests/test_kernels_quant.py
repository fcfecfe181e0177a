"""THC uniform stochastic quantization: kernel parity, error bound, and the
unbiasedness/homomorphic properties THC aggregation needs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, strategies as st
except ImportError:
    from _hypothesis_fallback import given, strategies as st

from repro.kernels import runtime
from repro.kernels.quant import (grid_quant, grid_quant_ref, uniform_dequant,
                                 uniform_quant, uniform_quant_ref)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(4, 256), (17, 1000)])
def test_kernel_matches_oracle(bits, shape, impl):
    key = jax.random.PRNGKey(bits)
    x = jax.random.normal(key, shape)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    lohi = jnp.array([float(x.min()) - 1e-3, float(x.max()) + 1e-3])
    with runtime.impl_scope(impl):
        a = uniform_quant(x, noise, lohi, bits=bits)
    b = uniform_quant_ref(x, noise, lohi[0], lohi[1], bits=bits)
    assert int(jnp.max(jnp.abs(a.astype(jnp.int32) -
                               b.astype(jnp.int32)))) == 0


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(4, 256), (37, 1000), (131, 256)])
def test_grid_quant_kernel_matches_ref(bits, shape, impl):
    """Per-row-grid quantizer (TAR stage-2 shard re-encode): kernel ==
    jnp oracle bit-exactly, including the padded-rows path."""
    key = jax.random.PRNGKey(bits + shape[0])
    x = jax.random.normal(key, shape)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=1), 1e-12)
    levels = (1 << bits) - 1
    lo, step = -amax, 2.0 * amax / levels
    with runtime.impl_scope(impl):
        a = grid_quant(x, noise, lo, step, bits=bits)
    b = grid_quant_ref(x, noise, lo, step, bits=bits)
    assert a.dtype == jnp.uint8
    assert int(jnp.max(jnp.abs(a.astype(jnp.int32) -
                               b.astype(jnp.int32)))) == 0


def test_grid_quant_matches_scalar_quant_on_uniform_grid(impl):
    """With every row sharing one grid, grid_quant degenerates to the
    scalar-grid uniform_quant."""
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (16, 512))
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    lohi = jnp.array([-4.0, 4.0])
    levels = 255
    lo = jnp.full((16,), -4.0)
    step = jnp.full((16,), 8.0 / levels)
    with runtime.impl_scope(impl):
        a = grid_quant(x, noise, lo, step, bits=8)
        b = uniform_quant(x, noise, lohi, bits=8)
    assert int(jnp.max(jnp.abs(a.astype(jnp.int32) -
                               b.astype(jnp.int32)))) == 0


@pytest.mark.parametrize("bits", [4, 8])
def test_dequant_error_bound(bits):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 512))
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    lohi = jnp.array([float(x.min()) - 1e-3, float(x.max()) + 1e-3])
    codes = uniform_quant(x, noise, lohi, bits=bits)
    step = float(lohi[1] - lohi[0]) / ((1 << bits) - 1)
    err = float(jnp.max(jnp.abs(uniform_dequant(codes, lohi, bits=bits) - x)))
    assert err <= step + 1e-5


@given(st.integers(0, 2**31 - 1))
def test_stochastic_rounding_unbiased(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, 64)) * 0.5
    lohi = jnp.array([-3.0, 3.0])
    trials = 256
    noise = jax.random.uniform(jax.random.fold_in(key, 7), (trials, 64))
    codes = jax.vmap(lambda n: uniform_quant(x[0:1], n[None], lohi,
                                             bits=4))(noise)
    deq = uniform_dequant(codes.astype(jnp.float32), lohi, bits=4)
    mean = jnp.mean(deq, axis=0)[0]
    step = 6.0 / 15
    assert float(jnp.max(jnp.abs(mean - x[0]))) < step / 2


def test_homomorphic_sum():
    """Sum of codes dequantizes to (approximately) the sum of values when
    quantized on a shared grid — THC's aggregation property."""
    key = jax.random.PRNGKey(2)
    n = 8
    xs = jax.random.normal(key, (n, 512))
    lohi = jnp.array([-6.0, 6.0])
    noise = jax.random.uniform(jax.random.fold_in(key, 1), xs.shape)
    codes = jax.vmap(lambda x, u: uniform_quant(x[None], u[None], lohi,
                                                bits=8))(xs, noise)
    code_sum = jnp.sum(codes.astype(jnp.int32), axis=0)
    approx = uniform_dequant(code_sum, lohi, bits=8, nsum=n)
    step = 12.0 / 255
    err = float(jnp.max(jnp.abs(approx - jnp.sum(xs, 0))))
    assert err <= n * step

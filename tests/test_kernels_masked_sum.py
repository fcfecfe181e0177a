"""Drop-compensated shard reduction: kernel parity + unbiasedness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, strategies as st
except ImportError:
    from _hypothesis_fallback import given, strategies as st

from repro.kernels import runtime
from repro.kernels.masked_sum import masked_mean, masked_mean_ref


@pytest.mark.parametrize("n", [2, 8, 16])
@pytest.mark.parametrize("length", [100, 2048, 5000])
def test_kernel_matches_oracle(n, length, impl):
    key = jax.random.PRNGKey(n * length)
    x = jax.random.normal(key, (n, length))
    m = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.8,
                             (n, length)).astype(jnp.float32)
    with runtime.impl_scope(impl):
        got = masked_mean(x, m)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(masked_mean_ref(x, m)), atol=1e-6)


def test_no_mask_is_mean():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
    m = jnp.ones_like(x)
    np.testing.assert_allclose(np.asarray(masked_mean_ref(x, m)),
                               np.asarray(jnp.mean(x, 0)), atol=1e-6)


def test_all_dropped_is_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    m = jnp.zeros_like(x)
    assert float(jnp.max(jnp.abs(masked_mean_ref(x, m)))) == 0.0


@given(st.integers(0, 2**31 - 1), st.floats(0.05, 0.5))
def test_unbiasedness(seed, rate):
    """E[compensated mean] == true mean when drops are value-independent
    (the §3.3 estimator property)."""
    rng = np.random.default_rng(seed)
    n, L, trials = 8, 64, 400
    x = rng.standard_normal((n, L)).astype(np.float32)
    true = x.mean(0)
    acc = np.zeros(L)
    for t in range(trials):
        m = (rng.random((n, L)) > rate).astype(np.float32)
        acc += np.asarray(masked_mean_ref(jnp.asarray(x), jnp.asarray(m)))
    est = acc / trials
    # standard error of the estimate shrinks with trials; loose 5-sigma band
    assert np.max(np.abs(est - true)) < 0.5

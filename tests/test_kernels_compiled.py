"""Mosaic-compiled kernel checks — run only on a real TPU backend.

CPU CI exercises every kernel in the Pallas interpreter; these tests close
the remaining gap on real hardware: the compiled double-buffered kernels
must agree with their interpreted selves (same grid, same revolving-buffer
DMA schedule, Mosaic lowering instead of the interpreter), and the
default implementation must actually be Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import runtime
from repro.kernels.dequant_reduce.dequant_reduce import \
    dequant_masked_mean_pallas
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.ht_quant.ht_quant import ht_amax_pallas, ht_quant_pallas
from repro.kernels.masked_sum.masked_sum import masked_mean_pallas
from repro.kernels.quant.quant import grid_quant_pallas, uniform_quant_pallas

pytestmark = pytest.mark.tpu


def _both_modes(fn):
    with runtime.impl_scope("interpret"):
        interp = np.asarray(fn())
    with runtime.impl_scope("mosaic"):
        comp = np.asarray(fn())
    return interp, comp


def test_auto_picks_compile_on_tpu():
    assert runtime.impl() == "mosaic"
    assert not runtime.interpret_flag()


@pytest.mark.parametrize("rows", [4, 37, 64])
def test_fwht_compiled_matches_interpret(rows):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1024))
    interp, comp = _both_modes(lambda: fwht_pallas(x, block_rows=16))
    np.testing.assert_allclose(comp, interp, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows", [4, 37])
def test_ht_amax_compiled_matches_interpret(rows):
    key = jax.random.PRNGKey(rows)
    x = jax.random.normal(key, (rows, 1024))
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1),
                                          shape=(1024,)), 1.0, -1.0)
    interp, comp = _both_modes(
        lambda: ht_amax_pallas(x, sign, block_rows=16))
    np.testing.assert_allclose(comp, interp, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows", [4, 37])
def test_ht_quant_compiled_matches_interpret(rows):
    key = jax.random.PRNGKey(rows)
    x = jax.random.normal(key, (rows, 1024))
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1),
                                          shape=(1024,)), 1.0, -1.0)
    noise = jax.random.uniform(jax.random.fold_in(key, 2), x.shape)
    amax = jnp.max(jnp.abs(x), axis=1) * jnp.sqrt(1024.0)
    lo, step = -amax, 2.0 * amax / 255.0
    interp, comp = _both_modes(
        lambda: ht_quant_pallas(x, sign, noise, lo, step, block_rows=16))
    # codes are integers: any float divergence at a rounding boundary moves
    # a code by at most 1 level
    assert np.abs(comp.astype(np.int32) - interp.astype(np.int32)).max() <= 1


def test_quant_compiled_matches_interpret():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (37, 512))
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    lohi = jnp.array([-3.0, 3.0])
    amax = jnp.max(jnp.abs(x), axis=1) + 0.1
    interp_u, comp_u = _both_modes(
        lambda: uniform_quant_pallas(x, noise, lohi, block_rows=16))
    assert np.abs(comp_u.astype(np.int32)
                  - interp_u.astype(np.int32)).max() <= 1
    interp_g, comp_g = _both_modes(
        lambda: grid_quant_pallas(x, noise, -amax, 2 * amax / 255,
                                  block_rows=16))
    assert np.abs(comp_g.astype(np.int32)
                  - interp_g.astype(np.int32)).max() <= 1


def test_reduce_kernels_compiled_match_interpret():
    key = jax.random.PRNGKey(3)
    shards = jax.random.normal(key, (8, 4096))
    mask = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.8,
                                shards.shape).astype(jnp.float32)
    interp_m, comp_m = _both_modes(
        lambda: masked_mean_pallas(shards, mask, block_rows=8))
    np.testing.assert_allclose(comp_m, interp_m, rtol=1e-6, atol=1e-6)
    # codes one 1024-wide quantization block a row, with per-row grids
    codes = jax.random.randint(jax.random.fold_in(key, 2), (8, 4, 1024),
                               0, 256, jnp.int32).astype(jnp.uint8)
    lo = jax.random.normal(jax.random.fold_in(key, 3), (4, 1))
    step = jax.random.uniform(jax.random.fold_in(key, 4), (4, 1),
                              minval=0.01, maxval=0.1)
    interp_d, comp_d = _both_modes(
        lambda: dequant_masked_mean_pallas(codes, lo, step,
                                           mask.reshape(codes.shape)))
    np.testing.assert_allclose(comp_d, interp_d, rtol=1e-6, atol=1e-6)

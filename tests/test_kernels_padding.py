"""Padding-edge property tests for every Pallas kernel package.

Each kernel pads its streaming axis up to a whole number of grid blocks
(rows to ``block_rows``) and slices the pad back off.
Because every output row/column depends only on its own input row/column,
the padded tail block must not perturb the kept prefix: for any prefix
length r — including r % block != 0, the tail-block path, and the
``step`` pad's ``constant_values=1.0`` guard — the kernel applied to the
prefix must equal the prefix of the kernel applied to the full operand,
*bit-exactly*, in interpret mode and (when a TPU backend is present)
Mosaic-compiled mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, strategies as st
except ImportError:
    from _hypothesis_fallback import given, strategies as st

from repro.kernels import runtime
from repro.kernels.dequant_reduce.dequant_reduce import \
    dequant_masked_mean_pallas
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.ht_quant.ht_quant import ht_amax_pallas, ht_quant_pallas
from repro.kernels.masked_sum.masked_sum import masked_mean_pallas
from repro.kernels.quant.quant import grid_quant_pallas, uniform_quant_pallas

# compiled mode rides along automatically when this suite runs on a TPU box
MODES = ["interpret"] + (
    ["mosaic"] if jax.default_backend() == "tpu" else [])

N = 128          # Hadamard block / column width
BR = 8           # block_rows: small so tails are cheap to sweep
R = 3 * BR       # full row count (a whole number of blocks: no pad)

rows_st = st.integers(min_value=1, max_value=R)
seed_st = st.integers(min_value=0, max_value=2**31 - 1)


def _rows_data(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (R, N), jnp.float32)
    sign = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(key, 1), shape=(N,)),
        1.0, -1.0).astype(jnp.float32)
    noise = jax.random.uniform(jax.random.fold_in(key, 2), (R, N))
    amax = jnp.max(jnp.abs(x), axis=1) + 0.1
    lo = -amax
    step = 2.0 * amax / 255.0
    return x, sign, noise, lo, step


def _assert_prefix(run_full, run_prefix):
    for mode in MODES:
        with runtime.impl_scope(mode):
            full = np.asarray(run_full())
            prefix = np.asarray(run_prefix())
        np.testing.assert_array_equal(prefix, full[:prefix.shape[0]])


@given(rows_st, seed_st)
def test_fwht_prefix_invariant(r, seed):
    x, _, _, _, _ = _rows_data(seed)
    _assert_prefix(lambda: fwht_pallas(x, block_rows=BR),
                   lambda: fwht_pallas(x[:r], block_rows=BR))


@given(rows_st, seed_st)
def test_ht_amax_prefix_invariant(r, seed):
    x, sign, _, _, _ = _rows_data(seed)
    _assert_prefix(lambda: ht_amax_pallas(x, sign, block_rows=BR),
                   lambda: ht_amax_pallas(x[:r], sign, block_rows=BR))


@given(rows_st, seed_st)
def test_ht_quant_prefix_invariant(r, seed):
    # the tail block runs the step pad's constant_values=1.0 guard: a zero
    # pad would 0-divide inside the kernel
    x, sign, noise, lo, step = _rows_data(seed)
    _assert_prefix(
        lambda: ht_quant_pallas(x, sign, noise, lo, step, block_rows=BR),
        lambda: ht_quant_pallas(x[:r], sign, noise[:r], lo[:r], step[:r],
                                block_rows=BR))


@given(rows_st, seed_st)
def test_grid_quant_prefix_invariant(r, seed):
    x, _, noise, lo, step = _rows_data(seed)
    _assert_prefix(
        lambda: grid_quant_pallas(x, noise, lo, step, block_rows=BR),
        lambda: grid_quant_pallas(x[:r], noise[:r], lo[:r], step[:r],
                                  block_rows=BR))


@given(rows_st, seed_st)
def test_uniform_quant_prefix_invariant(r, seed):
    x, _, noise, _, _ = _rows_data(seed)
    lohi = jnp.array([-3.0, 3.0], jnp.float32)
    _assert_prefix(
        lambda: uniform_quant_pallas(x, noise, lohi, block_rows=BR),
        lambda: uniform_quant_pallas(x[:r], noise[:r], lohi, block_rows=BR))


# ---- the reduce kernels: the pad is on the row axis (the flat form of
# masked_mean also pads its columns to whole 128-lane rows) -----------------
LANES = 128
COLS = 2 * BR * LANES + 64     # flat shards: 17 rows of 128, 3 row blocks
CR = 32                        # codes' block_rows: one uint8 sublane tile
CODE_ROWS = 3 * CR

cols_st = st.integers(min_value=1, max_value=COLS)
code_rows_st = st.integers(min_value=1, max_value=CODE_ROWS)


def _cols_data(seed):
    key = jax.random.PRNGKey(seed)
    shards = jax.random.normal(key, (4, COLS), jnp.float32)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                0.8, (4, COLS)).astype(jnp.float32)
    return shards, mask


def _codes_data(seed):
    key = jax.random.PRNGKey(seed)
    shape = (4, CODE_ROWS, LANES)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                0.8, shape).astype(jnp.float32)
    codes = jax.random.randint(jax.random.fold_in(key, 2), shape,
                               0, 256, jnp.int32).astype(jnp.uint8)
    lo = jax.random.normal(jax.random.fold_in(key, 3), (CODE_ROWS, 1))
    step = jax.random.uniform(jax.random.fold_in(key, 4), (CODE_ROWS, 1),
                              minval=0.01, maxval=0.1)
    return mask, codes, lo, step


@given(cols_st, seed_st)
def test_masked_mean_prefix_invariant(c, seed):
    shards, mask = _cols_data(seed)
    _assert_prefix(
        lambda: masked_mean_pallas(shards, mask, block_rows=BR),
        lambda: masked_mean_pallas(shards[:, :c], mask[:, :c],
                                   block_rows=BR))


@given(code_rows_st, seed_st)
def test_dequant_masked_mean_prefix_invariant(r, seed):
    mask, codes, lo, step = _codes_data(seed)
    for m, mp in [(mask, lambda: mask[:, :r]), (None, lambda: None)]:
        _assert_prefix(
            lambda: dequant_masked_mean_pallas(codes, lo, step, m,
                                               block_rows=CR),
            lambda: dequant_masked_mean_pallas(codes[:, :r], lo[:r],
                                               step[:r], mp(),
                                               block_rows=CR))

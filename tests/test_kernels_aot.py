"""Ahead-of-time compiles of every engine kernel for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so each
fused-engine kernel is lowered through Mosaic at the shapes one 25 MB
bucket gives it (6,553,600 fp32 = 6400 Hadamard blocks of 1024; 4-way DP
shards of 1,638,400) and must come out as a ``tpu_custom_call``. This
catches what interpret mode cannot: lane-splitting reshapes, casts Mosaic
lacks, unaligned DMA slices. Nothing runs, so it says nothing about
results or speed. The fused attention kernel of the train step compiles
here too, forward and backward, at a gpt2-paper layer of the benchmark's
64 x 1024 and at a head size of 128.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker given this
file loads the TPU library. The persistent compile cache is off around
these compiles: entries written without a chip cannot be read back.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import block_size, causal_attention_pallas
from repro.kernels.dequant_reduce.dequant_reduce import \
    dequant_masked_mean_pallas
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.ht_quant.ht_quant import ht_amax_pallas, ht_quant_pallas
from repro.kernels.masked_sum.masked_sum import masked_mean_pallas
from repro.kernels.quant.quant import grid_quant_pallas, uniform_quant_pallas

BUCKET = 6_553_600            # TrainConfig.bucket_elems: one 25 MB bucket
BLOCK = 1024                  # the launcher's Hadamard block
ROWS = BUCKET // BLOCK        # 6400 blocks per bucket
DP = 4                        # a 2x2 host's data axis
SHARD = BUCKET // DP          # 1,638,400 elements per peer shard


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# kernel -> (call with interpret=False, argument shapes/dtypes)
KERNELS = {
    "fwht_encode": (lambda x, s: fwht_pallas(x, s, sign_mode="pre",
                                             interpret=False),
                    [((ROWS, BLOCK),), ((BLOCK,),)]),
    "fwht_decode": (lambda x, s: fwht_pallas(x, s, sign_mode="post",
                                             interpret=False),
                    [((ROWS, BLOCK),), ((BLOCK,),)]),
    "ht_amax": (lambda x, s: ht_amax_pallas(x, s, interpret=False),
                [((ROWS, BLOCK),), ((BLOCK,),)]),
    "ht_quant": (lambda x, s, u, lo, st: ht_quant_pallas(
                     x, s, u, lo, st, interpret=False),
                 [((ROWS, BLOCK),), ((BLOCK,),), ((ROWS, BLOCK),),
                  ((ROWS,),), ((ROWS,),)]),
    "grid_quant": (lambda x, u, lo, st: grid_quant_pallas(
                       x, u, lo, st, interpret=False),
                   [((ROWS // DP, BLOCK),), ((ROWS // DP, BLOCK),),
                    ((ROWS // DP,),), ((ROWS // DP,),)]),
    "uniform_quant": (lambda x, u, lh: uniform_quant_pallas(
                          x, u, lh, interpret=False),
                      [((ROWS, BLOCK),), ((ROWS, BLOCK),), ((2,),)]),
    "masked_mean": (lambda x, m: masked_mean_pallas(x, m, interpret=False),
                    [((DP, SHARD),), ((DP, SHARD),)]),
    "dequant_masked_mean": (lambda c, lo, st, m: dequant_masked_mean_pallas(
                                c, lo, st, m, interpret=False),
                            [((DP, SHARD), jnp.uint8), ((SHARD,),),
                             ((SHARD,),), ((DP, SHARD),)]),
    # one peer (a single chip): fewer code rows than a uint8 HBM tile
    "dequant_masked_mean_one_peer": (
        lambda c, lo, st, m: dequant_masked_mean_pallas(
            c, lo, st, m, interpret=False),
        [((1, BUCKET), jnp.uint8), ((BUCKET,),), ((BUCKET,),),
         ((1, BUCKET),)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e_at_one_bucket(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(one_chip, *spec) for spec in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# (B, S, H, dh) of the attention kernel's q, k and v
ATTENTION_SHAPES = {"gpt2_paper": (64, 1024, 12, 64),
                    "head_dim_128": (8, 2048, 32, 128)}


@pytest.mark.parametrize("name", list(ATTENTION_SHAPES))
def test_attention_kernel_compiles_for_v5e(name, one_chip):
    b, s, h, dh = ATTENTION_SHAPES[name]
    arg = _sds(one_chip, (b, s, h, dh), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: causal_attention_pallas(
            q, k, v, 1.0 / math.sqrt(dh), block_size(s), False), q, k, v)
        return (o,) + vjp(do)

    text = jax.jit(fwd_bwd).lower(arg, arg, arg, arg).compile().as_text()
    for kernel in ("causal_attention_fwd", "causal_attention_bwd"):
        assert any(kernel in line and "tpu_custom_call" in line
                   for line in text.splitlines()), kernel

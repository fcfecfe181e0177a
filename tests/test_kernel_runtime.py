"""Kernel dispatch (kernels/runtime): the backend picks the implementation,
one test-only scope overrides it, and every public codec wrapper runs its
``ref.py`` form by default off the TPU and its Pallas kernel under the
``interpret`` scope."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.allreduce import OptiReduceConfig
from repro.kernels import runtime
from repro.kernels.dequant_reduce import (dequant_masked_mean,
                                          dequant_masked_mean_ref)
from repro.kernels.fwht import fwht, fwht_mxu_ref, randomized_fwht
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.fwht.ref import randomized_fwht_mxu_ref
from repro.kernels.ht_quant import ht_amax, ht_amax_ref, ht_quant, \
    ht_quant_ref
from repro.kernels.masked_sum import masked_mean, masked_mean_ref
from repro.kernels.quant import grid_quant, grid_quant_ref, uniform_quant, \
    uniform_quant_ref
from repro.launch.mesh import make_host_mesh
from repro.models import abstract_params
from repro.optim.optimizers import OptimizerConfig
from repro.train.trainer import TrainConfig, build_train_step


@pytest.fixture(autouse=True)
def _clean_scope():
    """Each test starts with no override in force."""
    prev = runtime._override
    runtime._override = None
    yield
    runtime._override = prev


def test_default_mode_is_auto():
    """With no scope the backend decides: Mosaic on a TPU, jnp elsewhere."""
    want = "mosaic" if jax.default_backend() == "tpu" else "jnp"
    assert runtime.impl() == want
    assert runtime.interpret_flag() == (want != "mosaic")


def test_auto_resolves_by_backend(monkeypatch):
    monkeypatch.setattr(runtime, "_mosaic_available", lambda: True)
    assert runtime.impl() == "mosaic"
    assert not runtime.interpret_flag()
    monkeypatch.setattr(runtime, "_mosaic_available", lambda: False)
    assert runtime.impl() == "jnp"
    assert runtime.interpret_flag()


def test_invalid_mode_rejected():
    with pytest.raises(ValueError, match="unknown kernel implementation"):
        with runtime.impl_scope("compile"):
            pass
    assert runtime._override is None


def test_compile_without_mosaic_is_a_clear_error():
    if jax.default_backend() == "tpu":
        pytest.skip("mosaic is legal on a TPU backend")
    with runtime.impl_scope("mosaic"):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            runtime.impl()
        # and the error surfaces at dispatch time, not inside a lowering
        with pytest.raises(RuntimeError, match="needs a TPU"):
            fwht_pallas(jnp.zeros((2, 64), jnp.float32))
        with pytest.raises(RuntimeError, match="needs a TPU"):
            fwht(jnp.zeros((2, 64), jnp.float32))


def test_scope_restores_previous_mode():
    default = runtime.impl()
    with runtime.impl_scope("interpret"):
        with runtime.impl_scope("jnp"):
            assert runtime.impl() == "jnp"
        assert runtime.impl() == "interpret"
        with pytest.raises(ValueError):
            with runtime.impl_scope("nope"):
                pass
        assert runtime.impl() == "interpret"
    assert runtime.impl() == default


def test_resolution_logged_once(caplog, monkeypatch):
    monkeypatch.setattr(runtime, "_logged", None)
    with caplog.at_level(logging.INFO, logger="repro.kernels.runtime"), \
            runtime.impl_scope("interpret"):
        runtime.impl()
        runtime.impl()
        runtime.impl()
    msgs = [r for r in caplog.records if "codec kernels" in r.getMessage()]
    assert len(msgs) == 1, msgs


def test_interpret_mode_bit_exact_to_explicit_flag():
    """The interpret scope reproduces an explicit ``interpret=True`` kernel
    call bit-exactly through every dispatch layer."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (5, 256), jnp.float32)
    sign = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(key, 1), shape=(256,)),
        1.0, -1.0).astype(jnp.float32)
    explicit = fwht_pallas(x, interpret=True)
    with runtime.impl_scope("interpret"):
        via_policy = fwht_pallas(x)
        via_ops = fwht(x)
        via_rand = randomized_fwht(x, sign, mode="encode")
    np.testing.assert_array_equal(np.asarray(via_policy),
                                  np.asarray(explicit))
    np.testing.assert_array_equal(np.asarray(via_ops), np.asarray(explicit))
    np.testing.assert_array_equal(
        np.asarray(via_rand),
        np.asarray(fwht_pallas(x * sign[None, :], interpret=True)))


# ------------------------------------------------ every public codec wrapper
def _codec_args():
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 6)
    rows, block, n = 12, 256, 4
    x = jax.random.normal(ks[0], (rows, block))
    sign = jnp.where(jax.random.bernoulli(ks[1], shape=(block,)), 1.0, -1.0)
    noise = jax.random.uniform(ks[2], (rows, block))
    amax = jnp.maximum(ht_amax_ref(x, sign), 1e-12)
    lo, step = -amax, 2.0 * amax / 255
    lohi = jnp.array([float(x.min()) - 1e-3, float(x.max()) + 1e-3])
    shards = jax.random.normal(ks[3], (n, rows * block))
    mask = jax.random.bernoulli(ks[4], 0.8, shards.shape).astype(jnp.float32)
    codes = jax.random.randint(ks[5], (n, rows * block), 0, 256
                               ).astype(jnp.uint8)
    return dict(x=x, sign=sign, noise=noise, lo=lo, step=step, lohi=lohi,
                shards=shards, mask=mask, codes=codes, block=block)


def _dequant_ref(a):
    n, s = a["codes"].shape
    rows = a["codes"].reshape(n, -1, a["block"])
    mask = a["mask"].reshape(rows.shape)
    return dequant_masked_mean_ref(rows, a["lo"][:, None], a["step"][:, None],
                                   mask).reshape(s)


# wrapper -> (call it, its ref.py form, the tolerance its kernel test pins)
CODEC_WRAPPERS = {
    "fwht": (lambda a: fwht(a["x"]), lambda a: fwht_mxu_ref(a["x"]), 1e-5),
    "randomized_fwht_encode": (
        lambda a: randomized_fwht(a["x"], a["sign"], mode="encode"),
        lambda a: randomized_fwht_mxu_ref(a["x"], a["sign"], mode="encode"),
        1e-5),
    "randomized_fwht_decode": (
        lambda a: randomized_fwht(a["x"], a["sign"], mode="decode"),
        lambda a: randomized_fwht_mxu_ref(a["x"], a["sign"], mode="decode"),
        1e-5),
    "ht_amax": (lambda a: ht_amax(a["x"], a["sign"]),
                lambda a: ht_amax_ref(a["x"], a["sign"]), 0.0),
    "ht_quant": (
        lambda a: ht_quant(a["x"], a["sign"], a["noise"], a["lo"], a["step"]),
        lambda a: ht_quant_ref(a["x"], a["sign"], a["noise"], a["lo"],
                               a["step"], bits=8), 0.0),
    "uniform_quant": (
        lambda a: uniform_quant(a["x"], a["noise"], a["lohi"]),
        lambda a: uniform_quant_ref(a["x"], a["noise"], a["lohi"][0],
                                    a["lohi"][1], bits=8), 0.0),
    "grid_quant": (
        lambda a: grid_quant(a["x"], a["noise"], a["lo"], a["step"]),
        lambda a: grid_quant_ref(a["x"], a["noise"], a["lo"], a["step"],
                                 bits=8), 0.0),
    "masked_mean": (lambda a: masked_mean(a["shards"], a["mask"]),
                    lambda a: masked_mean_ref(a["shards"], a["mask"]), 1e-6),
    "dequant_masked_mean": (
        lambda a: dequant_masked_mean(a["codes"], a["lo"], a["step"],
                                      a["mask"], block=a["block"]),
        _dequant_ref, 1e-6),
}


@pytest.mark.parametrize("name", list(CODEC_WRAPPERS))
def test_codec_wrapper_runs_ref_by_default_and_kernel_when_interpreted(name):
    """Off the TPU the default runs the wrapper's ``ref.py`` form (no
    Pallas call); under ``interpret`` it runs the Pallas kernel. Both match
    that form to the tolerance the wrapper's kernel test pins."""
    if jax.default_backend() == "tpu":
        pytest.skip("the default is Mosaic on a TPU")
    call, ref, tol = CODEC_WRAPPERS[name]
    a = _codec_args()
    want = np.asarray(ref(a)).astype(np.float32)

    def run():
        ran_kernel = "pallas_call" in str(jax.make_jaxpr(lambda: call(a))())
        return ran_kernel, np.asarray(call(a)).astype(np.float32)

    assert runtime.impl() == "jnp"
    ran_kernel, got = run()
    assert not ran_kernel
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    with runtime.impl_scope("interpret"):
        ran_kernel, got = run()
    assert ran_kernel
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


# ------------------------------------------- the train step follows the scope
def _step_jaxpr_text() -> str:
    """A CPU-built gpt2-smoke ``optireduce_q`` train step, traced."""
    cfg = get_smoke("gpt2-paper")
    tc = TrainConfig(
        sync=OptiReduceConfig(strategy="optireduce_q", drop_rate=0.01,
                              hadamard_block=256),
        optimizer=OptimizerConfig(name="adamw"), seq_chunk=32,
        bucket_elems=1 << 14)
    make_step, opt, _ = build_train_step(cfg, tc, make_host_mesh(dp=1, tp=1))
    params = abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32)
             for k in ("tokens", "labels")}
    step_fn, _ = make_step(opt_state, batch)
    return str(jax.make_jaxpr(step_fn)(
        params, opt_state, batch, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))


def test_cpu_train_step_lowers_no_pallas_call_by_default():
    if jax.default_backend() == "tpu":
        pytest.skip("the default is Mosaic on a TPU")
    text = _step_jaxpr_text()
    assert "ht_amax" in text and "dequant_masked_mean" in text  # the codec
    assert "pallas_call" not in text


def test_cpu_train_step_lowers_pallas_calls_under_interpret():
    with runtime.impl_scope("interpret"):
        text = _step_jaxpr_text()
    assert "pallas_call" in text

"""Kernel parity + timing sweep for the Pallas codec kernels.

Two kinds of rows per kernel family:

  parity     — Pallas output vs the jnp oracle (maxdiff / err), plus the
               jnp-form host timing the historical tables tracked.
  device     — ``*_interpret_steady_us`` rows time the Pallas interpreter
               (every box, incl. CPU CI: the interpreter's wall time tracks
               kernel *structure* — grid steps, DMA bookkeeping — not device
               speed), and on a real TPU backend ``*_compiled_steady_us``
               rows time the Mosaic-compiled kernels with
               ``block_until_ready``. Off-TPU the compiled rows are simply
               absent (the JSON schema treats them as optional), so the same
               bench file is the real-hardware mode: run it on a TPU box and
               the compiled columns appear.

Every ``*_steady_us`` row carries a ``*_steady_iqr_us`` dispersion sibling
(median/IQR over reps), per the suite-wide schema in benchmarks/run.py.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import runtime
from repro.kernels.dequant_reduce import dequant_masked_mean
from repro.kernels.dequant_reduce.dequant_reduce import \
    dequant_masked_mean_pallas
from repro.kernels.fwht import fwht, fwht_ref
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.ht_quant import ht_amax, ht_quant
from repro.kernels.ht_quant.ht_quant import ht_amax_pallas, ht_quant_pallas
from repro.kernels.masked_sum import masked_mean, masked_mean_ref
from repro.kernels.masked_sum.masked_sum import masked_mean_pallas
from repro.kernels.quant import uniform_quant, uniform_quant_ref
from repro.kernels.quant.quant import uniform_quant_pallas

from .common import Rows

# the Pallas kernels as this backend runs them
KERNEL = "mosaic" if jax.default_backend() == "tpu" else "interpret"


def _as(impl: str, fn):
    """``fn()`` with the codec wrappers running ``impl``."""
    with runtime.impl_scope(impl):
        return fn()


def _t(fn, *a, n=3):
    """Host us/call of ``fn`` with the wrappers in their jnp form."""
    with runtime.impl_scope("jnp"):
        jax.block_until_ready(fn(*a))    # one warmup; handles any pytree
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*a))
    return (time.perf_counter() - t0) / n * 1e6


def _steady(fn, reps=5):
    """(median_us, iqr_us) over ``reps`` timed calls after one warmup."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return (float(np.median(ts)),
            float(np.percentile(ts, 75) - np.percentile(ts, 25)))


def _device_rows(rows: Rows, name: str, fn, reps=5):
    """interpret-mode rows on every backend; Mosaic-compiled rows when a
    TPU is present (``fn`` must dispatch through ``kernels.runtime``)."""
    with runtime.impl_scope("interpret"):
        med, iqr = _steady(fn, reps)
    rows.add(f"{name}_interpret_steady_us", med,
             "Pallas interpreter host wall-clock (structure, not device)")
    rows.add(f"{name}_interpret_steady_iqr_us", iqr, "")
    if jax.default_backend() == "tpu":
        with runtime.impl_scope("mosaic"):
            med_c, iqr_c = _steady(fn, reps)
        rows.add(f"{name}_compiled_steady_us", med_c,
                 "Mosaic-compiled, block_until_ready")
        rows.add(f"{name}_compiled_steady_iqr_us", iqr_c, "")


def run(quick: bool = True) -> Rows:
    rows = Rows()
    key = jax.random.PRNGKey(0)
    blocks = [256, 1024, 4096] if quick else [256, 1024, 4096, 16384]
    for block in blocks:
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jax.random.normal(key, (32, block)).astype(dtype)
            ref = fwht_ref(x.astype(jnp.float32))
            with runtime.impl_scope("interpret"):
                out = fwht_pallas(x.astype(jnp.float32))
            err = float(jnp.max(jnp.abs(out - ref)))
            us = _t(lambda v=x: fwht(v.astype(jnp.float32)))
            rows.add(f"kernels/fwht_b{block}_{dtype.__name__}", us,
                     f"us/call (jnp MXU form); pallas_vs_oracle_err={err:.2e}")
    xf32 = jax.random.normal(key, (32, 1024))
    _device_rows(rows, "kernels/fwht_b1024",
                 lambda: fwht_pallas(xf32))
    n_peers = 8
    for length in ([1 << 14] if quick else [1 << 14, 1 << 18]):
        sh = jax.random.normal(key, (n_peers, length))
        mk = (jax.random.uniform(key, (n_peers, length)) > 0.05).astype(
            jnp.float32)
        err = float(jnp.max(jnp.abs(
            _as(KERNEL, lambda: masked_mean(sh, mk))
            - masked_mean_ref(sh, mk))))
        us = _t(lambda: masked_mean(sh, mk))
        rows.add(f"kernels/masked_sum_L{length}", us,
                 f"us/call; pallas_vs_oracle_err={err:.2e}")
        if length == (1 << 14):
            _device_rows(rows, f"kernels/masked_sum_L{length}",
                         lambda: masked_mean_pallas(sh, mk))
    x = jax.random.normal(key, (64, 4096))
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    lohi = jnp.array([float(x.min()), float(x.max())])
    for bits in (4, 8):
        q1 = _as(KERNEL, lambda b=bits: uniform_quant(x, noise, lohi, bits=b))
        q2 = uniform_quant_ref(x, noise, lohi[0], lohi[1], bits=bits)
        err = int(jnp.max(jnp.abs(q1.astype(jnp.int32) -
                                  q2.astype(jnp.int32))))
        us = _t(lambda b=bits: uniform_quant(x, noise, lohi, bits=b))
        rows.add(f"kernels/quant_b{bits}", us,
                 f"us/call; pallas_vs_oracle_maxdiff={err}")
    _device_rows(rows, "kernels/quant_b8",
                 lambda: uniform_quant_pallas(x, noise, lohi, bits=8))

    # fused sync-engine kernels: one-pass HT+quant vs the composed pipeline
    for block in ([1024] if quick else [1024, 4096]):
        rws = 32
        xf = jax.random.normal(key, (rws, block))
        sign = jnp.where(jax.random.bernoulli(key, 0.5, (block,)), 1., -1.)
        nz = jax.random.uniform(jax.random.fold_in(key, 2), xf.shape)
        am = jnp.maximum(_as("jnp", lambda: ht_amax(xf, sign)), 1e-12)
        lo, step = -am, 2.0 * am / 255
        qk, qr = (_as(impl, lambda: ht_quant(xf, sign, nz, lo, step, bits=8))
                  for impl in (KERNEL, "jnp"))
        err = int(jnp.max(jnp.abs(qk.astype(jnp.int32) -
                                  qr.astype(jnp.int32))))
        us = _t(lambda: ht_quant(xf, sign, nz, lo, step, bits=8))
        us_composed = (_t(lambda: fwht(xf * sign[None]))
                       + _t(lambda: uniform_quant(xf, nz, lohi, bits=8)))
        # host timing uses the jnp forms (Pallas runs in interpret mode off
        # TPU, so its wall time is meaningless here): one fused jit vs the
        # composed two-pass pipeline. The on-TPU win is the HBM pass count
        # (PERF.md); parity of the actual Pallas kernel is the maxdiff.
        rows.add(f"kernels/ht_quant_b{block}", us,
                 f"us/call one-pass jnp form; composed 2-pass jnp="
                 f"{us_composed:.0f}us; pallas_vs_oracle_maxdiff={err}")
        if block == 1024:
            _device_rows(rows, f"kernels/ht_amax_b{block}",
                         lambda: ht_amax_pallas(xf, sign, block_rows=16))
            _device_rows(
                rows, f"kernels/ht_quant_b{block}",
                lambda: ht_quant_pallas(xf, sign, nz, lo, step,
                                        block_rows=16))
    n_peers, nblk, blk = 8, 8, 1024
    s = nblk * blk
    codes = jax.random.randint(key, (n_peers, s), 0, 256).astype(jnp.uint8)
    lo_b = jax.random.normal(key, (nblk,))
    step_b = jax.random.uniform(key, (nblk,)) * 0.05 + 1e-3
    mk2 = (jax.random.uniform(key, (n_peers, s)) > 0.05).astype(jnp.float32)
    dk, dr = (_as(impl, lambda: dequant_masked_mean(codes, lo_b, step_b, mk2,
                                                    block=blk))
              for impl in (KERNEL, "jnp"))
    err = float(jnp.max(jnp.abs(dk - dr)))
    us = _t(lambda: dequant_masked_mean(codes, lo_b, step_b, mk2, block=blk))
    rows.add(f"kernels/dequant_masked_mean_L{s}", us,
             f"us/call one-pass jnp form; pallas_vs_oracle_err={err:.2e}")
    _device_rows(rows, f"kernels/dequant_masked_mean_L{s}",
                 lambda: dequant_masked_mean_pallas(
                     codes.reshape(n_peers, nblk, blk),
                     lo_b.reshape(nblk, 1), step_b.reshape(nblk, 1),
                     mk2.reshape(n_peers, nblk, blk)))
    return rows


if __name__ == "__main__":
    run(quick=False)

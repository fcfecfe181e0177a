"""A whole run through the harness's inner function at a small size on the
CPU: the window, the reference against the program, and the comparison
failing when the timed path is broken underneath."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, manifest

from .conftest import ROOT, small_bench

SEED = 2**33 + 17


def run_small(tmp_path, *, seconds=1.5, **kw):
    root, bench = small_bench(tmp_path, **kw)
    cell = manifest.load_cell("small", root=root, bench_dir=bench)
    return harness.run(cell, SEED, seconds, False,
                       t_start=time.perf_counter(),
                       devices=jax.devices()[:1])


def test_window_loop_at_small_size(tmp_path, no_persistent_cache):
    """The quantized cell's path (kernels in interpret mode) at d_model 64.
    At this size the 8-bit codec's noise is a larger share of a leaf's
    norm than at the cell's widths, so only the verdict's consistency with
    the printed checks is asserted here."""
    res = run_small(tmp_path)
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(
        manifest.load_cell("gpt2-q8k-1chip").workload["limits"])
    assert res["correct"] == all(c["value"] <= c["limit"]
                                 for c in res["checks"].values())
    assert set(res["metrics"]) == {"tokens_per_s", "step_p90_ms",
                                   "peak_hbm_gb", "setup_s"}
    assert res["attempted"] > harness.CHECKED_STEPS + 10
    assert res["failed"] == 0
    assert res["device"]["platform"] == "cpu"      # the inner function only
    json.dumps(res)


def test_reference_matches_the_program(tmp_path, no_persistent_cache):
    """build_train_step on the unquantized path with one peer (no drops,
    a Hadamard round trip) agrees with the float32 reference to fp32
    rounding."""
    fp32_rounding = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-3,
                     "failed_steps": 0}
    res = run_small(tmp_path, strategy="optireduce", use_kernels=False,
                    seconds=0.5, limits=fp32_rounding)
    assert res["correct"] is True, res["checks"]


def _unchanged(step):
    def broken(params, opt_state, batch, i, key):
        copy = jax.tree.map(jnp.copy, (params, opt_state))
        return params, opt_state, step(*copy, batch, i, key)[2]
    return broken


def _half_batch(step):
    def broken(params, opt_state, batch, i, key):
        half = jax.tree.map(lambda x: jnp.concatenate(
            [x[: x.shape[0] // 2]] * 2), batch)
        return step(params, opt_state, half, i, key)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, no_persistent_cache,
                                    monkeypatch, fault):
    real = harness.build_program

    def build(cell, seed=None):
        prog = real(cell, seed)
        prog.step = fault(prog.step)
        return prog

    monkeypatch.setattr(harness, "build_program", build)
    limits = manifest.load_cell("gpt2-q8k-1chip").workload["limits"]
    res = run_small(tmp_path, seconds=0.5, limits=limits)
    assert res["correct"] is False, res["checks"]


NO_EXCHANGE = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from pathlib import Path
from chipbench import harness, manifest
from chipbench.tests.conftest import small_bench
root, bench = small_bench(Path({tmp!r}), chips=4, global_batch=8,
                          limits={limits!r}, strategy="optireduce",
                          use_kernels=False)
cell = manifest.load_cell("small", root=root, bench_dir=bench)
real = harness.build_program
def build(cell, seed=None):
    prog = real(cell, seed)
    step = prog.step
    def local_only(params, opt_state, batch, i, key):
        # every chip sees shard 0's rows: the synced gradient is chip 0's
        # own, as if nothing were exchanged
        own = jax.tree.map(lambda x: jnp.concatenate(
            [x[: x.shape[0] // 4]] * 4), batch)
        return step(params, opt_state, own, i, key)
    prog.step = local_only
    return prog
harness.build_program = build
res = harness.run(cell, {seed}, 0.5, False, t_start=time.perf_counter(),
                  devices=jax.devices()[:4])
print(json.dumps(res))
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    """4-way DP on 4 virtual CPU devices, under the limits of the proven
    gpt2 cell."""
    limits = manifest.load_cell("gpt2-q8k-1chip").workload["limits"]
    code = NO_EXCHANGE.format(root=str(ROOT), src=str(ROOT / "src"),
                              tmp=str(tmp_path), limits=limits, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]


def test_control_is_not_correct(tmp_path, no_persistent_cache):
    """The reference in fp8 (the step below bf16), put in the program's
    place, fails the quantized cell's limits; the reference against
    itself passes them."""
    from chipbench import compare, reference

    root, bench = small_bench(tmp_path)
    cell = manifest.load_cell("small", root=root, bench_dir=bench)
    limits = manifest.load_cell("gpt2-q8k-1chip").workload["limits"]
    args = (cell.model, cell.workload["optimizer"], cell.traffic, SEED, 1)
    ref = reference.run(*args)
    control = reference.run(*args, lowp=True)
    assert compare.judge(compare.readings(ref, ref), limits)["correct"]
    verdict = compare.judge(compare.readings(control, ref), limits)
    assert verdict["correct"] is False, verdict


def test_peak_bytes_counts_the_programs_reserve():
    """The runtime keeps a step's temporaries in a reserve that
    ``peak_bytes_in_use`` leaves out; the peak is taken with it, on the
    fullest device."""
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devices = [Device({"peak_bytes_in_use": 5, "peak_bytes_reserved": 7}),
               Device({"peak_bytes_in_use": 9}), Device(None)]
    assert harness.peak_bytes(devices) == 12

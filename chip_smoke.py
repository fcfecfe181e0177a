#!/usr/bin/env python3
"""Chip smoke test: drive the main training path on a TPU and check it.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # 2x2 host: 4-way DP phase only

(a) gpt2-paper at its published widths (12 layers, d_model 768, vocab
    50257, bf16 params; ~124 M parameters), seq 1024, global batch 8,
    ``optireduce`` at 1% drop, through ``repro.launch.train.main`` in this
    process (its mesh, shardings, donation and data path). Losses must be
    finite and the last below step 0's.
(b) ``sync_packed`` with ``optireduce_q`` at 1% drop over a
    gpt2-paper-shaped gradient arena (the trainer's ``arena_plan`` of its
    params: 24 buckets of 25 MB, each 6400 x 1024), once through the
    Mosaic-compiled codec kernels (the TPU default) and once through the
    jnp codec path (``kernels.runtime.impl_scope("jnp")``), on the same
    arena and key. The two must agree (``KERNEL_REL_TOL``), and
    each must reproduce the arena to within 8-bit quantization noise.
--chips 4: only 4-way replicated DP of gpt2-paper (full width, depth cut to
    ``DP4_LAYERS``) at global batch 32, seq 1024:
    ``optireduce`` at drop 0.01 and at drop 0 beside ``psum``, from the same
    init and batches. The drop-0 losses must match psum's
    (``DP_LOSS_REL_TOL``); params and batch must span all four chips; the
    step's HLO must carry the all-to-all exchange.

Compile time, step time and peak device memory are printed as smoke
output, not benchmark metrics. The last line of stdout is the JSON result
``{"ok": true, "device": {...}}``; any failure exits non-zero without it.
Off the chip (no TPU, or ``JAX_PLATFORMS=cpu``) the script fails at once.
Tokens come from ``SyntheticLM`` and weights from ``--seed``; nothing is
downloaded. Compiles go to the cache chosen by
``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# (b): the kernel and jnp paths draw the same noise and grids, so their
# codes differ only where an fp32 rounding difference in the rotation moves
# a value across a level boundary: one level (2*amax/255, ~2.7% of a
# rotated value's std) on a rare code. 1e-3 relative L2 over the arena
# allows ~0.1% of all codes to move one level -- far above what rounding
# can flip, far below a wrong kernel (which moves every code).
KERNEL_REL_TOL = 1e-3
# (b): one stochastic-rounding stage of 8-bit codes on +-amax grids costs
# ~1.3% relative L2; two stages and the padded tail stay well inside 5%.
QUANT_REL_TOL = 5e-2
# --chips 4: drop-0 optireduce computes the same DP mean as psum up to fp32
# rounding (Hadamard round trip, different summation order). Step 0's loss
# sees identical params; later steps carry AdamW updates of at most lr per
# weight whose direction can flip only where a gradient is ~0.
DP_LOSS_REL_TOL = 1e-3
# --chips 4 compiles three step programs cold in one run; at full depth
# each takes minutes, so the exchange is proven on a two-layer stack (its
# widths, embedding and codec are full size: 9 buckets of 25 MB).
DP4_LAYERS = 2
ARCH = "gpt2-paper"
SEQ_LEN = 1024


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def smoke(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def train_argv(*, global_batch: int, dp: int, steps: int, seed: int,
               strategy: str, drop_rate: float,
               n_layers: int | None = None) -> list[str]:
    argv = ["--arch", ARCH, "--seq-len", str(SEQ_LEN),
            "--global-batch", str(global_batch), "--dp", str(dp),
            "--steps", str(steps), "--seed", str(seed),
            "--strategy", strategy, "--drop-rate", str(drop_rate),
            "--log-every", "1"]
    if n_layers:
        argv += ["--n-layers", str(n_layers)]
    return argv


def timing(step_times: list[float]) -> tuple[float, float]:
    """(compile seconds, steady step seconds): step 0's wall time carries
    the compile; the median of the rest is the steady step."""
    steady = statistics.median(step_times[1:])
    return step_times[0] - steady, steady


def check_losses(name: str, losses: list[float], steps: int) -> None:
    check(len(losses) == steps, f"{name}: {len(losses)} losses, "
          f"expected {steps}")
    check(all(math.isfinite(v) for v in losses),
          f"{name}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall ({losses[0]} -> {losses[-1]})")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_train(*, steps: int, seed: int):
    """(a) full-width gpt2-paper on one chip through the launcher."""
    import jax

    from repro.launch import train

    out = train.main(train_argv(
        global_batch=8, dp=1, steps=steps, seed=seed,
        strategy="optireduce", drop_rate=0.01))
    losses = out["losses"]
    check_losses("phase (a)", losses, steps)
    compile_s, step_s = timing(out["step_times"])
    smoke(f"(a) losses {[round(v, 4) for v in losses]}")
    smoke(f"(a) compile ~{compile_s:.1f} s (step 0 minus steady), "
          f"steady step {step_s * 1e3:.1f} ms, peak device memory "
          f"{peak_bytes(jax.devices()[0])} B")
    return losses


def phase_kernels(*, seed: int):
    """(b) sync_packed through the compiled kernels vs the jnp codec."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.core.allreduce import OptiReduceConfig, SyncContext, \
        sync_packed
    from repro.kernels import runtime
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params
    from repro.train.trainer import TrainConfig, arena_plan

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda: init_params(key, cfg, tp=1))
    # the trainer's bucket-major arena (both codecs share the 1024 block)
    plan = arena_plan(shapes, TrainConfig(sync=OptiReduceConfig(
        strategy="optireduce_q", hadamard_block=1024)))
    smoke(f"(b) arena: {plan.total} gradient elements in "
          f"{plan.num_buckets} buckets of {plan.bucket_elems}")
    arena = 1e-3 * jax.random.normal(jax.random.fold_in(key, 1),
                                     plan.arena_shape)
    mesh = make_host_mesh(dp=1)
    sync = OptiReduceConfig(strategy="optireduce_q", drop_rate=0.01,
                            hadamard_block=1024)

    def body(a, k):
        return sync_packed(a, SyncContext(cfg=sync, key=k), mode="pipelined")

    outs = {}
    for name, impl in (("kernels", "mosaic"), ("jnp", "jnp")):
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                  out_specs=P(), check_vma=False))
        t = time.perf_counter()
        with runtime.impl_scope(impl):
            compiled = f.lower(arena, key).compile()
        compile_s = time.perf_counter() - t
        if impl == "mosaic":
            check("tpu_custom_call" in compiled.as_text(),
                  "phase (b): no Mosaic kernel in the kernels program")
        out = jax.block_until_ready(compiled(arena, key))
        t = time.perf_counter()
        out = jax.block_until_ready(compiled(arena, key))
        run_s = time.perf_counter() - t
        check(bool(jnp.all(jnp.isfinite(out))), f"phase (b) {name}: "
              "non-finite output")
        rel = float(jnp.linalg.norm(out - arena) / jnp.linalg.norm(arena))
        smoke(f"(b) {name}: compile {compile_s:.1f} s, one sync of "
              f"{plan.num_buckets} buckets {run_s * 1e3:.1f} ms, rel L2 vs "
              f"arena {rel:.3e}")
        check(rel < QUANT_REL_TOL, f"phase (b) {name}: rel L2 {rel} vs "
              f"the arena exceeds {QUANT_REL_TOL}")
        outs[name] = out
    diff = outs["kernels"] - outs["jnp"]
    rel = float(jnp.linalg.norm(diff) / jnp.linalg.norm(outs["jnp"]))
    n_diff = int(jnp.sum(diff != 0))
    smoke(f"(b) kernels vs jnp: rel L2 {rel:.3e}, max |diff| "
          f"{float(jnp.max(jnp.abs(diff))):.3e}, {n_diff} of {diff.size} "
          f"elements differ (tolerance rel L2 {KERNEL_REL_TOL})")
    check(rel <= KERNEL_REL_TOL, f"phase (b): kernel and jnp paths differ "
          f"by rel L2 {rel} > {KERNEL_REL_TOL}")
    return rel


def phase_dp4(*, steps: int, seed: int):
    """--chips 4: optireduce (1% and 0 drop) beside psum, 4-way DP."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train

    runs = {}
    for name, strategy, drop in (("psum", "psum", 0.0),
                                 ("optireduce_drop0", "optireduce", 0.0),
                                 ("optireduce_drop0.01", "optireduce", 0.01)):
        out = train.main(train_argv(
            global_batch=32, dp=4, steps=steps, seed=seed,
            strategy=strategy, drop_rate=drop, n_layers=DP4_LAYERS))
        check_losses(name, out["losses"], steps)
        for leaf in jax.tree.leaves(out["params"]):
            check(len(leaf.sharding.device_set) == 4,
                  f"{name}: a param lives on {leaf.sharding.device_set}")
        for leaf in jax.tree.leaves(out["batch"]):
            check(len(leaf.sharding.device_set) == 4
                  and not leaf.sharding.is_fully_replicated,
                  f"{name}: batch not split over 4 chips: {leaf.sharding}")
        if strategy == "optireduce":
            hlo = out["step"].lower(
                out["params"], out["opt_state"], out["batch"],
                jnp.asarray(0, jnp.int32), jax.random.PRNGKey(seed)
            ).as_text()
            n_a2a = hlo.count("all_to_all")
            check(n_a2a > 0, f"{name}: no all-to-all in the step's HLO")
            smoke(f"[4] {name}: step HLO has {n_a2a} all_to_all ops")
        compile_s, step_s = timing(out["step_times"])
        smoke(f"[4] {name}: losses {out['losses']}")
        smoke(f"[4] {name}: compile ~{compile_s:.1f} s, steady step "
              f"{step_s * 1e3:.1f} ms, peak device memory "
              f"{[peak_bytes(d) for d in jax.devices()]} B")
        runs[name] = out["losses"]
    rel = [abs(a - b) / abs(b)
           for a, b in zip(runs["optireduce_drop0"], runs["psum"])]
    smoke(f"[4] optireduce drop 0 vs psum: max rel loss diff {max(rel):.3e} "
          f"(tolerance {DP_LOSS_REL_TOL})")
    check(max(rel) <= DP_LOSS_REL_TOL, "optireduce at drop 0 does not "
          f"match psum: rel diffs {rel}")
    return runs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {Path(__file__).name}: run it from a "
             "checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this smoke "
             "runs on the chip only")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, JAX sees "
          f"{len(devices)}")

    from repro.kernels import runtime
    from repro.launch.compile_cache import enable_compile_cache

    smoke(f"compile cache {enable_compile_cache()}")
    check(runtime.impl() == "mosaic", "Pallas kernels would not compile "
          f"(codec kernel implementation {runtime.impl()!r})")
    smoke(f"device {dev.device_kind} x{len(devices)}")

    t = time.perf_counter()
    if args.chips == 4:
        phase_dp4(steps=min(args.steps, 5), seed=args.seed)
    else:
        phase_train(steps=args.steps, seed=args.seed)
        phase_kernels(seed=args.seed)
    smoke(f"total {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

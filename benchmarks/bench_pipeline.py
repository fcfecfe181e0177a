"""End-to-end sync_pytree timing: the fused BucketPlan engine in its
``scan`` and stage-skewed ``pipelined`` schedules vs the seed per-bucket
Python loop, swept over bucket counts (``vmap`` is correctness-pinned by
the parity suite but not timed here).

Three costs are reported per (variant, B):

  trace_ms   — trace + lower time (the O(#buckets) HLO-growth tax the
               BucketPlan removes; this is host time paid on EVERY reshape
               of the step function)
  hlo_kb     — lowered module size (proxy for compile time / program cache
               pressure at production scale)
  steady_us  — steady-state wall time per call: the MEDIAN over >= 20 reps
               (single-shot means were noisy enough to invert B1 vs B2
               orderings between runs), with the interquartile range
               emitted as a ``steady_iqr_us`` dispersion row per steady
               row so trajectory diffs can tell signal from scheduler noise
               (run.py's schema rejects a steady row without its dispersion
               sibling)

plus derived per-bucket overhead slopes (d(steady)/dB via the
(B_max, B_min) secant) and the headline
``pipeline/pipelined_vs_scan_steady_pct`` — the steady-state delta of the
software-pipelined schedule vs the serial scan at the largest swept B
(on the single-device CI box the collectives are degenerate, so this mostly
prices the skew bookkeeping; the overlap win needs a real fabric).

Run via ``python -m benchmarks.run --only bench_pipeline``; ``run.py`` also
serializes these rows to BENCH_pipeline.json at the repo root so future PRs
can diff the perf trajectory mechanically.
"""
from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from jax.sharding import AxisType
from repro.core import (OptiReduceConfig, SyncContext, sync_pytree,
                        sync_pytree_unfused)
from jax.sharding import PartitionSpec as P

from .common import Rows

BUCKET = 4096

VARIANTS = (("fused", "scan"),            # historical row name for scan mode
            ("pipelined", "pipelined"),
            ("unfused", None))


def _build(nbuckets: int, strategy: str = "optireduce",
           mode: str | None = "scan"):
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cfg = OptiReduceConfig(strategy=strategy, drop_rate=0.0,
                           hadamard_block=256)
    tree = {"g": jnp.zeros((nbuckets * BUCKET,), jnp.float32)}
    spec = {"g": P()}

    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(0))
        if mode is None:
            return sync_pytree_unfused(t, ctx, bucket_elems=BUCKET)
        return sync_pytree(t, ctx, bucket_elems=BUCKET, mode=mode)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
    return f, tree


def _measure(nbuckets: int, reps: int, strategy: str = "optireduce",
             mode: str | None = "scan"):
    """Returns (trace_ms, hlo_kb, steady_med_us, steady_iqr_us)."""
    f, tree = _build(nbuckets, strategy, mode)
    t0 = time.perf_counter()
    lowered = f.lower(tree)
    trace_ms = (time.perf_counter() - t0) * 1e3
    hlo_kb = len(lowered.as_text()) / 1024
    # reuse the lowering (calling f would re-trace the whole pipeline)
    compiled = lowered.compile()
    jax.block_until_ready(compiled(tree))             # warmup
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(tree))
        times.append((time.perf_counter() - t0) * 1e6)
    med = statistics.median(times)
    q = statistics.quantiles(times, n=4)
    return trace_ms, hlo_kb, med, q[2] - q[0]


def run(quick: bool = True) -> Rows:
    rows = Rows()
    counts = [1, 2, 4, 8] if quick else [1, 2, 4, 8, 16, 32]
    reps = 20 if quick else 40          # median needs >= 20 reps either way
    steady = {}
    for name, mode in VARIANTS:
        for b in counts:
            trace_ms, hlo_kb, med_us, iqr_us = _measure(b, reps, mode=mode)
            steady[(name, b)] = med_us
            rows.add(f"pipeline/{name}_B{b}_trace_ms", trace_ms,
                     "trace+lower host time")
            rows.add(f"pipeline/{name}_B{b}_hlo_kb", hlo_kb,
                     "lowered module size")
            rows.add(f"pipeline/{name}_B{b}_steady_us", med_us,
                     f"wall us/call, median of {reps} reps")
            rows.add(f"pipeline/{name}_B{b}_steady_iqr_us", iqr_us,
                     f"interquartile range of the {reps} reps")
    b_lo, b_hi = counts[0], counts[-1]
    slopes = {}
    for name, _ in VARIANTS:
        slopes[name] = ((steady[(name, b_hi)] - steady[(name, b_lo)])
                        / (b_hi - b_lo))
        rows.add(f"pipeline/{name}_per_bucket_us", slopes[name],
                 f"d(steady)/dB secant over B={b_lo}..{b_hi}")
    if slopes["unfused"] > 0:
        rows.add("pipeline/per_bucket_overhead_reduction_pct",
                 100.0 * (1 - slopes["fused"] / slopes["unfused"]),
                 "fused vs seed loop (higher is better)")
    rows.add("pipeline/pipelined_vs_scan_steady_pct",
             100.0 * (1 - steady[("pipelined", b_hi)]
                      / steady[("fused", b_hi)]),
             f"pipelined vs scan steady median at B={b_hi} "
             "(positive = pipelined faster; CI box has degenerate "
             "collectives, so this prices skew bookkeeping only)")
    # composable-pipeline specs: the same fused engine over other registry
    # entries (the quantized exchange and a register_strategy'd composition)
    # — tracks the trace/steady cost of the Topology x Transport x Codec
    # dispatch vs the plain optireduce spec above
    b_spec = 4
    for strat in ("optireduce_q", "optireduce_rounds"):
        trace_ms, hlo_kb, med_us, iqr_us = _measure(b_spec, reps,
                                                    strategy=strat)
        rows.add(f"pipeline/spec_{strat}_B{b_spec}_trace_ms", trace_ms,
                 "trace+lower host time, fused engine")
        rows.add(f"pipeline/spec_{strat}_B{b_spec}_steady_us", med_us,
                 f"wall us/call, median of {reps} reps")
        rows.add(f"pipeline/spec_{strat}_B{b_spec}_steady_iqr_us", iqr_us,
                 f"interquartile range of the {reps} reps")
    return rows


if __name__ == "__main__":
    run(quick=False)

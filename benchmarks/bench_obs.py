"""Tracing overhead (DESIGN §12): the cost of the observability layer.

Measures the contract the obs subsystem makes to every hot path it
instruments:

* ``disabled_gate_us`` — one ``get_tracer()`` + ``is not None`` check with
  tracing OFF: the price every instrumented call site pays all the time.
  Must stay in the low tens of ns.
* ``enabled_complete_us`` / ``enabled_event_us`` — one ring-buffer record
  with tracing ON (span with explicit ts/dur; instant event).
* ``enabled_span_us`` — one ``with tracer.span(...)`` opened and closed with
  tracing ON: two clock reads, the record, and the profiler annotation the
  span opens (no profiler session running).
* ``hist_record_us`` — one :class:`TailHistogram` sample.
* ``wire_step_untraced/traced_median_us`` — a real 4-peer inproc HostRing
  allreduce step, tracing off vs on: the end-to-end overhead on the wire
  datapath the acceptance criterion bounds.

All medians carry ``_iqr_us`` dispersion siblings per the run.py schema.
"""
from __future__ import annotations

import time

import numpy as np

from .common import Rows


def _median_iqr(samples_us) -> tuple[float, float]:
    a = np.asarray(samples_us, np.float64)
    q1, med, q3 = np.percentile(a, [25, 50, 75])
    return float(med), float(q3 - q1)


def _per_call_us(fn, calls: int, reps: int) -> tuple[float, float]:
    """Median + IQR of per-call cost over ``reps`` timed batches."""
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(calls)
        samples.append((time.perf_counter() - t0) * 1e6 / calls)
    return _median_iqr(samples)


def _bench_primitives(rows: Rows, *, calls: int, reps: int) -> None:
    from repro.obs import TailHistogram, trace

    trace.reset()
    get_tracer = trace.get_tracer

    def disabled_gate(n):
        for _ in range(n):
            tr = get_tracer()
            if tr is not None:
                tr.event("x", "bench")
    med, iqr = _per_call_us(disabled_gate, calls, reps)
    rows.add("obs/disabled_gate_median_us", med,
             "get_tracer()+None check, tracing off")
    rows.add("obs/disabled_gate_iqr_us", iqr, "")

    tr = trace.configure(True, capacity=1 << 14)

    def enabled_complete(n):
        for i in range(n):
            tr.complete("round", "bench", ts=float(i), dur=1.0, tid=0,
                        args={"round": i})
    med, iqr = _per_call_us(enabled_complete, calls, reps)
    rows.add("obs/enabled_complete_median_us", med,
             "one X record into the ring")
    rows.add("obs/enabled_complete_iqr_us", iqr, "")

    def enabled_event(n):
        for i in range(n):
            tr.event("tick", "bench")
    med, iqr = _per_call_us(enabled_event, calls, reps)
    rows.add("obs/enabled_event_median_us", med,
             "one instant record into the ring")
    rows.add("obs/enabled_event_iqr_us", iqr, "")

    def enabled_span(n):
        for _ in range(n):
            with tr.span("step"):
                pass
    med, iqr = _per_call_us(enabled_span, calls, reps)
    rows.add("obs/enabled_span_median_us", med,
             "one span opened and closed (record + profiler annotation)")
    rows.add("obs/enabled_span_iqr_us", iqr, "")
    trace.reset()

    h = TailHistogram()
    vals = np.random.default_rng(0).lognormal(0.0, 1.0, calls)

    def hist_record(n):
        for i in range(n):
            h.record(vals[i])
    med, iqr = _per_call_us(hist_record, calls, reps)
    rows.add("obs/hist_record_median_us", med,
             "one TailHistogram sample (log-bucketed)")
    rows.add("obs/hist_record_iqr_us", iqr, "")


def _wire_step_us(ring, buckets, key, steps: int) -> list[float]:
    out = []
    for s in range(steps):
        t0 = time.perf_counter()
        ring.allreduce(buckets, key, step=s)
        out.append((time.perf_counter() - t0) * 1e6)
    return out


def _bench_wire(rows: Rows, *, steps: int) -> None:
    import jax

    from repro.core.pipeline import OptiReduceConfig
    from repro.net import HostRing
    from repro.obs import trace

    n, elems = 4, 4096
    cfg = OptiReduceConfig(strategy="optireduce", hadamard_block=256)
    key = jax.random.PRNGKey(0)
    buckets = np.random.default_rng(1).standard_normal(
        (n, elems)).astype(np.float32)

    trace.reset()
    ring = HostRing(n, cfg, backend="inproc")
    _wire_step_us(ring, buckets, key, 2)          # jit warmup, uncounted
    untraced = _wire_step_us(ring, buckets, key, steps)
    ring.close()
    med_u, iqr_u = _median_iqr(untraced)
    rows.add("obs/wire_step_untraced_median_us", med_u,
             f"4-peer inproc allreduce of {elems} fp32, tracing off")
    rows.add("obs/wire_step_untraced_iqr_us", iqr_u, "")

    trace.configure(True, capacity=1 << 16)
    ring = HostRing(n, cfg, backend="inproc")
    _wire_step_us(ring, buckets, key, 2)
    traced = _wire_step_us(ring, buckets, key, steps)
    ring.close()
    trace.reset()
    med_t, iqr_t = _median_iqr(traced)
    rows.add("obs/wire_step_traced_median_us", med_t,
             "same step, tracing on (round+phase spans recorded)")
    rows.add("obs/wire_step_traced_iqr_us", iqr_t, "")
    rows.add("obs/wire_step_overhead_pct",
             100.0 * (med_t - med_u) / max(med_u, 1e-9),
             "traced vs untraced median")


def run(quick: bool = True) -> Rows:
    rows = Rows()
    _bench_primitives(rows, calls=2000 if quick else 20000,
                      reps=9 if quick else 21)
    _bench_wire(rows, steps=6 if quick else 30)
    return rows

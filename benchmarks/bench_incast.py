"""Paper Fig 13: static (I=1) vs dynamic incast in UBT — dynamic incast
raises I when loss stays low, halving the round count and cutting mean GA
latency (paper: ~21% on a 500M-gradient AllReduce).

Besides the simulator rows, this bench measures the REAL lowered schedule:
``tar_allreduce_rounds(incast=I)`` gates each group of I ppermutes on the
previous group's arrivals (an optimization_barrier chain), so the HLO
barrier count and the wall time on an 8-device host mesh genuinely change
with I (subprocess, same pattern as the collective tests)."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from repro.sim.netsim import NetworkModel, simulate_job

from .common import Rows

_CHILD = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core.tar import pad_for_tar, tar_allreduce_rounds

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1 << 16))
for incast in (1, 4):
    def body(v, incast=incast):
        vv, ln = pad_for_tar(v.reshape(-1), 8)
        return tar_allreduce_rounds(vv, "data", incast=incast)[None, :ln]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                              out_specs=P("data", None), check_vma=False))
    barriers = f.lower(x).as_text().count("optimization_barrier")
    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(f(x))
    us = (time.perf_counter() - t0) / 10 * 1e6
    print(f"ROW incast/rounds_I{incast}_us {us:.1f} "
          f"hlo_barriers={barriers}")
"""


def _real_schedule_rows(rows: Rows) -> None:
    # the child wants eight virtual CPU devices, never the chip (which
    # this benchmark process may already hold)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              capture_output=True, text=True, timeout=600)
    except (subprocess.TimeoutExpired, OSError) as e:
        rows.add("incast/rounds_FAILED", 0, type(e).__name__)
        return
    if proc.returncode != 0:
        rows.add("incast/rounds_FAILED", 0, proc.stderr.strip()[-120:])
        return
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            _, name, value, derived = line.split(" ", 3)
            rows.add(name, float(value), derived)


def run(quick: bool = True) -> Rows:
    rows = Rows()
    # Fig 13's regime: per-round latency floors dominate (the incast win is
    # halving the ROUND COUNT); use latency-bound chunk sizes — with
    # bandwidth-bound 25 MB buckets the byte volume is invariant in I and
    # dynamic incast is correctly a no-op.
    nbytes = 2 * 2 ** 20
    steps = 120 if quick else 400
    env_kw = dict(n_nodes=8, bucket_bytes=nbytes, n_steps=steps,
                  compute_ms=0.0, overlap=0.0)
    stat = simulate_job("optireduce",
                        env=NetworkModel.environment("local_1.5", seed=5),
                        incast_dynamic=False, **env_kw)
    dyn = simulate_job("optireduce",
                       env=NetworkModel.environment("local_1.5", seed=5),
                       incast_dynamic=True, **env_kw)
    rows.add("incast/static_I1_mean_ms", stat["mean_ga_ms"], "")
    rows.add("incast/dynamic_mean_ms", dyn["mean_ga_ms"], "")
    rows.add("incast/latency_reduction_pct",
             100 * (1 - dyn["mean_ga_ms"] / stat["mean_ga_ms"]),
             "paper ~21%")
    rows.add("incast/static_p99_ms", stat["p99_ga_ms"], "")
    rows.add("incast/dynamic_p99_ms", dyn["p99_ga_ms"], "")
    rows.add("incast/dynamic_drop", dyn["mean_drop"],
             "must stay < 0.1% while I grows")
    _real_schedule_rows(rows)
    return rows


if __name__ == "__main__":
    run(quick=False)

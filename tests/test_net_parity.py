"""The wire subsystem's load-bearing correctness pin (ISSUE acceptance):

given identical arrival masks, the *wire-exchanged* TAR result — every
byte really crossing the inproc backend, scripted to drop exactly the
packets the in-JAX ``Lossy`` transport's ``core/drops.py`` masks name — is
**bitwise-identical** to the in-JAX result, for registered strategies
including a quantized one (grid pmax reproduced by wire max-sharing), and
the ``WireTransport`` io_callback bridge feeding those observed masks into
the in-JAX datapath hits the same bits too.

Runs in ONE subprocess (4 forced host devices, same pattern as
test_pipeline_parity.py); parametrized tests assert per-strategy markers.
"""
import os
import subprocess
import sys

import pytest

# (strategy, drop_rate, codec kernel implementation): Hadamard,
# rounds-scheduled quantized, and the quantized a2a path through the
# interpreted Pallas kernels
STRATEGIES = [
    ("optireduce", 0.1, "jnp"),
    ("tar_rounds_q", 0.05, "jnp"),
    ("optireduce_q", 0.05, "interpret"),
]

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core.allreduce import OptiReduceConfig, SyncContext, sync_bucket
from repro.core import drops as drops_lib, tar as tar_lib
from repro.core.pipeline import resolve_spec
from repro.kernels.runtime import impl_scope
from repro.net import HostRing, InprocBackend, mask_scripted_drops, wire_spec

N, L = 4, 1000            # not block-aligned: pad + tail-packet paths on
mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(5)
buckets = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (N, L)),
                     np.float32)

def run(cfg, spec=None):
    def body(x):
        ctx = SyncContext(cfg=cfg, key=key)
        return sync_bucket(x[0], ctx, spec=spec), ctx.loss_fraction()
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P("data"), P()), check_vma=False))
    out, frac = f(buckets)
    return np.asarray(out).reshape(N, L), float(frac)

def lossy_masks(cfg):
    padded, _ = tar_lib.pad_for_tar(jnp.zeros(L), N,
                                    resolve_spec(cfg).codec.block(cfg))
    s = padded.shape[0] // N
    return {me: np.asarray(drops_lib.make_mask(
        cfg.drop_pattern, jax.random.fold_in(key, me), N, s,
        rate=cfg.drop_rate, packet_elems=cfg.packet_elems,
        self_index=jnp.asarray(me))) for me in range(N)}

def check(strat, dr):
    cfg = OptiReduceConfig(strategy=strat, drop_rate=dr, hadamard_block=256,
                           quant_bits=8, packet_elems=64, incast=2)
    ref, ref_frac = run(cfg)
    masks = lossy_masks(cfg)
    drop_fn = mask_scripted_drops(masks, cfg.packet_elems)

    # --- host datapath: every byte over the wire, scripted drops ---------
    ring = HostRing(N, cfg, backend=InprocBackend(N, drop_fn=drop_fn))
    out, tel = ring.allreduce(buckets, key)
    assert np.array_equal(out, ref), (strat, "host datapath")
    assert abs(tel.loss_frac - ref_frac) < 1e-6, (strat, tel.loss_frac,
                                                  ref_frac)
    assert len(tel.peer_stage_times) == N
    print("WIRE_PARITY %%s OK loss_frac=%%.5f" %% (strat, tel.loss_frac))

    # --- io_callback bridge: in-JAX datapath, wire-observed masks --------
    # (the bridge is one-exchange lagged: call 0 primes with all-ones; the
    # scripted loss is a pure function of the packet header, so call 1
    # consumes exchange 0's masks == the Lossy masks, bitwise)
    cfg_w = OptiReduceConfig(strategy=strat, drop_rate=0.0,
                             hadamard_block=256, quant_bits=8,
                             packet_elems=64, incast=2)
    bridge_ring = HostRing(N, cfg_w,
                           backend=InprocBackend(N, drop_fn=drop_fn))
    wspec = wire_spec(cfg_w, bridge_ring)
    _, prime_frac = run(cfg_w, spec=wspec)
    assert prime_frac == 0.0, (strat, "priming call must see no loss")
    assert bridge_ring.flush()
    wout, wfrac = run(cfg_w, spec=wspec)
    assert np.array_equal(wout, ref), (strat, "bridge")
    assert abs(wfrac - ref_frac) < 1e-6, (strat, wfrac, ref_frac)
    assert bridge_ring.bridge_misses == 0
    assert bridge_ring.flush()
    wt = bridge_ring.drain_telemetry()
    assert wt is not None and len(wt.peer_stage_times) == N
    print("BRIDGE_PARITY %%s OK" %% strat)

for strat, dr, impl in %(strategies)r:
    with impl_scope(impl):
        check(strat, dr)

print("ALL_WIRE_PARITY_OK")
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_marker_cache: dict = {}


def _child_output() -> str:
    if "out" not in _marker_cache:
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
        proc = subprocess.run(
            [sys.executable, "-c", CHILD % {"strategies": STRATEGIES}],
            env=env, capture_output=True, text=True, timeout=900)
        _marker_cache["out"] = proc.stdout + "\n" + proc.stderr + \
            f"\nreturncode={proc.returncode}"
        _marker_cache["rc"] = proc.returncode
    return _marker_cache["out"]


@pytest.mark.slow
@pytest.mark.parity
@pytest.mark.net
@pytest.mark.parametrize("strategy", [s for s, _, _ in STRATEGIES])
def test_wire_vs_lossy_bitwise(strategy):
    out = _child_output()
    assert _marker_cache["rc"] == 0, out
    assert f"WIRE_PARITY {strategy} OK" in out, out
    assert f"BRIDGE_PARITY {strategy} OK" in out, out


@pytest.mark.slow
@pytest.mark.parity
@pytest.mark.net
def test_wire_parity_suite_completed():
    out = _child_output()
    assert "ALL_WIRE_PARITY_OK" in out, out

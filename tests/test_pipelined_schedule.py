"""Op-order inspection of the software-pipelined bucket schedule.

The CPU runs the codec kernels in their jnp form, so on-TPU overlap cannot
be timed here; what CAN be pinned is the lowered HLO: with ``mode="pipelined"`` the
exchange collectives of bucket k-1 must be *emitted between* the encode
kernels of bucket k and the decode kernels of bucket k-2 (StableHLO emission
follows trace order for data-independent ops, and the skew removes the data
dependencies), which is exactly the program shape XLA's async collectives
need to overlap communication with neighboring buckets' codec work.

Runs in a 2-forced-host-device subprocess; reads op order over the whole
lowered module (the shard_map body is inlined into ``main``, and the nested
codec functions hold no collectives), identifies the codec kernels by their
nested-jit callees (``randomized_fwht`` specializations, encode and decode
distinct, for the Hadamard codec; ``_ht_amax`` for the quantizing codec's
encode) and the exchanges by the stablehlo collective ops.
"""
import os
import subprocess
import sys

import pytest

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import re
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core import (OptiReduceConfig, SyncContext, sync_pytree,
                        sync_pytree_unfused)

mesh = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,))
cfg = OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                       hadamard_block=256)

def lower(fn, nbuckets, **kw):
    tree = {"g": jnp.zeros((nbuckets * 2048,), jnp.float32)}
    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(0))
        return fn(t, ctx, bucket_elems=2048, **kw)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=({"g": P()},),
                              out_specs={"g": P()}, check_vma=False))
    return f.lower(tree).as_text()

def module_lines(txt):
    # op order over the whole module: collectives and codec calls appear
    # only where the traced schedule emits them
    return txt.splitlines()

def stages(body):
    a2a = [i for i, l in enumerate(body) if "stablehlo.all_to_all" in l]
    ag = [i for i, l in enumerate(body) if "stablehlo.all_gather" in l]
    fwht = [(i, l) for i, l in enumerate(body)
            if re.search(r"call @randomized_fwht[_0-9]*\(", l)]
    callee = lambda l: re.search(r"call @(randomized_fwht[_0-9]*)\(",
                                 l).group(1)
    enc_name = callee(fwht[0][1])     # the first rotation is an encode
    enc = [i for i, l in fwht if callee(l) == enc_name]
    dec = [i for i, l in fwht if callee(l) != enc_name]
    return a2a, ag, enc, dec

# ---- B=3 pipelined: the full skew unrolls ---------------------------------
# expected trace order: E0 E1 | X0 | E2 | X1 | D0 | X2 | D1 D2
body = module_lines(lower(sync_pytree, 3, mode="pipelined"))
a2a, ag, enc, dec = stages(body)
assert len(a2a) == 3 and len(ag) == 3, (len(a2a), len(ag))
assert len(enc) == 3 and len(dec) == 3, (len(enc), len(dec))
assert enc[0] < enc[1] < a2a[0], \
    "buckets 0 AND 1 must encode before bucket 0's exchange is issued"
assert ag[0] < enc[2] < a2a[1], \
    "bucket 2's encode must interleave between exchanges 0 and 1"
assert ag[1] < dec[0] < a2a[2], \
    "bucket 0's decode must interleave between exchanges 1 and 2"
assert ag[2] < dec[1] < dec[2], "epilogue drains decodes after the last exchange"
print("PIPELINED_ORDER OK")

# ---- negative control: the seed loop serializes ---------------------------
body_u = module_lines(lower(sync_pytree_unfused, 3))
a2a_u, ag_u, enc_u, dec_u = stages(body_u)
assert len([i for i in enc_u if i < a2a_u[0]]) == 1, \
    "seed loop: only bucket 0 encodes before bucket 0's exchange"
assert dec_u[0] < a2a_u[1], "seed loop: bucket 0 decodes before exchange 1"
print("SERIAL_CONTROL OK")

# ---- collective count stays constant in B ---------------------------------
# pipelined = prologue + one scan body + epilogue = 3 all_to_all at any B>3;
# scan = 1; the seed loop = B
n_pip = lambda b: lower(sync_pytree, b, mode="pipelined").count(
    "stablehlo.all_to_all")
assert n_pip(8) == 3 and n_pip(16) == 3, (n_pip(8), n_pip(16))
assert lower(sync_pytree, 8, mode="scan").count("stablehlo.all_to_all") == 1
print("CONSTANT_HLO OK")
"""


CHILD_QUANT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import re
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.core import OptiReduceConfig, SyncContext, sync_pytree

mesh = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,))
cfg = OptiReduceConfig(strategy="optireduce_q", drop_rate=0.0,
                       hadamard_block=256)

def lower(nbuckets, **kw):
    tree = {"g": jnp.zeros((nbuckets * 2048,), jnp.float32)}
    def body(t):
        ctx = SyncContext(cfg=cfg, key=jax.random.PRNGKey(0))
        return sync_pytree(t, ctx, bucket_elems=2048, **kw)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=({"g": P()},),
                              out_specs={"g": P()}, check_vma=False))
    return f.lower(tree).as_text()

# op order over the whole lowered module (see CHILD); the quantizing
# codec's encode stage is its rotate-and-amax kernel call
body = lower(3, mode="pipelined").splitlines()
a2a = [i for i, l in enumerate(body) if "stablehlo.all_to_all" in l]
ag = [i for i, l in enumerate(body) if "stablehlo.all_gather" in l]
ar = [i for i, l in enumerate(body) if "stablehlo.all_reduce" in l]
enc = [i for i, l in enumerate(body)
       if re.search(r"call @_ht_amax[_0-9]*\(", l)]
assert len(enc) == 3, (len(enc), "one encode per bucket")

# ---- the THC grid pmax rides the exchange stage ---------------------------
# split encode: encode_stage emits only the local amax; the pmax
# (stablehlo.all_reduce) is deferred into the exchange stage, so bucket k's
# grid collective is emitted alongside bucket k-1's exchange instead of
# serializing after bucket k's rotation.  B=3 expected trace order:
#   E0 E1 | ar0 X0 | E2 ar1 X1 | ... (exactly ONE pmax before exchange 0 —
# the encode-fused layout would put both buckets' pmaxes there)
assert len(ar) == 3, (len(ar), "one grid pmax per bucket")
assert enc[1] < ar[0] < a2a[0], \
    "bucket 0's grid pmax must defer past bucket 1's encode"
assert sum(1 for r in ar if r < a2a[0]) == 1, \
    "exactly one grid pmax precedes the first exchange (deferred placement)"
assert ag[0] < enc[2] < ar[1] < a2a[1], \
    "bucket 1's grid pmax must ride the exchange stage, after encode 2"
print("QUANT_PMAX OK")

# ---- collective count stays constant in B ---------------------------------
txt8 = lower(8, mode="pipelined")
assert txt8.count("stablehlo.all_to_all") == 3
assert txt8.count("stablehlo.all_reduce") == 3
print("QUANT_CONSTANT_HLO OK")
"""


def _run_child(code):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


@pytest.fixture(scope="module")
def schedule_output():
    return _run_child(CHILD)


@pytest.fixture(scope="module")
def quant_schedule_output():
    return _run_child(CHILD_QUANT)


@pytest.mark.slow
def test_exchange_interleaves_neighboring_codec_kernels(schedule_output):
    """Acceptance: the pipelined HLO shows exchange collectives emitted
    between neighboring buckets' encode/decode kernels."""
    assert "PIPELINED_ORDER OK" in schedule_output, schedule_output


@pytest.mark.slow
def test_seed_loop_is_the_serial_baseline(schedule_output):
    assert "SERIAL_CONTROL OK" in schedule_output, schedule_output


@pytest.mark.slow
def test_pipelined_hlo_constant_in_bucket_count(schedule_output):
    assert "CONSTANT_HLO OK" in schedule_output, schedule_output


@pytest.mark.slow
def test_grid_pmax_rides_the_exchange_stage(quant_schedule_output):
    """Acceptance: for quantized pipelined strategies the THC grid pmax is
    emitted inside the exchange stage (deferred split encode), not at the
    tail of the encode stage."""
    assert "QUANT_PMAX OK" in quant_schedule_output, quant_schedule_output


@pytest.mark.slow
def test_quant_pipelined_hlo_constant_in_bucket_count(quant_schedule_output):
    assert "QUANT_CONSTANT_HLO OK" in quant_schedule_output, \
        quant_schedule_output

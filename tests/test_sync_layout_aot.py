"""Ahead-of-time compile of the sync engine for a described TPU v5e: the
bucket data must move in whole (8, 128) tiles.

``sync_packed`` in the ``pipelined`` schedule is compiled, with the codec
kernels Mosaic-compiled, on one described chip (the 1-chip cells' layout)
over a small multi-bucket arena: 5 buckets of 8 x 8 x 1024. The compiled
module is then read for what would move one sublane in eight of a tile:

* a bucket-sized f32 array tiled ``T(N,128)`` with N < 8 — a bucket laid
  along one sublane, as a row of a ``(B, E)`` arena or a ``(1, S)`` shard;
* a ``dynamic-slice`` / ``dynamic-update-slice`` of one row of a
  ``(B, E)`` row arena;
* a concatenate of arena size (the stack-then-concatenate the in-place
  schedule removed);
* a codec kernel fused into another operation: the fusion takes the
  kernel's name (``%_fwht_call.N``), and the trace's per-kernel accounting
  reads every such name as a kernel call.

Nothing runs, so this says nothing about results or times. The topology
is described in a module fixture, never at import (see
tests/test_kernels_aot.py), and the persistent compile cache is off around
the compiles. The kernel dispatch sees the CPU here, so the fixture steers
it to Mosaic for these compiles only.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import BucketPlan
from repro.core.allreduce import OptiReduceConfig, SyncContext, sync_packed
from repro.core.pipeline import arena_lane, resolve_spec
from repro.kernels import runtime

BLOCK = 1024                      # the cells' Hadamard block
BUCKET = 8 * 8 * BLOCK            # 65,536 elements: 64 rows of one block
NBUCKETS = 5                      # prologue, a 1-step loop, epilogue

STRATEGIES = ["optireduce", "optireduce_q"]


@pytest.fixture(scope="module")
def v5e_mesh():
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
    with pytest.MonkeyPatch.context() as mp, \
            runtime.impl_scope("mosaic"):
        mp.setattr(runtime, "_mosaic_available", lambda: True)
        yield Mesh([topo.devices[0]], ("data",))
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(v5e_mesh):
    """strategy -> (compiled module text, arena shape)."""
    out = {}
    for strategy in STRATEGIES:
        cfg = OptiReduceConfig(strategy=strategy, drop_rate=0.01,
                               drop_pattern="tail", hadamard_block=BLOCK)
        tree = {"g": jax.ShapeDtypeStruct((NBUCKETS * BUCKET,), jnp.float32)}
        plan = BucketPlan.for_tree(
            tree, BUCKET, lane=arena_lane(resolve_spec(cfg), cfg))

        def body(arena, key, cfg=cfg):
            return sync_packed(arena, SyncContext(cfg=cfg, key=key),
                               mode="pipelined")

        rep = NamedSharding(v5e_mesh, P())
        fn = jax.jit(jax.shard_map(body, mesh=v5e_mesh, in_specs=(P(), P()),
                                   out_specs=P(), check_vma=False),
                     donate_argnums=0)
        text = fn.lower(
            jax.ShapeDtypeStruct(plan.arena_shape, jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        ).compile().as_text()
        out[strategy] = (text, plan.arena_shape)
    return out


# %name = f32[d0,d1,...]{minor-to-major:T(a,b)...} op(
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([0-9,]*)\]"
                    r"\{([^}]*)\}\s+([\w\-]+)\(")


def _instructions(text):
    """(name, dtype, dims, layout, opcode) of every array instruction."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            yield m.group(1), m.group(2), dims, m.group(4), m.group(5)


def _sublane_sparse(text):
    out = []
    for name, dtype, dims, layout, _ in _instructions(text):
        tile = re.search(r"T\((\d+),128\)", layout)
        if (dtype == "f32" and math.prod(dims) >= BUCKET and tile
                and int(tile.group(1)) < 8):
            out.append((name, dims, layout))
    return out


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_arena_is_bucket_major_whole_tiles(compiled, strategy):
    _, shape = compiled[strategy]
    assert shape == (NBUCKETS, BUCKET // BLOCK, BLOCK), shape


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_codec_kernels_compile_in_the_sync(compiled, strategy):
    text, _ = compiled[strategy]
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_no_sublane_sparse_bucket_arrays(compiled, strategy):
    text, _ = compiled[strategy]
    sparse = _sublane_sparse(text)
    assert not sparse, sparse[:8]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_no_row_slices_of_a_flat_arena(compiled, strategy):
    text, _ = compiled[strategy]
    rows = [(name, dims) for name, _, dims, _, op in _instructions(text)
            if op in ("dynamic-slice", "dynamic-update-slice")
            and len(dims) == 2 and dims[1] == BUCKET]
    assert not rows, rows[:8]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_no_arena_sized_concatenate(compiled, strategy):
    text, _ = compiled[strategy]
    cats = [(name, dims) for name, _, dims, _, op in _instructions(text)
            if op == "concatenate" and math.prod(dims) >= NBUCKETS * BUCKET]
    assert not cats, cats


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_codec_kernels_stay_custom_calls(compiled, strategy):
    text, _ = compiled[strategy]
    named = [(name, op) for name, _, _, _, op in _instructions(text)
             if re.match(r"_\w+_call\.\d+$", name)]
    assert named, "no codec kernel in the compiled sync"
    fused = [n for n in named if n[1] != "custom-call"]
    assert not fused, fused

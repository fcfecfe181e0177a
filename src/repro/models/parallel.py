"""Parallelism context for manual-SPMD model code.

All model code runs inside a ``shard_map`` body (or on a single device for
smoke tests). ``ParallelCtx`` carries the mesh axis names; when an axis is
None the corresponding collective is the identity, so the *same* model code
runs single-device (CPU tests) and fully sharded (dry-run / production).

Tensor-parallel layout (manual Megatron-style):
  column-parallel:  W (D, F/tp) local -> local matmul, no collective
  row-parallel:     W (F/tp, D) local -> local matmul + psum over tp
  activations are replicated over tp between blocks (sequence-parallel
  variant available as a perf option — see train/trainer.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    tp_axis: str | None = None      # 'model'
    dp_axis: str | None = None      # 'data'  (fsdp gathers + grad sync)
    pod_axis: str | None = None     # 'pod'   (multi-pod meshes)
    fsdp: bool = False              # params sharded over dp_axis
    # fsdp weight gather: (w_local, dim, key) -> w_full. The trainer installs
    # a custom-VJP version whose backward is the OptiReduce reduce-scatter.
    gather: Callable | None = None
    # serving: keep MoE expert weights sharded over the dp axes and psum the
    # (tiny) expert activations instead of gathering the (huge) weights —
    # decode is weights-dominated, so this removes the collective bottleneck
    # (§Perf H2). Dense/attn weights still gather.
    moe_stationary: bool = False
    # sequence parallelism (Megatron-SP): the residual stream between
    # blocks is sharded over tp along the sequence dim; sublayers gather it
    # and reduce-scatter their output (same wire bytes as the psum it
    # replaces, but the per-layer saved residual shrinks by 1/tp — the
    # §Perf H3 memory lever).
    sp: bool = False

    def gather_seq(self, x):
        """(B, S/tp, D) -> (B, S, D) at a sublayer input."""
        if not (self.sp and self.tp_axis):
            return x
        return jax.lax.all_gather(x, self.tp_axis, axis=1, tiled=True)

    def reduce_output(self, x):
        """Row-parallel output reduction: psum, or psum_scatter over the
        sequence dim under sequence parallelism."""
        if not self.tp_axis:
            return x
        if self.sp:
            return jax.lax.psum_scatter(x, self.tp_axis,
                                        scatter_dimension=1, tiled=True)
        return jax.lax.psum(x, self.tp_axis)

    def dp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in (self.pod_axis, self.dp_axis) if a)

    def dp_shard_index(self) -> jnp.ndarray:
        """Linear index over (pod, data) — matches P(('pod','data'))."""
        idx = jnp.zeros((), jnp.int32)
        for ax in self.dp_axes():
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return idx

    def psum_dp(self, x):
        axes = self.dp_axes()
        return jax.lax.psum(x, axes) if axes else x

    def tp_size(self) -> int:
        return jax.lax.axis_size(self.tp_axis) if self.tp_axis else 1

    def dp_size(self) -> int:
        n = jax.lax.axis_size(self.dp_axis) if self.dp_axis else 1
        if self.pod_axis:
            n *= jax.lax.axis_size(self.pod_axis)
        return n

    def tp_index(self) -> jnp.ndarray:
        if self.tp_axis is None:
            return jnp.zeros((), jnp.int32)
        return jax.lax.axis_index(self.tp_axis)

    def psum_tp(self, x):
        return jax.lax.psum(x, self.tp_axis) if self.tp_axis else x

    def pmax_tp(self, x):
        return jax.lax.pmax(x, self.tp_axis) if self.tp_axis else x

    def all_gather_dp(self, x, axis: int):
        """FSDP weight gather (identity when not fsdp)."""
        if not (self.fsdp and self.dp_axis):
            return x
        return jax.lax.all_gather(x, self.dp_axis, axis=axis, tiled=True)


# A no-parallelism context for single-device smoke tests / references.
SINGLE = ParallelCtx()

"""Serving engine: batched decode (and prefill) under the production mesh.

Sharding policy (chosen per shape):
  * batch >= dp_total           -> KV/state batch dim over ('pod','data'),
                                   heads over 'model' (decode_32k).
  * batch <  dp_total (B=1 long) -> KV *sequence* dim over the data axes
                                   (flash-decoding split: partial softmax
                                   merged with psum'd statistics), heads
                                   over 'model' (long_500k).

The engine builds serve_step = shard_map(decode_step) and exposes
``abstract_state`` for the dry-run (ShapeDtypeStructs only).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import decode_step, init_decode_state, param_specs
from repro.models.layers import KVCache
from repro.models.parallel import ParallelCtx
from repro.models.ssm import SSMState


@dataclasses.dataclass(frozen=True)
class ServePlan:
    batch_axes: tuple[str, ...]      # mesh axes carrying the batch dim
    seq_axes: tuple[str, ...]        # mesh axes carrying the KV seq dim
    tp: int
    dp_total: int

    @property
    def seq_shards(self) -> int:
        return self.dp_total if self.seq_axes else 1


def plan_serving(mesh, global_batch: int) -> ServePlan:
    names = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    tp = mesh.shape["model"] if "model" in names else 1
    if global_batch >= dp_total and global_batch % dp_total == 0:
        return ServePlan(batch_axes=dp_axes, seq_axes=(), tp=tp,
                         dp_total=dp_total)
    # tiny batch: shard the cache's sequence dim instead (split-K decode)
    return ServePlan(batch_axes=(), seq_axes=dp_axes, tp=tp,
                     dp_total=dp_total)


def state_specs(cfg: ModelConfig, plan: ServePlan):
    """PartitionSpecs for the decode-state pytree from init_decode_state:
    KV (stack, B, S, H_kv, dh); SSM conv (stack, B, K-1, d_inner),
    ssm (stack, B, H, P, N)."""
    from repro.models.transformer import TpLayout
    lay = TpLayout.build(cfg, plan.tp)
    b_ax = (plan.batch_axes if len(plan.batch_axes) > 1
            else (plan.batch_axes[0] if plan.batch_axes else None))
    s_ax = (plan.seq_axes if len(plan.seq_axes) > 1
            else (plan.seq_axes[0] if plan.seq_axes else None))
    kv_sharded = plan.tp > 1 and (not lay.kv_replicated or lay.kv_single)
    kv_tp = "model" if kv_sharded else None

    def kv_spec(_):
        return P(None, b_ax, s_ax, kv_tp, None)

    def conv_spec(_):
        return P(None, b_ax, None, "model" if plan.tp > 1 else None)

    def ssm_spec(_):
        return P(None, b_ax, "model" if plan.tp > 1 else None, None, None)

    def build(state):
        out = []
        for st in state:
            if isinstance(st, KVCache):
                out.append(KVCache(k=kv_spec(st), v=kv_spec(st)))
            elif isinstance(st, SSMState):
                out.append(SSMState(conv=conv_spec(st), ssm=ssm_spec(st)))
            else:
                raise TypeError(type(st))
        return out

    return build


def abstract_state(cfg: ModelConfig, shape: ShapeConfig, plan: ServePlan):
    """ShapeDtypeStructs for the decode state at GLOBAL (tp-padded) shapes."""
    return jax.eval_shape(
        functools.partial(_global_state, cfg=cfg, batch=shape.global_batch,
                          max_seq=shape.seq_len, tp=plan.tp))


def _global_state(cfg: ModelConfig, batch: int, max_seq: int, tp: int):
    """Global decode state with tp-padded head counts (local x tp)."""
    local = init_decode_state(None, cfg, batch=batch, max_seq=max_seq, tp=tp,
                              seq_shards=1)
    out = []
    for st in local:
        if isinstance(st, KVCache):
            k = st.k
            # local kv heads x tp when kv is sharded or sliced; replicated
            # (multi-head) kv stays at its local count
            from repro.models.transformer import TpLayout
            lay = TpLayout.build(cfg, tp)
            mult = tp if (not lay.kv_replicated or lay.kv_single) else 1
            shape = (k.shape[0], k.shape[1], k.shape[2],
                     k.shape[3] * mult, k.shape[4])
            out.append(KVCache(k=jnp.zeros(shape, k.dtype),
                               v=jnp.zeros(shape, k.dtype)))
        else:
            conv = st.conv
            ssm = st.ssm
            out.append(SSMState(
                conv=jnp.zeros((conv.shape[0], conv.shape[1], conv.shape[2],
                                conv.shape[3] * tp), conv.dtype),
                ssm=jnp.zeros((ssm.shape[0], ssm.shape[1],
                               ssm.shape[2] * tp, ssm.shape[3],
                               ssm.shape[4]), ssm.dtype)))
    return out


def build_serve_step(cfg: ModelConfig, mesh, plan: ServePlan, *,
                     unroll: bool = False, weight_fsdp: bool = False,
                     moe_stationary: bool = False):
    """serve_step(params, state, tokens, pos, key) -> (next_tok, new_state).

    ``weight_fsdp``: additionally shard weights over the data axes and
    gather them just-in-time per layer (ZeRO-inference). Required for the
    archs whose weights exceed HBM at tp=16 (arctic 477B: 60 GB/chip at
    tp-only vs 3.7 GB fsdp'd over 256); costs an all-gather per layer —
    the roofline flags these cells collective-bound, and §Perf explores
    the 2-axis expert-parallel alternative.
    """
    names = tuple(mesh.axis_names)
    tp_axis = "model" if "model" in names else None
    dp_axes = tuple(a for a in ("pod", "data") if a in names)

    gather = None
    if weight_fsdp:
        def gather(w, dim, key):
            del key
            for ax in reversed(dp_axes):
                w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
            return w

    pctx = ParallelCtx(tp_axis=tp_axis,
                       dp_axis="data" if "data" in names else None,
                       pod_axis="pod" if "pod" in names else None,
                       fsdp=weight_fsdp, gather=gather,
                       moe_stationary=moe_stationary)
    seq_axes = plan.seq_axes if plan.seq_axes else None

    def body(params, state, tokens, pos, key):
        return decode_step(params, state, tokens, pos, cfg, pctx, key=key,
                           seq_shard_axis=seq_axes, unroll=unroll)

    p_specs = param_specs(cfg, tp=plan.tp,
                          fsdp_axes=dp_axes if weight_fsdp else None)
    s_specs = state_specs(cfg, plan)
    b_ax = (plan.batch_axes if len(plan.batch_axes) > 1
            else (plan.batch_axes[0] if plan.batch_axes else None))
    tok_spec = P(b_ax, None)

    def make(abstract_st):
        st_specs = s_specs(abstract_st)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, st_specs, tok_spec, P(), P()),
            out_specs=(tok_spec, st_specs),
            check_vma=False)
        shardings = {
            "params": jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            "state": jax.tree.map(lambda s: NamedSharding(mesh, s), st_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
            "tokens": NamedSharding(mesh, tok_spec),
        }
        return fn, shardings

    return make


def generate(params, cfg: ModelConfig, prompts: jnp.ndarray, *,
             max_new: int = 16, key=None, pctx: ParallelCtx | None = None
             ) -> jnp.ndarray:
    """Single-host convenience loop (examples/tests): prefill the prompt
    token-by-token, then greedy-decode ``max_new`` tokens."""
    from repro.models.parallel import SINGLE
    pctx = pctx or SINGLE
    key = key if key is not None else jax.random.PRNGKey(0)
    b, s0 = prompts.shape
    state = init_decode_state(params, cfg, batch=b, max_seq=s0 + max_new,
                              dtype=cfg.param_dtype)
    tok = prompts[:, :1]
    out = [prompts]
    for t in range(s0 + max_new - 1):
        nxt, state = decode_step(params, state, tok, jnp.asarray(t, jnp.int32),
                                 cfg, pctx, key=key)
        if t + 1 < s0:
            tok = prompts[:, t + 1:t + 2]      # teacher-force the prompt
        else:
            tok = nxt
            out.append(nxt)
    return jnp.concatenate(out, axis=1)

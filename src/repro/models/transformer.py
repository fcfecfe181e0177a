"""Decoder-only transformer family covering all assigned architectures:
dense (GQA), MoE (top-k, shared experts, dense-residual), SSM (Mamba-2),
hybrid interleave (Jamba), and frontend-stubbed VLM/audio variants.

Structure
---------
Layers are organized into a repeating *period* (1 for homogeneous stacks;
8 for Jamba's 1:7 attn:mamba interleave). Parameters are stacked over the
repeat count and the stack is driven by ``jax.lax.scan`` — compact HLO,
which matters for the 512-device dry-run compiles.

Parallelism (manual SPMD inside shard_map; see models/parallel.py)
  * tp ('model'): q heads / d_ff / experts / vocab column-sharded; row-
    parallel projections psum. KV heads are replicated (and q heads padded)
    when they don't divide tp.
  * fsdp ('data' [,'pod']): every large weight additionally sharded on its
    non-tp dim; gathered just-in-time in the scan body via ``pctx.gather``
    — whose custom VJP is where OptiReduce runs as the ZeRO reduce-scatter
    (see train/trainer.py).
  * loss: vocab-sharded cross-entropy, chunked over sequence, its gradient
    formed in the forward pass — full logits are never alive (256k vocab
    would not fit otherwise).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.obs import scopes
from .layers import (KVCache, attention_decode, attention_train, gated_mlp,
                     rms_norm)
from .moe import moe_block
from .parallel import ParallelCtx
from .ssm import SSMState, mamba2_forward


# --------------------------------------------------------------------- layout
def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


@dataclasses.dataclass(frozen=True)
class TpLayout:
    """Padded dimensions for a given tensor-parallel degree."""
    tp: int
    heads_pad: int
    kv_pad: int          # padded KV heads (== n_kv when replicated/sliced)
    kv_replicated: bool  # n_kv < tp: KV projection weights replicated
    kv_single: bool      # ...and each shard's q heads share ONE kv head, so
                         # each shard keeps exactly one KV head (cache 1/tp)
    vocab_pad: int
    experts_pad: int
    ssm_heads_pad: int

    @staticmethod
    def build(cfg: ModelConfig, tp: int) -> "TpLayout":
        heads_pad = _ceil_to(cfg.n_heads, tp) if cfg.n_heads else 0
        kv_single = False
        if cfg.n_kv_heads and cfg.n_kv_heads >= tp:
            kv_pad, kv_repl = _ceil_to(cfg.n_kv_heads, tp), False
        else:
            kv_pad, kv_repl = cfg.n_kv_heads, True
            if kv_pad and tp > 1:
                hq_l = heads_pad // tp
                kv_single = all(
                    len({(q * kv_pad) // heads_pad
                         for q in range(s * hq_l, (s + 1) * hq_l)}) == 1
                    for s in range(tp))
        return TpLayout(
            tp=tp,
            heads_pad=heads_pad,
            kv_pad=kv_pad,
            kv_replicated=kv_repl,
            kv_single=kv_single,
            vocab_pad=_ceil_to(cfg.vocab_size, tp),
            experts_pad=_ceil_to(cfg.n_experts, tp) if cfg.n_experts else 0,
            ssm_heads_pad=_ceil_to(cfg.ssm_heads, tp) if cfg.ssm_heads else 0,
        )

    @property
    def kv_local(self) -> int:
        """KV heads held per shard (cache head dim)."""
        if self.kv_single:
            return 1
        if self.kv_replicated:
            return self.kv_pad
        return self.kv_pad // self.tp

    def kv_select(self, shard: jnp.ndarray) -> jnp.ndarray | None:
        """Global KV head this shard keeps (kv_single only)."""
        if not self.kv_single:
            return None
        hq_l = self.heads_pad // self.tp
        return (shard * hq_l * self.kv_pad) // self.heads_pad

    def kv_map(self, cfg: ModelConfig, shard: jnp.ndarray) -> jnp.ndarray | None:
        """Local q head -> local KV-cache head index (None = default GQA)."""
        hq_l = self.heads_pad // self.tp
        if self.kv_single:
            return None          # one local head; default repeat covers it
        if self.kv_replicated:
            # cache holds all n_kv heads; global q head h uses h*kv//heads
            gq = shard * hq_l + jnp.arange(hq_l)
            return jnp.clip((gq * self.kv_pad) // max(self.heads_pad, 1),
                            0, self.kv_pad - 1)
        kv_l = self.kv_pad // self.tp
        if hq_l % kv_l == 0 and (self.heads_pad // self.kv_pad) * kv_l == hq_l:
            return None  # contiguous GQA grouping holds shard-locally
        return jnp.arange(hq_l) * kv_l // hq_l


# ------------------------------------------------------------- param building
class Leaf(NamedTuple):
    shape: tuple
    spec: P              # global PartitionSpec (stack dim first where present)
    fsdp_dim: int | None # dim sharded over the fsdp axes (None = replicated)
    init: str            # 'normal' | 'zeros' | 'ones' | 'alog' | 'conv'


def _period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.attn_every:
        p = cfg.attn_every
    if cfg.n_experts and cfg.moe_every > 1:
        p = int(np.lcm(p, cfg.moe_every))
    return p


def _layer_leaves(cfg: ModelConfig, lay: TpLayout, layer_in_period: int,
                  n_repeat: int, fsdp_axes) -> dict[str, Leaf]:
    """Leaf table for one period position; all shapes carry the stack dim."""
    d = cfg.d_model
    dh = cfg.dh
    fx = fsdp_axes  # e.g. ('data',) or ('pod','data') or None
    L = n_repeat

    def w(shape, tp_dim=None, fsdp_dim=None, init="normal"):
        spec = [None] * (len(shape))
        if tp_dim is not None and lay.tp > 1:
            spec[tp_dim] = "model"
        if fx is not None and fsdp_dim is not None:
            spec[fsdp_dim] = fx if len(fx) > 1 else fx[0]
        return Leaf(tuple(shape), P(*spec), fsdp_dim if fx else None, init)

    leaves: dict[str, Leaf] = {}
    is_attn = cfg.is_attn_layer(layer_in_period)
    is_moe = cfg.is_moe_layer(layer_in_period)

    leaves["ln1"] = w((L, d), init="ones")
    if is_attn:
        leaves["wq"] = w((L, d, lay.heads_pad * dh), tp_dim=2, fsdp_dim=1)
        kv_tp = None if lay.kv_replicated else 2
        leaves["wk"] = w((L, d, lay.kv_pad * dh), tp_dim=kv_tp, fsdp_dim=1)
        leaves["wv"] = w((L, d, lay.kv_pad * dh), tp_dim=kv_tp, fsdp_dim=1)
        leaves["wo"] = w((L, lay.heads_pad * dh, d), tp_dim=1, fsdp_dim=2)
    else:
        di = cfg.d_inner
        h = lay.ssm_heads_pad or cfg.ssm_heads
        gn = 1 * cfg.ssm_state
        leaves["wx"] = w((L, d, di), tp_dim=2, fsdp_dim=1)
        leaves["wz"] = w((L, d, di), tp_dim=2, fsdp_dim=1)
        leaves["wB"] = w((L, d, gn), fsdp_dim=1)
        leaves["wC"] = w((L, d, gn), fsdp_dim=1)
        leaves["wdt"] = w((L, d, h), tp_dim=2, fsdp_dim=1)
        leaves["dt_bias"] = w((L, h), tp_dim=1, init="zeros")
        leaves["conv_w"] = w((L, cfg.ssm_conv_k, di), tp_dim=2, init="conv")
        leaves["a_log"] = w((L, h), tp_dim=1, init="alog")
        leaves["d_skip"] = w((L, h), tp_dim=1, init="ones")
        leaves["out_proj"] = w((L, di, d), tp_dim=1, fsdp_dim=2)

    # FFN position: MLP or MoE (or both for arctic's dense residual);
    # pure-SSM layers (d_ff == 0, no MoE) have no FFN sublayer at all.
    needs_dense = (not is_moe) or cfg.dense_residual
    if (needs_dense and cfg.d_ff) or is_moe:
        leaves["ln2"] = w((L, d), init="ones")
    if needs_dense and cfg.d_ff:
        leaves["w_gate"] = w((L, d, cfg.d_ff), tp_dim=2, fsdp_dim=1)
        leaves["w_up"] = w((L, d, cfg.d_ff), tp_dim=2, fsdp_dim=1)
        leaves["w_down"] = w((L, cfg.d_ff, d), tp_dim=1, fsdp_dim=2)
    if is_moe:
        e = lay.experts_pad
        f = cfg.d_ff
        leaves["router"] = w((L, d, e), fsdp_dim=1)
        leaves["we_gate"] = w((L, e, d, f), tp_dim=1, fsdp_dim=2)
        leaves["we_up"] = w((L, e, d, f), tp_dim=1, fsdp_dim=2)
        leaves["we_down"] = w((L, e, f, d), tp_dim=1, fsdp_dim=3)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * cfg.d_ff
            leaves["ws_gate"] = w((L, d, fs), tp_dim=2, fsdp_dim=1)
            leaves["ws_up"] = w((L, d, fs), tp_dim=2, fsdp_dim=1)
            leaves["ws_down"] = w((L, fs, d), tp_dim=1, fsdp_dim=2)
    return leaves


def param_table(cfg: ModelConfig, *, tp: int = 1,
                fsdp_axes: tuple[str, ...] | None = None
                ) -> dict[str, Any]:
    """The complete leaf table: {'embed': ..., 'stages': [pos0, pos1, ...],
    'final_ln': ...}. Shapes are global (padded); specs are PartitionSpecs."""
    lay = TpLayout.build(cfg, tp)
    period = _period(cfg)
    assert cfg.n_layers % period == 0, (cfg.name, cfg.n_layers, period)
    n_repeat = cfg.n_layers // period
    fx = fsdp_axes

    def w(shape, tp_dim=None, fsdp_dim=None, init="normal"):
        spec = [None] * len(shape)
        if tp_dim is not None and tp > 1:
            spec[tp_dim] = "model"
        if fx is not None and fsdp_dim is not None:
            spec[fsdp_dim] = fx if len(fx) > 1 else fx[0]
        return Leaf(tuple(shape), P(*spec), fsdp_dim if fx else None, init)

    table: dict[str, Any] = {
        "embed": w((lay.vocab_pad, cfg.d_model), tp_dim=0, fsdp_dim=1),
        "final_ln": w((cfg.d_model,), init="ones"),
        "stages": [
            _layer_leaves(cfg, lay, pos, n_repeat, fx) for pos in range(period)
        ],
    }
    if not cfg.tie_embeddings:
        table["lm_head"] = w((cfg.d_model, lay.vocab_pad), tp_dim=1,
                             fsdp_dim=0)
    if cfg.frontend:
        table["frontend_proj"] = w((cfg.frontend_dim, cfg.d_model),
                                   fsdp_dim=0)
    return table


def _tree_map_table(fn: Callable[[Leaf], Any], table) -> Any:
    if isinstance(table, Leaf):
        return fn(table)
    if isinstance(table, dict):
        return {k: _tree_map_table(fn, v) for k, v in table.items()}
    if isinstance(table, list):
        return [_tree_map_table(fn, v) for v in table]
    raise TypeError(type(table))


def param_specs(cfg: ModelConfig, *, tp: int = 1, fsdp_axes=None):
    return _tree_map_table(lambda l: l.spec,
                           param_table(cfg, tp=tp, fsdp_axes=fsdp_axes))


def abstract_params(cfg: ModelConfig, *, tp: int = 1, fsdp_axes=None):
    dt = cfg.param_dtype
    return _tree_map_table(lambda l: jax.ShapeDtypeStruct(l.shape, dt),
                           param_table(cfg, tp=tp, fsdp_axes=fsdp_axes))


def init_params(key: jax.Array, cfg: ModelConfig, *, tp: int = 1,
                fsdp_axes=None, scale: float = 0.02):
    """Materialize parameters (single-host; used by smoke tests/examples)."""
    table = param_table(cfg, tp=tp, fsdp_axes=fsdp_axes)
    leaves_flat = jax.tree.leaves(table,
                                  is_leaf=lambda x: isinstance(x, Leaf))
    keys = jax.random.split(key, len(leaves_flat))
    it = iter(range(len(leaves_flat)))

    def mk(leaf: Leaf):
        i = next(it)
        if leaf.init == "zeros":
            return jnp.zeros(leaf.shape, cfg.param_dtype)
        if leaf.init == "ones":
            return jnp.ones(leaf.shape, cfg.param_dtype)
        if leaf.init == "alog":
            # A in [1, 16) -> a_log = log(A), mamba2 default
            u = jax.random.uniform(keys[i], leaf.shape, jnp.float32,
                                   1.0, 16.0)
            return jnp.log(u).astype(cfg.param_dtype)
        if leaf.init == "conv":
            fan = leaf.shape[-2]
            return (jax.random.normal(keys[i], leaf.shape, jnp.float32)
                    / math.sqrt(fan)).astype(cfg.param_dtype)
        fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
        std = min(scale, 1.0 / math.sqrt(fan_in))
        return (jax.random.normal(keys[i], leaf.shape, jnp.float32)
                * std).astype(cfg.param_dtype)

    return _tree_map_table(mk, table)


def count_params(cfg: ModelConfig) -> int:
    table = param_table(cfg, tp=1)
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        table, is_leaf=lambda x: isinstance(x, Leaf)))


def active_params(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top_k of n_experts)."""
    if not cfg.n_experts:
        return count_params(cfg)
    table = param_table(cfg, tp=1)
    total = 0
    for path, leaf in _walk(table):
        n = int(np.prod(leaf.shape))
        if path.startswith("we_"):
            n = n * cfg.top_k // max(cfg.n_experts, 1)
        total += n
    return total


def _walk(table, prefix=""):
    if isinstance(table, Leaf):
        yield prefix, table
    elif isinstance(table, dict):
        for k, v in table.items():
            yield from _walk(v, k)
    elif isinstance(table, list):
        for v in table:
            yield from _walk(v, prefix)


# ------------------------------------------------------------------- forward
def _maybe_gather(pctx: ParallelCtx, w: jnp.ndarray, dim: int | None,
                  key: jax.Array | None) -> jnp.ndarray:
    if dim is None or not pctx.fsdp or pctx.gather is None:
        return w
    return pctx.gather(w, dim, key)


def _apply_layer(x, lw, cfg: ModelConfig, lay: TpLayout, pctx: ParallelCtx,
                 pos_in_period: int, *, positions, key,
                 cache=None, decode=False, pos=None, seq_shard_axis=None,
                 collect_state=False):
    """One layer (pre-norm residual). Returns (x, new_cache)."""
    table = _layer_leaves(cfg, lay, pos_in_period, 1, ("data",))
    # fsdp_dim in the table counts the stack dim; layer slices have it removed
    fsdp_dim = {k: (v.fsdp_dim - 1 if v.fsdp_dim is not None else None)
                for k, v in table.items()}

    def g(name):
        return _maybe_gather(pctx, lw[name], fsdp_dim.get(name), key)

    is_attn = cfg.is_attn_layer(pos_in_period)
    is_moe = cfg.is_moe_layer(pos_in_period)
    h = rms_norm(x, lw["ln1"])           # per-token: valid on a seq shard
    h = pctx.gather_seq(h)               # SP: (B, S/tp, D) -> (B, S, D)
    if pctx.sp and not decode:
        # sublayers see the full sequence; rebuild absolute positions
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2])
    new_cache = cache
    if is_attn:
        wdict = {"wq": g("wq"), "wk": g("wk"), "wv": g("wv"), "wo": g("wo"),
                 "head_dim": cfg.dh, "attn_chunk": cfg.attn_chunk}
        shard = pctx.tp_index()
        kv_map = lay.kv_map(cfg, shard)
        kv_sel = lay.kv_select(shard)
        if decode:
            att, new_cache = attention_decode(
                h, wdict, cache, pctx, pos=pos, rope_theta=cfg.rope_theta,
                seq_shard_axis=seq_shard_axis, kv_map=kv_map,
                kv_select=kv_sel)
        elif collect_state:
            att, new_cache = attention_train(
                h, wdict, pctx, positions=positions,
                rope_theta=cfg.rope_theta, kv_map=kv_map, kv_select=kv_sel,
                collect_kv=True)
        else:
            att = attention_train(h, wdict, pctx, positions=positions,
                                  rope_theta=cfg.rope_theta, kv_map=kv_map,
                                  kv_select=kv_sel)
        x = x + att
    else:
        wdict = {"wx": g("wx"), "wz": g("wz"), "wB": g("wB"), "wC": g("wC"),
                 "wdt": g("wdt"), "dt_bias": lw["dt_bias"],
                 "conv_w": lw["conv_w"], "a_log": lw["a_log"],
                 "d_skip": lw["d_skip"], "out_proj": g("out_proj"),
                 "d_state": cfg.ssm_state, "n_groups": 1}
        y, new_cache = mamba2_forward(h, wdict, pctx, chunk=cfg.ssm_chunk,
                                      state=cache, decode=decode)
        x = x + y

    if "ln2" not in lw:                    # pure-SSM layer: no FFN sublayer
        return x, new_cache
    h2 = rms_norm(x, lw["ln2"])
    h2 = pctx.gather_seq(h2)
    ff = 0.0
    if "w_gate" in lw:
        ff = ff + gated_mlp(h2, {"w_gate": g("w_gate"), "w_up": g("w_up"),
                                 "w_down": g("w_down")}, pctx,
                            activation=cfg.activation)
    if is_moe:
        stationary = pctx.moe_stationary and pctx.fsdp
        if stationary:   # expert weights stay dp-sharded (§Perf H2)
            moe_w = {"router": g("router"), "we_gate": lw["we_gate"],
                     "we_up": lw["we_up"], "we_down": lw["we_down"]}
        else:
            moe_w = {"router": g("router"), "we_gate": g("we_gate"),
                     "we_up": g("we_up"), "we_down": g("we_down")}
        ff = ff + moe_block(h2, moe_w, pctx, top_k=cfg.top_k,
                            n_experts=cfg.n_experts,
                            capacity_factor=cfg.capacity_factor,
                            activation=cfg.activation,
                            weights_stationary=stationary)
        if cfg.n_shared_experts:
            ff = ff + gated_mlp(h2, {"w_gate": g("ws_gate"),
                                     "w_up": g("ws_up"),
                                     "w_down": g("ws_down")}, pctx,
                                activation=cfg.activation)
    return x + ff, new_cache


def embed_tokens(params, tokens, cfg: ModelConfig, lay: TpLayout,
                 pctx: ParallelCtx, key=None) -> jnp.ndarray:
    """Vocab-sharded embedding lookup: local-range gather + psum over tp.

    Rows are gathered from a float32 view of the table, so the gather's
    transpose, the scatter-add of the gradient, sums a token's
    contributions in float32 and casts to the table's dtype once. (A bf16
    scatter-add loses much of a frequent token's thousands of same-signed
    contributions.) The rows looked up are unchanged: a bf16 value is
    exact in float32.
    """
    emb = _maybe_gather(pctx, params["embed"], 1, key)   # (V_local, D)
    with jax.named_scope(scopes.EMBED):
        v_local = emb.shape[0]
        shard = pctx.tp_index()
        lo = shard * v_local
        local = tokens - lo
        valid = (local >= 0) & (local < v_local)
        safe = jnp.clip(local, 0, v_local - 1)
        out = jnp.take(emb.astype(jnp.float32), safe, axis=0)
        out = jnp.where(valid[..., None], out, 0).astype(cfg.param_dtype)
    return pctx.psum_tp(out)


def forward_hidden(params, batch, cfg: ModelConfig, pctx: ParallelCtx, *,
                   key: jax.Array, remat: bool = True,
                   collect_state: bool = False, unroll: bool = False):
    """Token/prefix embeddings -> final hidden states (B, S, D).

    With collect_state=True (prefill), also returns the per-stage decode
    state (KV caches / SSM states), stacked over the scan dim.
    """
    lay = TpLayout.build(cfg, pctx.tp_size())
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg, lay, pctx, key)
    if cfg.frontend and "prefix_embeds" in batch:
        proj = _maybe_gather(pctx, params["frontend_proj"], 0, key)
        pref = jnp.einsum("bpf,fd->bpd", batch["prefix_embeds"].astype(
            cfg.param_dtype), proj.astype(cfg.param_dtype))
        x = jnp.concatenate([pref, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if pctx.sp and pctx.tp_axis:
        # residual stream sharded over tp along seq (Megatron-SP); x is
        # replicated over tp here, so every shard just takes its slice
        tpn = pctx.tp_size()
        s_l = s // tpn
        x = jax.lax.dynamic_slice_in_dim(
            x, pctx.tp_index() * s_l, s_l, axis=1)
    period = _period(cfg)

    def body(carry, stage_params):
        xc, idx = carry
        states = []
        for pos in range(period):
            # serialize layer scheduling: without the barrier (on weights
            # too — their layout copies don't depend on xc and would be
            # hoisted) XLA's latency-oriented scheduler overlaps several
            # layers' temporaries (jamba prefill measured 55 GiB/dev)
            xc, lw = jax.lax.optimization_barrier((xc, stage_params[pos]))
            lkey = jax.random.fold_in(key, idx * period + pos)
            xc, st = _apply_layer(xc, lw, cfg, lay, pctx, pos,
                                  positions=positions, key=lkey,
                                  collect_state=collect_state)
            states.append(st)
        return (xc, idx + 1), (states if collect_state else None)

    if remat and not collect_state:
        body = jax.checkpoint(body)
    stages = params["stages"]  # list over period of stacked leaves
    if unroll:
        # Python-loop form: no while-loop in HLO, so cost_analysis counts
        # every layer (the dry-run cost model compiles shallow unrolled
        # variants; scan undercounts loop bodies — see launch/dryrun.py)
        n_repeat = jax.tree.leaves(stages)[0].shape[0]
        carry = (x, jnp.zeros((), jnp.int32))
        collected = []
        for r in range(n_repeat):
            stage_r = jax.tree.map(lambda a: a[r], stages)
            carry, st = body(carry, stage_r)
            collected.append(st)
        x, _ = carry
        states = (jax.tree.map(lambda *xs: jnp.stack(xs), *collected)
                  if collect_state else None)
    else:
        (x, _), states = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)),
                                      stages)
    x = rms_norm(x, params["final_ln"])
    if pctx.sp and pctx.tp_axis:
        # restore the full sequence for the (vocab-sharded) head
        x = jax.lax.all_gather(x, pctx.tp_axis, axis=1, tiled=True)
    if collect_state:
        return x, states
    return x


def lm_loss(params, batch, cfg: ModelConfig, pctx: ParallelCtx, *,
            key: jax.Array, seq_chunk: int = 1024,
            remat: bool = True, unroll: bool = False) -> jnp.ndarray:
    """Mean next-token cross-entropy with a vocab-sharded, seq-chunked
    softmax (full logits are never materialized).

    ``remat`` applies to ``forward_hidden``. The head and loss are one
    custom-VJP function (``_head_loss``): differentiated, it forms the
    head's gradient in the forward pass from each chunk's logits, so the
    logits matmul runs once; undifferentiated, it computes the loss only.
    """
    x = forward_hidden(params, batch, cfg, pctx, key=key, remat=remat,
                       unroll=unroll)
    labels = batch["labels"]
    p = x.shape[1] - labels.shape[1]
    if p:
        x = x[:, p:]                      # loss only on token positions
    head = params.get("lm_head")
    if head is None:
        emb = _maybe_gather(pctx, params["embed"], 1, key)
        head_l = emb.T                    # (D, V_local)
    else:
        head_l = _maybe_gather(pctx, head, 0, key)
    return _head_loss(x, head_l, labels, pctx, seq_chunk, unroll)


def _head_chunks(x, head_l, labels, pctx: ParallelCtx, seq_chunk: int,
                 unroll: bool, grads: bool):
    """Loss (and with ``grads`` its gradient for x and head_l) over
    sequence chunks of the vocab-sharded head.

    The cross-entropy ends the model and its cotangent is a scalar, so the
    gradient is g = (softmax - onehot) * weight / count per chunk, with
    dx = g W^T and dW = sum of x^T g: three matmuls a chunk, g fused into
    the last two by XLA. On a tp mesh (``shard_map(check_vma=False)``)
    autodiff transposes each psum_tp of the loss into a psum_tp of its
    replicated cotangent; psum_tp of the row coefficient gives the same
    gradient, and each shard's dx is its vocabulary slice's partial.
    """
    b, s, d = x.shape
    v_local = head_l.shape[1]
    lo = pctx.tp_index() * v_local
    # frontend prefixes may leave a token count that is not a multiple
    # of the requested chunk
    chunk = math.gcd(min(seq_chunk, s), s)
    xc = x.reshape(b, s // chunk, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, s // chunk, chunk).transpose(1, 0, 2)
    denom = jnp.maximum(jnp.sum((labels >= 0).astype(jnp.float32)), 1.0)
    w32 = head_l.astype(jnp.float32)

    def chunk_loss(x_chunk, y_chunk):
        x32 = x_chunk.astype(jnp.float32)
        logits = jnp.einsum("bcd,dv->bcv", x32, w32)
        m = pctx.pmax_tp(jnp.max(logits, axis=-1))
        z = pctx.psum_tp(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        local_y = y_chunk - lo
        valid = (local_y >= 0) & (local_y < v_local)
        safe = jnp.clip(local_y, 0, v_local - 1)
        picked = jnp.take_along_axis(logits, safe[..., None],
                                     axis=-1)[..., 0]
        correct = pctx.psum_tp(jnp.where(valid, picked, 0.0))
        weight = (y_chunk >= 0).astype(jnp.float32)
        lse = jnp.log(z) + m
        nll = jnp.sum((lse - correct) * weight)
        if not grads:
            return nll, None, None
        coef = pctx.psum_tp(weight / denom)
        onehot = (jnp.arange(v_local) == local_y[..., None])
        g = (jnp.exp(logits - lse[..., None]) - onehot) * coef[..., None]
        dx = jnp.einsum("bcv,dv->bcd", g, w32).astype(x.dtype)
        dw = jnp.einsum("bcd,bcv->dv", x32, g).astype(head_l.dtype)
        return nll, dx, dw

    dw = jnp.zeros(head_l.shape, head_l.dtype) if grads else None
    if xc.shape[0] == 1 or unroll:
        total = jnp.zeros(())
        dxs = []
        for i in range(xc.shape[0]):
            l, dx_i, dw_i = chunk_loss(xc[i], yc[i])
            total = total + l
            if grads:
                dxs.append(dx_i)
                dw = dw + dw_i
        dx = jnp.concatenate(dxs, axis=1) if grads else None
    else:
        def scan_body(carry, inp):
            tot, dw_acc = carry
            l, dx_i, dw_i = chunk_loss(*inp)
            if grads:
                dw_acc = dw_acc + dw_i
            return (tot + l, dw_acc), dx_i

        (total, dw), dxc = jax.lax.scan(scan_body, (jnp.zeros(()), dw),
                                        (xc, yc))
        dx = (dxc.transpose(1, 0, 2, 3).reshape(b, s, d) if grads
              else None)
    return total / denom, dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _head_loss(x, head_l, labels, pctx, seq_chunk, unroll):
    with jax.named_scope(scopes.LM_HEAD):
        return _head_chunks(x, head_l, labels, pctx, seq_chunk, unroll,
                            grads=False)[0]


def _head_loss_fwd(x, head_l, labels, pctx, seq_chunk, unroll):
    with jax.named_scope(scopes.LM_HEAD):
        loss, dx, dw = _head_chunks(x, head_l, labels, pctx, seq_chunk,
                                    unroll, grads=True)
    return loss, (dx, dw)


def _head_loss_bwd(pctx, seq_chunk, unroll, res, ct):
    dx, dw = res
    with jax.named_scope(scopes.LM_HEAD):
        return ((dx.astype(jnp.float32) * ct).astype(dx.dtype),
                (dw.astype(jnp.float32) * ct).astype(dw.dtype), None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def prefill_step(params, batch, cfg: ModelConfig, pctx: ParallelCtx, *,
                 key: jax.Array, unroll: bool = False):
    """Serving prefill: consume the prompt, return (first_token, state).

    State leaves are stacked over the scan dim, matching init_decode_state's
    layout, so decode_step can consume them directly.
    """
    x, states = forward_hidden(params, batch, cfg, pctx, key=key,
                               remat=False, collect_state=True,
                               unroll=unroll)
    head = params.get("lm_head")
    if head is None:
        emb = _maybe_gather(pctx, params["embed"], 1, key)
        head_l = emb.T
    else:
        head_l = _maybe_gather(pctx, head, 0, key)
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        head_l.astype(jnp.float32))
    v_local = logits.shape[-1]
    shard = pctx.tp_index()
    best_local = jnp.max(logits, axis=-1)
    best_idx = jnp.argmax(logits, axis=-1) + shard * v_local
    best = pctx.pmax_tp(best_local)
    winner = jnp.where(best_local >= best, best_idx, 0)
    next_tok = pctx.psum_tp(winner).astype(jnp.int32)
    return next_tok[:, None], states


# -------------------------------------------------------------------- decode
def init_decode_state(params_like, cfg: ModelConfig, *, batch: int,
                      max_seq: int, tp: int = 1, seq_shards: int = 1,
                      dtype=jnp.bfloat16):
    """Abstract/zero decode state matching the stage structure.

    KV caches: (n_repeat, B, S_max/seq_shards, kv_local, dh);
    SSM states: conv (n_repeat, B, K-1, d_inner_local) +
    ssm (n_repeat, B, H_local, P, N) fp32. Returned as a list over period
    positions (None-free pytree: attention layers get KVCache, ssm SSMState).
    """
    lay = TpLayout.build(cfg, tp)
    period = _period(cfg)
    n_repeat = cfg.n_layers // period
    s_local = max_seq // seq_shards
    states = []
    for pos in range(period):
        if cfg.is_attn_layer(pos):
            kv_l = lay.kv_local
            shape = (n_repeat, batch, s_local, kv_l, cfg.dh)
            states.append(KVCache(k=jnp.zeros(shape, dtype),
                                  v=jnp.zeros(shape, dtype)))
        else:
            di_l = cfg.d_inner // tp
            h_l = (lay.ssm_heads_pad or cfg.ssm_heads) // tp
            p = cfg.ssm_head_dim
            states.append(SSMState(
                conv=jnp.zeros((n_repeat, batch, cfg.ssm_conv_k - 1, di_l),
                               dtype),
                ssm=jnp.zeros((n_repeat, batch, h_l, p, cfg.ssm_state),
                              jnp.float32)))
    return states


def decode_step(params, state, tokens, pos, cfg: ModelConfig,
                pctx: ParallelCtx, *, key: jax.Array,
                seq_shard_axis=None, unroll: bool = False):
    """One greedy decode step. tokens: (B, 1) -> (next_tokens, new_state)."""
    lay = TpLayout.build(cfg, pctx.tp_size())
    x = embed_tokens(params, tokens, cfg, lay, pctx, key)
    period = _period(cfg)

    def body(carry, inp):
        xc, idx = carry
        stage_params, stage_state = inp
        new_states = []
        for p_ in range(period):
            # see forward_hidden: barrier weights + activations per layer
            xc, lw = jax.lax.optimization_barrier((xc, stage_params[p_]))
            lkey = jax.random.fold_in(key, idx * period + p_)
            xc, ns = _apply_layer(
                xc, lw, cfg, lay, pctx, p_, positions=None,
                key=lkey, cache=stage_state[p_], decode=True, pos=pos,
                seq_shard_axis=seq_shard_axis)
            new_states.append(ns)
        return (xc, idx + 1), new_states

    if unroll:
        n_repeat = jax.tree.leaves(params["stages"])[0].shape[0]
        carry = (x, jnp.zeros((), jnp.int32))
        collected = []
        for r in range(n_repeat):
            inp = jax.tree.map(lambda a: a[r], (params["stages"], state))
            carry, st = body(carry, inp)
            collected.append(st)
        x, _ = carry
        new_state = jax.tree.map(lambda *xs: jnp.stack(xs), *collected)
    else:
        # Cache lives in the CARRY (updated in place with
        # dynamic_update_slice), not in xs/ys: through-scan xs->ys caches
        # would hold TWO full copies live (the decode_32k cells measured
        # +5..11 GiB/device from exactly that; see EXPERIMENTS §Dry-run).
        def carry_body(carry, stage_params):
            xc, idx, cache_all = carry
            stage_state = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                       keepdims=False),
                cache_all)
            (xc, idx2), new_st = body((xc, idx), (stage_params, stage_state))
            cache_all = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, u.astype(a.dtype), idx, 0),
                cache_all, new_st)
            return (xc, idx2, cache_all), None

        (x, _, new_state), _ = jax.lax.scan(
            carry_body, (x, jnp.zeros((), jnp.int32), state),
            params["stages"])
    x = rms_norm(x, params["final_ln"])

    head = params.get("lm_head")
    if head is None:
        emb = _maybe_gather(pctx, params["embed"], 1, key)
        head_l = emb.T
    else:
        head_l = _maybe_gather(pctx, head, 0, key)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        head_l.astype(jnp.float32))[:, 0]   # (B, V_local)
    v_local = logits.shape[-1]
    shard = pctx.tp_index()
    best_local = jnp.max(logits, axis=-1)
    best_idx = jnp.argmax(logits, axis=-1) + shard * v_local
    best = pctx.pmax_tp(best_local)
    # break ties toward the winning shard; exact for continuous logits
    winner = jnp.where(best_local >= best, best_idx, 0)
    next_tok = pctx.psum_tp(winner).astype(jnp.int32)
    return next_tok[:, None], new_state

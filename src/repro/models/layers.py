"""Transformer building blocks: norms, RoPE, GQA attention, gated MLP.

All functions are pure and shape-agnostic: head counts and hidden sizes are
read from the (possibly tensor-parallel-local) weight arrays, so the same
code serves the single-device smoke tests and the sharded production mesh.
Compute dtype is bf16 with fp32 accumulation on matmuls/softmax statistics.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.attention import ops as attention_ops
from repro.obs import scopes

from .parallel import ParallelCtx


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, *,
               eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, *, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, *,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta=theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class KVCache(NamedTuple):
    """Decode-time cache. k/v: (B, S_max, Hkv, dh) — locally sharded either
    on batch (dp) or on sequence (flash-decoding split for tiny batches)."""
    k: jnp.ndarray
    v: jnp.ndarray


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def _map_kv(k: jnp.ndarray, hq: int, kv_map: jnp.ndarray | None) -> jnp.ndarray:
    """Expand KV heads to match the local query heads.

    ``kv_map`` (Hq_local,) gives each local q head its KV head index in the
    local cache — needed when KV heads are replicated across tp shards or
    padded; defaults to the contiguous-group GQA mapping."""
    if kv_map is None:
        return _repeat_kv(k, hq // k.shape[2])
    return jnp.take(k, kv_map, axis=2)


def _chunked_causal_attention(q, k, v, *, scale: float, chunk: int):
    """Flash-style online-softmax attention over KV blocks: the (S, S)
    score matrix is never materialized (prefill_32k feasibility). q, k, v:
    (B, S, H, dh) with H already expanded to the query head count."""
    b, s, h, dh = q.shape
    nq = s // chunk

    qc = q.reshape(b, nq, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def q_block(_, qi_idx):
        qi, i = qi_idx
        # running (out, max, denom) over kv blocks
        o0 = jnp.zeros((b, chunk, h, dh), jnp.float32)
        m0 = jnp.full((b, h, chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)

        def kv_block(carry, j):
            o, m, l = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, axis=1)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                            preferred_element_type=jnp.float32) * scale
            # causal mask between block i (rows) and block j (cols)
            rows = i * chunk + jnp.arange(chunk)
            cols = j * chunk + jnp.arange(chunk)
            mask = rows[:, None] >= cols[None, :]
            sc = jnp.where(mask[None, None], sc, -1e30)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                            vj).astype(jnp.float32)
            o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
            return (o_new, m_new, l_new), None

        (o, m, l), _ = jax.lax.scan(kv_block, (o0, m0, l0),
                                    jnp.arange(nq))
        # blocks j > i contribute nothing (fully masked); scanning all nq
        # keeps the trip count static — XLA skips masked work poorly but
        # correctness is exact. (§Perf iterates on this.)
        out = o / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
        return None, out

    _, outs = jax.lax.scan(q_block, None, (qc, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)
    return out.astype(q.dtype)


def attention_train(x: jnp.ndarray, w: dict, pctx: ParallelCtx, *,
                    positions: jnp.ndarray, rope_theta: float = 10000.0,
                    causal: bool = True,
                    kv_map: jnp.ndarray | None = None,
                    kv_select: jnp.ndarray | None = None,
                    collect_kv: bool = False):
    """Full (causal) attention for train/prefill. x: (B, S, D) replicated
    over tp; w holds local shards: wq (D, Hq_l*dh), wk/wv (D, Hkv_l*dh),
    wo (Hq_l*dh, D). Output is psum'd over tp (row-parallel wo).
    With collect_kv, also returns the post-RoPE KVCache (prefill path)."""
    b, s, _ = x.shape
    dh = w["head_dim"]
    q = jnp.einsum("bsd,dh->bsh", x, w["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, w["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, w["wv"].astype(x.dtype))
    hq = q.shape[-1] // dh
    hkv = k.shape[-1] // dh
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    q = apply_rope(q, positions, theta=rope_theta)
    k = apply_rope(k, positions, theta=rope_theta)
    del hkv
    if kv_select is not None:
        # this tp shard keeps exactly one KV head (the one its q heads use)
        k = jax.lax.dynamic_slice_in_dim(k, kv_select, 1, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v, kv_select, 1, axis=2)
    k_raw, v_raw = k, v                   # (B, S, Hkv_l, dh) pre-expansion
    k = _map_kv(k, hq, kv_map)
    v = _map_kv(v, hq, kv_map)

    scale = 1.0 / math.sqrt(dh)
    attn_chunk = w.get("attn_chunk", 0)
    with jax.named_scope(scopes.ATTENTION):
        if attn_chunk and s > attn_chunk:
            ctx = _chunked_causal_attention(q, k, v, scale=scale,
                                            chunk=attn_chunk)
        else:
            # the fused kernel on a TPU where it applies, else dense
            ctx = attention_ops.attention(q, k, v, scale=scale,
                                          causal=causal)
    ctx = ctx.reshape(b, s, hq * dh)
    out = jnp.einsum("bsh,hd->bsd", ctx, w["wo"].astype(x.dtype))
    out = pctx.reduce_output(out)   # psum, or psum_scatter(seq) under SP
    if collect_kv:
        return out, KVCache(k=k_raw, v=v_raw)
    return out


def attention_decode(x: jnp.ndarray, w: dict, cache: KVCache,
                     pctx: ParallelCtx, *, pos: jnp.ndarray,
                     rope_theta: float = 10000.0,
                     seq_shard_axis=None,
                     kv_map: jnp.ndarray | None = None,
                     kv_select: jnp.ndarray | None = None
                     ) -> tuple[jnp.ndarray, KVCache]:
    """One-token decode against a KV cache. x: (B, 1, D).

    ``seq_shard_axis``: when set (tiny global batch, e.g. long_500k), the
    cache's sequence dim is sharded over that mesh axis and attention is
    merged with flash-decoding-style partial-softmax statistics (psum of
    renormalized numerators / denominators).
    """
    b, _, _ = x.shape
    dh = w["head_dim"]
    q = jnp.einsum("bsd,dh->bsh", x, w["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dh->bsh", x, w["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dh->bsh", x, w["wv"].astype(x.dtype))
    hq = q.shape[-1] // dh
    hkv = k_new.shape[-1] // dh
    q = q.reshape(b, 1, hq, dh)
    k_new = k_new.reshape(b, 1, hkv, dh)
    v_new = v_new.reshape(b, 1, hkv, dh)
    posb = jnp.broadcast_to(pos.reshape(1, 1), (b, 1))
    q = apply_rope(q, posb, theta=rope_theta)
    k_new = apply_rope(k_new, posb, theta=rope_theta)
    if kv_select is not None:
        k_new = jax.lax.dynamic_slice_in_dim(k_new, kv_select, 1, axis=2)
        v_new = jax.lax.dynamic_slice_in_dim(v_new, kv_select, 1, axis=2)

    s_local = cache.k.shape[1]
    if seq_shard_axis is None:
        k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                         (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                         (0, pos, 0, 0))
        valid = jnp.arange(s_local) <= pos
        new_cache = KVCache(k=k, v=v)
    else:
        # sequence-sharded cache: only the owning shard writes the new token.
        # seq_shard_axis may be a tuple of mesh axes (e.g. ('pod', 'data')):
        # linearize with the first axis major, matching P(('pod','data')).
        axes = ((seq_shard_axis,) if isinstance(seq_shard_axis, str)
                else tuple(seq_shard_axis))
        shard = jnp.zeros((), jnp.int32)
        for ax in axes:
            shard = shard * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        start = shard * s_local
        local_pos = jnp.clip(pos - start, 0, s_local - 1)
        owns = (pos >= start) & (pos < start + s_local)
        k_upd = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (0, local_pos, 0, 0))
        v_upd = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (0, local_pos, 0, 0))
        k = jnp.where(owns, k_upd, cache.k)
        v = jnp.where(owns, v_upd, cache.v)
        valid = (jnp.arange(s_local) + start) <= pos
        new_cache = KVCache(k=k, v=v)

    del hkv
    kk = _map_kv(k, hq, kv_map)
    vv = _map_kv(v, hq, kv_map)
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)

    if seq_shard_axis is None:
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    else:
        # partial softmax merge across sequence shards
        m_local = jnp.max(logits, axis=-1, keepdims=True)
        m = jax.lax.pmax(m_local, seq_shard_axis)
        p = jnp.exp(logits - m)
        num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(jnp.float32),
                         vv.astype(jnp.float32))
        den = jnp.sum(p, axis=-1)                     # (b, h, 1)
        num = jax.lax.psum(num, seq_shard_axis)
        den = jax.lax.psum(den, seq_shard_axis)
        ctx = (num / den.transpose(0, 2, 1)[..., None]).astype(x.dtype)
    ctx = ctx.reshape(b, 1, hq * dh)
    out = jnp.einsum("bsh,hd->bsd", ctx, w["wo"].astype(x.dtype))
    return pctx.psum_tp(out), new_cache


def gated_mlp(x: jnp.ndarray, w: dict, pctx: ParallelCtx, *,
              activation: str = "silu") -> jnp.ndarray:
    """SwiGLU (or GELU-gated) MLP. w_gate/w_up column-parallel,
    w_down row-parallel + psum."""
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    g = jnp.einsum("bsd,df->bsf", x, w["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, w["w_up"].astype(x.dtype))
    h = act(g) * u
    out = jnp.einsum("bsf,fd->bsd", h, w["w_down"].astype(x.dtype))
    return pctx.reduce_output(out)

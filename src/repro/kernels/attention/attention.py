"""Pallas TPU kernel: fused causal attention with an online softmax.

The dense path writes the (B, H, S, S) f32 scores, the probabilities and
their gradient to HBM; at 64 x 12 x 1024 x 1024 that traffic, not the
MXU, sets its time. Here each program holds one block of queries and one
block of keys in VMEM, and no S x S array leaves the chip:

* forward: grid (B, head group, q block, kv block). Scores and the
  running max and sum are f32; P is cast to the operands' dtype for P·V;
  the output is normalized once, after the last kv block of its row
  block. Only the output and the per-row logsumexp are saved.
* backward: grid (B, head group, kv block, q block), one kernel for dQ,
  dK and dV. It recomputes P^T per block from the logsumexp, forms dS in
  f32 (scaled by ``scale``, as the dense gradient is) and casts it to the
  operands' dtype for the dQ and dK products. dK and dV accumulate over
  the q blocks of their kv block; dQ accumulates in an f32 VMEM buffer
  over all kv blocks of its batch row and head group.

Queries and keys come in blocks of one size, so only the block on the
diagonal is masked (an additive bias), and the blocks above it are
skipped: their index maps repeat the diagonal's block, so a skipped step
neither computes nor fetches.

Layout. q and k are read sequence-minor, (B, H, dh, S): the layout XLA
gives RoPE's output, so they reach the kernel without a copy, and dQ and
dK leave it the same way. v, the output and dO stay as the projections
use them, (B, S, H * dh), 128 lanes at a time: for dh = 64 a lane group
holds two heads, each head's products land in its own lanes, and dP^T
takes v with the other head's lanes zeroed (the MXU contracts 128 lanes
either way). The per-row logsumexp and the backward's rowsum(O * dO) are
(B, H, 8, S) f32, one row per head over 8 sublanes, which the transposed
score blocks of the backward take as lane-dense rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
HEAD_DIMS = (64, 128)          # heads per 128-lane group: 2 or 1
MASK = -1e30                   # the dense path's masked score

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
# batch and head group independent; the two block axes carry accumulators
_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))


def _head_of_lane(dh: int) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // dh


def _only_head(x, heads, h: int, n_heads: int):
    """x with every lane outside head ``h`` zeroed (x itself for one
    head per group)."""
    if n_heads == 1:
        return x
    return jnp.where(heads == h, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _into_head(acc, upd, heads, h: int, n_heads: int):
    """acc with head ``h``'s lanes taken from upd."""
    return upd if n_heads == 1 else jnp.where(heads == h, upd, acc)


def _tile_lanes(x, width: int):
    """(rows, 128) with equal lanes -> (rows, width)."""
    return jnp.tile(x, (1, width // LANES))


def _split_scale(scale: float) -> tuple[float, float]:
    """(factor applied to q and k, factor applied to the f32 scores and
    dS). A power of two such as 1/8 (dh = 64) is exact in any float
    format, so it moves onto the (dh, blk) q and k blocks and off the
    (blk, blk) ones; any other scale stays on the f32 blocks, as the
    dense path applies it."""
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _times(x, factor: float):
    if factor == 1.0:
        return x
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def _causal_bias(blk: int) -> jnp.ndarray:
    """Additive mask of a block on the diagonal: 0 where the key is not
    after the query, MASK elsewhere (s + MASK rounds to MASK, as the
    dense path's select gives)."""
    keep = jnp.tril(jnp.ones((blk, blk), jnp.bool_))
    return jnp.where(keep, 0.0, MASK).astype(jnp.float32)



# ------------------------------------------------------------------ forward
def _fwd_kernel(qt_ref, kt_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale: float, dh: int):
    i, j = pl.program_id(2), pl.program_id(3)   # query block, key block
    n_heads = LANES // dh
    q_scale, s_scale = _split_scale(scale)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, MASK, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def block(diagonal: bool):
        v = v_ref[...]
        blk = v.shape[0]
        heads = _head_of_lane(dh)
        acc = acc_sc[...]
        for h in range(n_heads):
            q = _times(qt_ref[h], q_scale).T                  # (blk, dh)
            s = jnp.dot(q, kt_ref[h], preferred_element_type=jnp.float32)
            if s_scale != 1.0:
                s = s * s_scale
            if diagonal:
                s = s + bias_ref[...]
            m_prev, l_prev = m_sc[h], l_sc[h]                 # (blk, 128)
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - _tile_lanes(m_next, blk))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[h] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
            m_sc[h] = m_next
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            acc = _into_head(acc, alpha * acc + pv, heads, h, n_heads)
        acc_sc[...] = acc

    @pl.when(j < i)
    def _below():
        block(diagonal=False)

    @pl.when(j == i)             # the row block's last key block
    def _diagonal_and_end():
        block(diagonal=True)
        heads = _head_of_lane(dh)
        denom = l_sc[0]
        for h in range(1, n_heads):
            denom = _into_head(denom, l_sc[h], heads, h, n_heads)
        o_ref[...] = (acc_sc[...] / denom).astype(o_ref.dtype)
        for h in range(n_heads):
            lse = m_sc[h] + jnp.log(l_sc[h])                  # (blk, 128)
            lse_ref[h] = lse.T[:SUBLANES]


def _fwd_call(qt, kt, v, *, scale, dh, blk, interpret):
    b, s, width = v.shape
    n_heads, groups, n = LANES // dh, width // LANES, s // blk
    # key blocks after the diagonal repeat the diagonal's: never fetched
    last = lambda i, j: jnp.minimum(i, j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, dh=dh),
        grid=(b, groups, n, n),
        in_specs=[
            pl.BlockSpec((None, n_heads, dh, blk),
                         lambda b_, g, i, j: (b_, g, 0, i)),
            pl.BlockSpec((None, n_heads, dh, blk),
                         lambda b_, g, i, j: (b_, g, 0, last(i, j))),
            pl.BlockSpec((None, blk, LANES),
                         lambda b_, g, i, j: (b_, last(i, j), g)),
            pl.BlockSpec((blk, blk), lambda b_, g, i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, blk, LANES), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, n_heads, SUBLANES, blk),
                         lambda b_, g, i, j: (b_, g, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, groups * n_heads, SUBLANES, s),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_heads, blk, LANES), jnp.float32),
                        pltpu.VMEM((n_heads, blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, LANES), jnp.float32)],
        compiler_params=_GRID,
        interpret=interpret,
        name="causal_attention_fwd",
    )(qt, kt, v, _causal_bias(blk))


# ----------------------------------------------------------------- backward
def _bwd_kernel(qt_ref, kt_ref, v_ref, do_ref, lse_ref, di_ref, bias_ref,
                dqt_ref, dkt_ref, dv_ref, dqt_sc, dkt_sc, dv_sc, *,
                scale: float, dh: int):
    j, i = pl.program_id(2), pl.program_id(3)   # key block, query block
    n = pl.num_programs(3)
    n_heads = LANES // dh
    q_scale, s_scale = _split_scale(scale)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init_dq():
        dqt_sc[...] = jnp.zeros(dqt_sc.shape, jnp.float32)

    @pl.when(i == 0)
    def _init_dkv():
        dkt_sc[...] = jnp.zeros(dkt_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def block(diagonal: bool):
        v, do = v_ref[...], do_ref[...]
        blk = v.shape[0]
        heads = _head_of_lane(dh)
        queries = pl.ds(pl.multiple_of(i * blk, blk), blk)
        dv = jnp.zeros((blk, LANES), jnp.float32)
        for h in range(n_heads):
            qt = _times(qt_ref[h], q_scale)                   # (dh, blk)
            kt = kt_ref[h]
            # transposed blocks: rows are keys, columns queries
            st = jnp.dot(kt.T, qt, preferred_element_type=jnp.float32)
            if s_scale != 1.0:
                st = st * s_scale
            if diagonal:
                st = st + bias_ref[...]
            pt = jnp.exp(st - lse_ref[h, 0:1, :])
            dpt = lax.dot_general(_only_head(v, heads, h, n_heads), do, _NT,
                                  preferred_element_type=jnp.float32)
            dst = pt * (dpt - di_ref[h, 0:1, :])
            if s_scale != 1.0:
                dst = dst * s_scale
            dst = dst.astype(qt.dtype)
            dv = _into_head(dv, jnp.dot(pt.astype(do.dtype), do,
                                        preferred_element_type=jnp.float32),
                            heads, h, n_heads)
            dkt_sc[h] += lax.dot_general(qt, dst, _NT,
                                         preferred_element_type=jnp.float32)
            dqt_sc[h, :, queries] += jnp.dot(
                _times(kt, q_scale), dst, preferred_element_type=jnp.float32)
        dv_sc[...] += dv

    @pl.when(i > j)
    def _below():
        block(diagonal=False)

    @pl.when(i == j)
    def _diagonal():
        block(diagonal=True)

    @pl.when(i == n - 1)
    def _end_dkv():
        dkt_ref[...] = dkt_sc[...].astype(dkt_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == n - 1, i == n - 1))
    def _end_dq():
        dqt_ref[...] = dqt_sc[...].astype(dqt_ref.dtype)


def _bwd_call(qt, kt, v, do, lse, di, *, scale, dh, blk, interpret):
    b, s, width = v.shape
    n_heads, groups, n = LANES // dh, width // LANES, s // blk
    # query blocks before the diagonal repeat the diagonal's: never fetched
    first = lambda j, i: jnp.maximum(i, j)
    qt_spec = pl.BlockSpec((None, n_heads, dh, blk),
                           lambda b_, g, j, i: (b_, g, 0, first(j, i)))
    kt_spec = pl.BlockSpec((None, n_heads, dh, blk),
                           lambda b_, g, j, i: (b_, g, 0, j))
    row_spec = pl.BlockSpec((None, blk, LANES),
                            lambda b_, g, j, i: (b_, first(j, i), g))
    v_spec = pl.BlockSpec((None, blk, LANES), lambda b_, g, j, i: (b_, j, g))
    stat_spec = pl.BlockSpec((None, n_heads, SUBLANES, blk),
                             lambda b_, g, j, i: (b_, g, 0, first(j, i)))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, dh=dh),
        grid=(b, groups, n, n),
        in_specs=[qt_spec, kt_spec, v_spec, row_spec, stat_spec, stat_spec,
                  pl.BlockSpec((blk, blk), lambda b_, g, j, i: (0, 0))],
        out_specs=[pl.BlockSpec((None, n_heads, dh, s),
                                lambda b_, g, j, i: (b_, g, 0, 0)),
                   kt_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((n_heads, dh, s), jnp.float32),
                        pltpu.VMEM((n_heads, dh, blk), jnp.float32),
                        pltpu.VMEM((blk, LANES), jnp.float32)],
        compiler_params=_GRID,
        interpret=interpret,
        name="causal_attention_bwd",
    )(qt, kt, v, do, lse, di, _causal_bias(blk).T)


# ---------------------------------------------------------------- autodiff
def _packed(x):
    b, s, h, dh = x.shape
    return x.reshape(b, s, h * dh)


def _seq_minor(x):
    """(B, S, H, dh) -> (B, H, dh, S)."""
    return x.transpose(0, 2, 3, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention_pallas(q, k, v, scale: float, blk: int,
                            interpret: bool):
    """Causal softmax(q k^T * scale) v. q, k, v: (B, S, H, dh) with H the
    query heads, dh in ``HEAD_DIMS``, H * dh a multiple of 128 and S a
    multiple of the block ``blk`` -> (B, S, H, dh)."""
    return _forward(q, k, v, scale, blk, interpret)[0]


def _forward(q, k, v, scale, blk, interpret):
    o, lse = _fwd_call(_seq_minor(q), _seq_minor(k), _packed(v),
                       scale=scale, dh=q.shape[-1], blk=blk,
                       interpret=interpret)
    return o.reshape(q.shape), lse


def _vjp_fwd(q, k, v, scale, blk, interpret):
    o, lse = _forward(q, k, v, scale, blk, interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(scale, blk, interpret, res, do):
    q, k, v, o, lse = res
    b, s, h, dh = q.shape
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di.transpose(0, 2, 1)[:, :, None, :],
                          (b, h, SUBLANES, s))
    dqt, dkt, dv = _bwd_call(_seq_minor(q), _seq_minor(k), _packed(v),
                             _packed(do), lse, di, scale=scale, dh=dh,
                             blk=blk, interpret=interpret)
    return (dqt.transpose(0, 3, 1, 2), dkt.transpose(0, 3, 1, 2),
            dv.reshape(v.shape))


causal_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)

"""Plain float32 reference of a dense-family training step.

Forward, loss, gradient, the data-parallel mean and AdamW, in plain
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and takes its weights from :mod:`chipbench.inputs`.
The block follows the configuration file: pre-norm RMSNorm, rotate-half
RoPE over the whole head, causal softmax attention, a gated MLP
(``act(x W_gate) * (x W_up) W_down``), a final RMSNorm and a tied or untied
head; the loss is the mean next-token cross-entropy over every token.
Weights are stored in the configuration's ``param_dtype`` between steps
and computed on in float32, as the configuration states.

It runs in blocks of rows, so that the logits and attention scores of a
full-size cell fit beside the weights; Adam's moments live on the host
between steps and visit the device one leaf at a time.

``lowp=True`` is the control: every matrix product, forward and backward,
takes fp8 (e4m3, one scale per tensor) operands -- the step below the bf16
that the configuration states. It must fail the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs
from chipbench.compare import change_norms, leaf_norms

F32 = jnp.float32
FP8_MAX = 448.0                          # largest finite float8_e4m3fn
ROW_BLOCK_BYTES = 512 << 20              # logits or scores of one row block


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def _q_fwd(x):
    """fp8 rounding of a forward operand (straight-through backward)."""
    return _fp8(x)


_q_fwd.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(x):
    """Identity forward; fp8 rounding of the cotangent."""
    return x


_q_bwd.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def _mm(spec: str, a, b, lowp: bool):
    if lowp:
        return _q_bwd(jnp.einsum(spec, _q_fwd(a), _q_fwd(b)))
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


_ACT = {"gelu": _gelu_tanh, "silu": lambda x: x * jax.nn.sigmoid(x)}


def _rope(x, theta):
    """x: (B, S, H, dh); rotate-half over the whole head."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block_loss(params, tokens, labels, model: dict, lowp: bool = False):
    """Mean next-token cross-entropy of a block of rows (float32)."""
    b, s = tokens.shape
    h_q, h_kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps, act = model["norm_eps"], _ACT[model["activation"]]
    mm = lambda spec, a, w: _mm(spec, a, w, lowp)
    x = params["embed"][tokens]                               # (B, S, D)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        h = _rms(x, w["ln1"], eps)
        q = mm("bsd,de->bse", h, w["wq"]).reshape(b, s, h_q, dh)
        k = mm("bsd,de->bse", h, w["wk"]).reshape(b, s, h_kv, dh)
        v = mm("bsd,de->bse", h, w["wv"]).reshape(b, s, h_kv, dh)
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
        rep = h_q // h_kv
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        ctx = mm("bhqk,bkhd->bqhd", p, v).reshape(b, s, h_q * dh)
        x = x + mm("bse,ed->bsd", ctx, w["wo"])
        h2 = _rms(x, w["ln2"], eps)
        ff = act(mm("bsd,df->bsf", h2, w["w_gate"])) \
            * mm("bsd,df->bsf", h2, w["w_up"])
        return x + mm("bsf,fd->bsd", ff, w["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["stages"][0])
    x = _rms(x, params["final_ln"], eps)
    head = (params["embed"].T if model["tie_embeddings"]
            else params["lm_head"])
    logits = mm("bsd,dv->bsv", x, head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def row_block(model: dict, rows: int, seq: int) -> int:
    """Largest divisor of ``rows`` whose logits and scores each stay under
    ``ROW_BLOCK_BYTES``."""
    per_row = 4 * seq * max(model["vocab_size"], model["n_heads"] * seq)
    cap = max(1, ROW_BLOCK_BYTES // per_row)
    return max(d for d in range(1, rows + 1) if rows % d == 0 and d <= cap)


def mean_grad_fn(model: dict, rows: int, seq: int, chips: int, *,
                 lowp: bool = False, fault: str | None = None):
    """jitted ``(params, batch) -> (loss, grads)``: the plain DP mean.

    The global batch splits into ``chips`` shards of consecutive rows, as
    the program's batch sharding does. Each shard's gradient is the mean
    over its rows (taken in row blocks) and the DP mean is the mean over
    shards. The loss is the mean over all rows, as the program reports it.

    ``fault`` plants a fault of the program in the reference put in its
    place: ``half_batch`` keeps the first half of each shard's rows;
    ``no_exchange`` returns shard 0's gradient as the synced one.
    """
    shard = rows // chips
    keep = shard // 2 if fault == "half_batch" else shard
    rb = row_block(model, keep, seq)
    vg = jax.value_and_grad(
        lambda p, t, y: block_loss(p, t, y, model, lowp))

    def shard_mean(params, t_s, y_s):
        """Mean loss and gradient of one shard's (blocks, rb, seq) rows."""
        def acc(carry, ty):
            return jax.tree.map(jnp.add, carry, vg(params, *ty)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(acc, zero, (t_s, y_s))
        n = t_s.shape[0]
        return l / n, jax.tree.map(lambda a: a / n, g)

    def fn(stored, batch):
        params = jax.tree.map(lambda x: x.astype(F32), stored)

        def blocks(x):
            return x.reshape(chips, shard, seq)[:, :keep].reshape(
                chips, keep // rb, rb, seq)

        toks, labs = blocks(batch["tokens"]), blocks(batch["labels"])
        if fault == "no_exchange":
            loss = sum(shard_mean(params, toks[c], labs[c])[0]
                       for c in range(chips)) / chips
            return loss, shard_mean(params, toks[0], labs[0])[1]

        def add_shard(carry, ty):
            return jax.tree.map(jnp.add, carry,
                                shard_mean(params, *ty)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(add_shard, zero, (toks, labs))
        return l / chips, jax.tree.map(lambda a: a / chips, g)

    return jax.jit(fn)


def _adamw_leaf(opt: dict):
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]

    def upd(p, g, m, v, scale, t, lr):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        pf = p.astype(F32)
        pf = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
        return pf.astype(p.dtype), m, v

    return jax.jit(upd)


def run(model: dict, opt: dict, traffic: dict, seed: int, chips: int, *,
        steps: int = 3, lowp: bool = False,
        fault: str | None = None) -> dict:
    """Train ``steps`` steps from the seed's weights on the seed's batches.

    Returns ``losses`` (one per step), ``grad`` (per-leaf norms of the
    first step's gradient as AdamW receives it, after the global-norm
    clip), ``raw_grad`` (the same before the clip) and ``change`` (per-leaf
    norms of the weights' change after ``steps`` steps).
    """
    block = (model["norm"], model["mlp"], model["positions"])
    if block != ("rms", "gated", "rope"):
        raise ValueError(f"the reference implements the rms/gated/rope "
                         f"block, not {block}")
    store = jnp.dtype(model["param_dtype"])
    rows, seq = traffic["global_batch"], traffic["seq_len"]
    with jax.default_matmul_precision("highest"):
        # weights live in their stored dtype between steps (a convert to
        # bf16 and back inside one program may be folded away by XLA)
        params = inputs.make_params(seed, model, store)
        make_batch = inputs.batch_maker(traffic, model["vocab_size"])
        grad_fn = mean_grad_fn(model, rows, seq, chips, lowp=lowp,
                               fault=fault)
        upd = _adamw_leaf(opt)
        flat, tdef = jax.tree.flatten(params)
        moments = [(np.zeros(x.shape, np.float32),) * 2 for x in flat]
        data_key = jax.random.fold_in(inputs.seed_key(seed), 1)
        losses, out = [], {}
        for step in range(steps):
            loss, grads = grad_fn(params, make_batch(data_key, step))
            losses.append(float(loss))
            gflat = jax.tree.leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gflat))
            scale = jnp.minimum(1.0, opt["grad_clip"]
                                / jnp.maximum(gnorm, 1e-9))
            if step == 0:
                out["raw_grad"] = leaf_norms(grads)
                out["grad"] = leaf_norms(jax.tree.map(lambda g: g * scale,
                                                      grads))
            del grads
            new = []
            for i, (p, g) in enumerate(zip(jax.tree.leaves(params), gflat)):
                m, v = moments[i]
                p, m, v = upd(p, g, m, v, scale, float(step + 1), opt["lr"])
                moments[i] = (np.asarray(m), np.asarray(v))
                new.append(p)
            del gflat
            params = jax.tree.unflatten(tdef, new)
        out["change"] = change_norms(params, seed, model)
    out["losses"] = losses
    return out

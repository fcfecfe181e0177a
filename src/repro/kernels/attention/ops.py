"""Public wrapper and dispatch for causal attention in the train step.

``attention(q, k, v, scale=..., causal=...)`` runs the fused Pallas kernel
where it applies and the dense oracle (``ref.attention_ref``) everywhere
else. The choice follows from what the call can see, with no setting of
its own: the kernel runs when ``kernels.runtime.impl()`` is ``mosaic`` (a
TPU backend), the call is causal, the operands are bf16, the head size is
one the kernel takes, the heads fill whole 128-lane groups, and the
sequence is a multiple of a block. Under ``jnp`` and ``interpret`` the
dense path runs as it always has; tests reach the interpreted kernel by
passing ``interpret=True``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import runtime

from .attention import HEAD_DIMS, LANES, causal_attention_pallas
from .ref import attention_ref

# largest first: at S = 1024, dh = 64 on a v5e the forward and backward
# took a quarter less time with 512-row blocks than with 256-row ones
BLOCKS = (512, 256, 128)


def block_size(s: int) -> int | None:
    """Query and key block of the kernel for sequence length ``s``: the
    largest of ``BLOCKS`` that divides it, or None."""
    return next((blk for blk in BLOCKS if s % blk == 0), None)


def kernel_applies(shape, dtype, *, causal: bool) -> bool:
    """Whether the fused kernel takes q of this (B, S, H, dh) shape."""
    _, s, h, dh = shape
    return (causal and dtype == jnp.bfloat16 and dh in HEAD_DIMS
            and (h * dh) % LANES == 0 and block_size(s) is not None)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              scale: float, causal: bool = True,
              interpret: bool | None = None) -> jnp.ndarray:
    """softmax(q k^T * scale) v over (B, S, H, dh) operands, H already
    expanded to the query heads.

    ``interpret=None`` dispatches as the module docstring says; True or
    False runs the kernel (interpreted or compiled) and raises where it
    does not apply.
    """
    applies = kernel_applies(q.shape, q.dtype, causal=causal)
    if interpret is None:
        if not (applies and runtime.impl() == "mosaic"):
            return attention_ref(q, k, v, scale=scale, causal=causal)
        interpret = False
    elif not applies:
        raise ValueError(f"the fused attention kernel does not take q "
                         f"{q.shape} {q.dtype} causal={causal}")
    blk = block_size(q.shape[1])
    return causal_attention_pallas(q, k, v, scale, blk, interpret)

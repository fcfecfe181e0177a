"""stablelm-1.6b [dense]: Stable LM 2 1.6B.

Sources: hf:stabilityai/stablelm-2-1_6b ``config.json``; Bellagente et al.
2024, "Stable LM 2 1.6B Technical Report", arXiv:2402.17834.

Checked against the published numbers: 24 layers, hidden 2048, 32 heads of
64 and 32 KV heads, a SiLU gated MLP of width 5632, a vocabulary of 100,352
with untied input and output embeddings (``tie_word_embeddings: false``),
RoPE theta 10000, context 4096. 1.644 B parameters in this block.

The repo's dense block departs from the published one in three places:
RMSNorm in place of LayerNorm with bias (eps 1e-5), no biases on Q, K and
V (``use_qkv_bias``, about 0.3 M parameters), and RoPE over the whole head
in place of its first 25% (``partial_rotary_factor`` 0.25).
"""
import jax.numpy as jnp
from .base import ModelConfig

CONFIG = ModelConfig(
    name="stablelm-1.6b", family="dense",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32, d_ff=5632,
    vocab_size=100352, head_dim=64, tie_embeddings=False,
    source="hf:stabilityai/stablelm-2-1_6b",
)

SMOKE = ModelConfig(
    name="stablelm-smoke", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=512, head_dim=16, tie_embeddings=False,
    param_dtype=jnp.float32,
)

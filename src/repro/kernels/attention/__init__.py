from .attention import causal_attention_pallas
from .ops import attention, block_size, kernel_applies
from .ref import attention_ref

__all__ = ["attention", "attention_ref", "block_size",
           "causal_attention_pallas", "kernel_applies"]

"""Device time per traced step of the LM head and loss, on device 0, in
ms: the operations with an ``lm_head`` component on their ``tf_op`` path,
which the program opens around the head's logits and the chunked
cross-entropy of ``lm_loss``, forward, recomputation and gradient. Part of
``fwd_bwd_ms``; a program without the scope reads nothing
(``chipbench/component_ms.py``)."""
from chipbench import component_ms


def read(run):
    return component_ms.read(run, "lm_head")

"""Device time per traced step of the token embedding, on device 0, in ms:
the operations with an ``embed`` component on their ``tf_op`` path, which
the program opens around ``embed_tokens``'s lookup (and so around its
gradient's scatter-add). Part of ``fwd_bwd_ms``; a program without the
scope reads nothing (``chipbench/component_ms.py``)."""
from chipbench import component_ms


def read(run):
    return component_ms.read(run, "embed")

"""Device time per traced step of the attention core, on device 0, in ms:
the operations whose ``tf_op`` path (``op_scopes.read_tf_ops``) has an
``attention`` component, bare or wrapped as in
``transpose(jvp(attention))``. The program opens that scope around the
core of ``attention_train`` (scores, mask, softmax, P·V or the fused
kernel, and their gradients), inside ``fwd_bwd``, so this time is part of
``fwd_bwd_ms``.

Standard error also gets the share of that time spent in the fused
kernel's own custom calls (``causal_attention_fwd`` and
``causal_attention_bwd``): 0% on the dense path, most of it with the
kernel. A program without the scope reads nothing.
"""
import re
import sys

from chipbench import op_scopes

_COMPONENT = re.compile(r"(^|\()attention(\)|$)")
_KERNEL = re.compile(r"causal_attention_(fwd|bwd)")


def in_attention(path: str | None) -> bool:
    return any(_COMPONENT.search(c) for c in (path or "").split("/"))


def is_kernel(text: str) -> bool:
    return bool(_KERNEL.search(text.split(" = ", 1)[0]))


def read(run):
    red = run.trace
    path = run.window.trace_file
    if red is None or path is None:
        return None
    tf_ops = op_scopes.read_tf_ops(path)
    texts = {t for t, paths in tf_ops.items()
             if any(in_attention(p) for p in paths)}
    total = kernel = 0
    for e in red.devices[0]:
        if e.name in texts:
            total += e.end - e.start
            if is_kernel(e.name):
                kernel += e.end - e.start
    if not total:
        return None
    ms = total * 1e-6 / red.n_steps
    print(f"chipbench: attention_ms {ms!r} per step, of it in the fused "
          f"kernel's calls {kernel * 1e-6 / red.n_steps!r} "
          f"({100 * kernel / total!r}%)", file=sys.stderr)
    return ms

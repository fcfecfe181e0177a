"""Device time per traced step of the operations under one scope that lies
inside a layer of the step (``embed``, ``lm_head``), on device 0, in ms.

An operation counts where its ``tf_op`` path (``op_scopes.read_tf_ops``)
has a component that names the scope, bare or wrapped as in
``transpose(jvp(lm_head))``. Such scopes lie inside ``fwd_bwd``, and
``op_scopes`` does not know them, so this time is part of ``fwd_bwd_ms``.
A program without the scope reads nothing.
"""
from __future__ import annotations

import re

from chipbench import op_scopes


def component(scope: str):
    """``inside(path)``: whether an ``op_name`` path has a component that
    names ``scope``."""
    pat = re.compile(rf"(^|\(){re.escape(scope)}(\)|$)")
    return lambda path: any(pat.search(c) for c in (path or "").split("/"))


def read(run, scope: str) -> float | None:
    red = run.trace
    path = run.window.trace_file
    if red is None or path is None:
        return None
    inside = component(scope)
    texts = {t for t, paths in op_scopes.read_tf_ops(path).items()
             if any(inside(p) for p in paths)}
    total = sum(e.end - e.start for e in red.devices[0] if e.name in texts)
    if not total:
        return None
    return total * 1e-6 / red.n_steps

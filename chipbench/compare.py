"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit kept in the cell's file:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the checked steps.
* ``grad_gap``: the first step's gradient as AdamW receives it (the
  program's is read back from its first moment, m = (1 - beta1) g), by the
  worst leaf: the gap between the two per-leaf norms over the reference's
  norm of that leaf or of the median leaf, whichever is larger.
* ``change_gap``: the weights' change after the checked steps, by the
  worst leaf in the same measure. Leaves whose reference gradient is under
  ``NOUGHT`` of the median leaf's move under Adam by round-off alone and
  are left out.

A step that returns non-finite loss, or that the program's safeguard
skipped, is counted under ``failed_steps`` with the limit 0.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from chipbench import inputs

NOUGHT = 1e-3


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict[str, float]:
    """Per-leaf L2 norms (float32 on the device), keyed by leaf path."""
    flat = jax.tree_util.tree_flatten_with_path(_norms(tree))[0]
    return {inputs.path_name(p): float(v) for p, v in flat}


def change_norms(params, seed: int, model: dict) -> dict[str, float]:
    """Per-leaf norms of ``params`` minus the seed's initial weights (made
    again here, in the configuration's ``param_dtype``)."""
    p0 = inputs.make_params(seed, model, jnp.dtype(model["param_dtype"]))
    return leaf_norms(jax.tree.map(
        lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
        params, p0))


def _worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    names = [n for n in ref if keep is None or n in keep]
    median = statistics.median(ref[n] for n in names)
    return max(abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
               for n in names)


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """The compared numbers from the program's and the reference's runs
    (both as returned by ``harness.checked_steps`` / ``reference.run``)."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError(f"leaf sets differ: {sorted(prog['grad'])} vs "
                         f"{sorted(ref['grad'])}")
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    median_raw = statistics.median(ref["raw_grad"].values())
    moving = {n for n, g in ref["raw_grad"].items()
              if g >= NOUGHT * median_raw}
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"]),
        "change_gap": _worst_leaf(prog["change"], ref["change"], moving),
        "failed_steps": float(prog.get("failed_steps", 0)),
    }


def judge(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` of every number that has a limit, and
    whether each is within it (a NaN never is). Numbers without a limit
    are returned under ``info``: a cell compares only numbers that
    separate sound runs from the control and the faults."""
    checks = {n: {"value": v, "limit": limits[n]}
              for n, v in numbers.items() if n in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(ok), "checks": checks,
            "info": {n: v for n, v in numbers.items() if n not in limits}}


def leaf_table(prog: dict, ref: dict) -> dict:
    """Per leaf: the program's and the reference's gradient and change
    norms and the reference's gradient before the clip."""
    return {n: {"grad": [prog["grad"][n], ref["grad"][n]],
                "change": [prog["change"][n], ref["change"][n]],
                "raw_grad": ref["raw_grad"][n]} for n in sorted(ref["grad"])}

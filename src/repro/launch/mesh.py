"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips (TPU v5e pod);
multi-pod: 2 pods = 512 chips with a leading 'pod' axis (the hierarchical-
TAR group axis, DESIGN §2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(dp: int = 1, tp: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert dp * tp <= n, (dp, tp, n)
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

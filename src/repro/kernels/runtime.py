"""Kernel dispatch: the one place that decides how a codec kernel runs.

:func:`impl` names the implementation every kernel wrapper (each package's
``ops.py``) runs:

  ``mosaic``     the Pallas kernel, compiled by Mosaic (TPU only; asking
                 for it on another backend raises at once, not from inside
                 a kernel's lowering).
  ``interpret``  the Pallas kernel in the Pallas interpreter (any backend).
  ``jnp``        the kernel's ``ref.py`` form (same math, plain XLA).

It follows from ``jax.default_backend()``: ``mosaic`` on a TPU, ``jnp``
everywhere else. :func:`impl_scope` overrides it for tests and kernel
benchmarks, which run the kernels in the interpreter on the CPU or lower
Mosaic for a described TPU.

The wrappers read :func:`impl` *outside* their ``jax.jit`` and pass it in
as a static argument, so the choice is part of every kernel's jit cache
key. A caller that jits a larger step around the wrappers fixes the choice
at its own trace time.
"""
from __future__ import annotations

import contextlib
import logging

import jax

IMPLS = ("mosaic", "interpret", "jnp")

logger = logging.getLogger("repro.kernels.runtime")

_override: str | None = None
_logged: str | None = None        # the last implementation logged


def _mosaic_available() -> bool:
    """Whether this process can compile Pallas TPU kernels (Mosaic)."""
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def impl_scope(name: str):
    """Run the kernel wrappers as ``name`` (one of :data:`IMPLS`) inside
    the block; tests and kernel benchmarks only."""
    global _override
    if name not in IMPLS:
        raise ValueError(f"unknown kernel implementation {name!r}; "
                         f"expected one of {IMPLS}")
    prev, _override = _override, name
    try:
        yield
    finally:
        _override = prev


def impl() -> str:
    """The implementation the kernel wrappers run now (see module doc)."""
    name = _override or ("mosaic" if _mosaic_available() else "jnp")
    if name == "mosaic" and not _mosaic_available():
        raise RuntimeError(
            f"the 'mosaic' kernel implementation needs a TPU backend but "
            f"jax.default_backend() is {jax.default_backend()!r}; use "
            f"'interpret' to run the Pallas kernels in the interpreter here")
    global _logged
    if _logged != name:
        _logged = name
        logger.info("codec kernels: %s (backend %s)", name,
                    jax.default_backend())
    return name


def interpret_flag() -> bool:
    """The ``interpret=`` a bare Pallas kernel call receives: compiled
    under ``mosaic``, the interpreter otherwise."""
    return impl() != "mosaic"

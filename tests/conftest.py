"""Test configuration. NOTE: no XLA device-count flags here by design —
smoke tests run on the single real device; collective-equivalence tests
spawn a subprocess with their own XLA_FLAGS (test_collectives.py)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_collection_modifyitems(config, items):
    """tpu-marked tests only make sense with a Mosaic backend: auto-skip
    elsewhere (the jax import is deferred until a marked test exists)."""
    marked = [it for it in items if it.get_closest_marker("tpu")]
    if not marked:
        return
    import jax
    if jax.default_backend() == "tpu":
        return
    skip = pytest.mark.skip(reason='jax.default_backend() != "tpu"')
    for it in marked:
        it.add_marker(skip)

try:
    from hypothesis import settings
except ImportError:       # hypothesis absent: profile registration is
    settings = None       # best-effort; tests fall back to _hypothesis_fallback

if settings is not None:
    settings.register_profile("ci", max_examples=20, deadline=None)
    settings.load_profile("ci")


@pytest.fixture(params=["jnp", "interpret"])
def impl(request):
    """Each codec kernel implementation that runs off the TPU: a test
    enters it with ``kernels.runtime.impl_scope(impl)``."""
    return request.param

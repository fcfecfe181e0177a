"""Loss-recovery subsystem (DESIGN §8, core/recovery.py): policy parsing,
registry wiring, StaleFill fill-then-mean semantics, and the EF
mass-conservation property that makes error feedback sound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import recovery as recovery_lib
from repro.core import tar as tar_lib
from repro.core.allreduce import OptiReduceConfig
from repro.core.hadamard import ht_decode, ht_encode
from repro.core.pipeline import (Encoded, HTQuant, Identity, SyncContext,
                                 resolve_spec)
from repro.core.recovery import StaleFill


def _cfg(**kw):
    base = dict(strategy="optireduce", drop_rate=0.3,
                drop_pattern="bernoulli", use_hadamard=False,
                hadamard_block=32, packet_elems=8)
    base.update(kw)
    return OptiReduceConfig(**base)


# --------------------------------------------------- policy + registry wiring
def test_parse_layering():
    assert not recovery_lib.parse("none").any
    st_ = recovery_lib.parse("stale")
    assert st_.stale and not st_.ef and not st_.budget
    ef = recovery_lib.parse("ef")
    assert ef.stale and ef.ef and not ef.budget       # ef implies stale
    full = recovery_lib.parse("ef+budget")
    assert full.stale and full.ef and full.budget
    with pytest.raises(ValueError):
        recovery_lib.parse("zero")


def test_disabled_recovery_is_inert():
    """recovery='none' must resolve to the exact seed spec — same codec
    type, no wrapper (the parity suites pin the traced program)."""
    plain = resolve_spec(_cfg(recovery="none"))
    assert not isinstance(plain.codec, StaleFill)
    armed = resolve_spec(_cfg(recovery="stale"))
    assert isinstance(armed.codec, StaleFill)
    assert type(armed.codec.inner) is type(plain.codec)


def test_wrap_codec_rejects_nonlinear_codec():
    with pytest.raises(ValueError, match="linear"):
        recovery_lib.wrap_codec(HTQuant(), _cfg(recovery="ef"))


def test_wrap_codec_rejects_degraded_participation():
    with pytest.raises(ValueError, match="active_peers"):
        recovery_lib.wrap_codec(Identity(),
                                _cfg(recovery="stale",
                                     active_peers=(0, 1, 2)))


# --------------------------------------------------------- StaleFill.reduce
def test_stalefill_fill_then_plain_mean():
    """Every lost (sender, span) entry takes the stale prediction; the
    reduce is the plain mean over all N (arrived entries weigh exactly
    1/N — the EF split depends on it)."""
    cfg = _cfg(recovery="stale")
    ctx = SyncContext(cfg, jax.random.PRNGKey(0))
    n, s = 4, 16
    rng = np.random.default_rng(0)
    received = jnp.asarray(rng.standard_normal((n, s)), jnp.float32)
    mask = jnp.asarray(rng.random((n, s)) < 0.7, jnp.float32)
    stale = jnp.asarray(rng.standard_normal(n * s), jnp.float32)
    codec = StaleFill(inner=Identity())
    out = codec.reduce(received, mask, jnp.int32(1),
                       Encoded(received, stale=stale), ctx)
    shard = np.asarray(stale).reshape(n, s)[1]
    want = np.mean(np.asarray(mask) * np.asarray(received)
                   + (1 - np.asarray(mask)) * shard[None], 0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
    assert float(ctx.stats["filled"]) == float(jnp.sum(1.0 - mask))


def test_stalefill_without_cache_matches_inner_bitwise():
    cfg = _cfg(recovery="stale")
    ctx = SyncContext(cfg, jax.random.PRNGKey(0))
    received = jnp.asarray(np.random.default_rng(1).standard_normal((4, 8)),
                           jnp.float32)
    mask = jnp.ones((4, 8), jnp.float32)
    enc = Encoded(received, stale=None)
    a = StaleFill(inner=Identity()).reduce(received, mask, jnp.int32(0),
                                           enc, ctx)
    b = Identity().reduce(received, mask, jnp.int32(0), enc, ctx)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------- EF mass conservation property
@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1), st.sampled_from([False, True]),
       st.integers(0, 3))
def test_ef_mass_conservation(seed, use_ht, me):
    """The split is exact for linear codecs: what the stale fill applied in
    rank ``me``'s stead this step plus the carried residual equals its full
    contribution — ``decode(m*w + (1-m)*w_stale) + residual == bucket`` —
    so dropped gradient mass is applied exactly once, never twice."""
    cfg = _cfg(use_hadamard=use_ht, drop_pattern="burst", drop_rate=0.4)
    n, length = 4, 200
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)
    bucket = jnp.asarray(rng.standard_normal(length), jnp.float32)
    stale = jnp.asarray(rng.standard_normal(length), jnp.float32)

    block = cfg.hadamard_block if use_ht else 1
    x, _ = tar_lib.pad_for_tar(bucket, n, block)
    st_pad, _ = tar_lib.pad_for_tar(stale, n, block)
    if use_ht:
        w = ht_encode(x, key, block=block)
        w_st = ht_encode(st_pad, key, block=block)
    else:
        w, w_st = x, st_pad
    arrival = recovery_lib.sender_arrival_masks(cfg, key, n, x.shape[0] // n)
    mine = arrival[me]
    applied = mine * w + (1.0 - mine) * w_st
    if use_ht:
        applied = ht_decode(applied, key, block=block)
    resid = recovery_lib.ef_residual(bucket, key, cfg, n, jnp.int32(me),
                                    stale=stale)
    np.testing.assert_allclose(np.asarray(applied[:length] + resid),
                               np.asarray(bucket), rtol=2e-4, atol=2e-4)


def test_ef_residual_zero_without_drops():
    cfg = _cfg(drop_rate=0.0)
    bucket = jnp.ones(64)
    out = recovery_lib.ef_residual(bucket, jax.random.PRNGKey(0), cfg, 4,
                                   jnp.int32(0))
    assert float(jnp.abs(out).max()) == 0.0


def test_ef_residual_arena_uses_sync_engine_bucket_keys():
    """The arena wrapper must derive per-bucket keys exactly as the sync
    engine does (bucket_plan.bucket_keys) — a drifted fold would make the
    residual reconstruct the wrong arrival masks."""
    from repro.core.bucket_plan import bucket_keys
    cfg = _cfg(drop_rate=0.25)
    arena = jnp.asarray(np.random.default_rng(3).standard_normal((3, 96)),
                        jnp.float32)
    stale = jnp.zeros_like(arena)
    step_key = jax.random.PRNGKey(9)
    got = recovery_lib.ef_residual_arena(arena, step_key, cfg, 4,
                                         jnp.int32(2), stale=stale)
    keys = bucket_keys(step_key, 3)
    want = jnp.stack([recovery_lib.ef_residual(arena[b], keys[b], cfg, 4,
                                               jnp.int32(2))
                      for b in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_init_state_shapes():
    pol = recovery_lib.parse("ef")
    state = recovery_lib.init_state(pol, (5, 32), n_dp=4)
    assert state["stale"].shape == (5, 32)
    assert state["ef"].shape == (4, 5, 32)
    assert not np.asarray(state["stale"]).any()
    assert "ef" not in recovery_lib.init_state(recovery_lib.parse("stale"),
                                               (5, 32))
    assert recovery_lib.init_state(recovery_lib.parse("none"), (5, 32)) == {}
    # the tile-dense (B, R, L) arena
    dense = recovery_lib.init_state(pol, (5, 4, 128), n_dp=2)
    assert dense["stale"].shape == (5, 4, 128)
    assert dense["ef"].shape == (2, 5, 4, 128)

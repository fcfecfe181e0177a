"""Host wire transport end-to-end: inproc determinism/fidelity, adaptive
deadlines, ControlPlane integration, and the UDP backend (which auto-skips
when the sandbox forbids socket binding, and runs a real 4-peer localhost
allreduce as a slow smoke).
"""
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import drops as drops_lib
from repro.core import tar as tar_lib
from repro.core.allreduce import OptiReduceConfig
from repro.core.pipeline import resolve_spec
from repro.core.ubt import AdaptiveTimeout
from repro.net import (HostRing, InprocBackend, bernoulli_drops,
                       mask_scripted_drops, peer_factor_delays, udp_available)
from repro.runtime import ControlPlane

pytestmark = pytest.mark.net

N = 4
KEY = jax.random.PRNGKey(5)


def _cfg(**kw):
    base = dict(strategy="optireduce", drop_rate=0.0, hadamard_block=256,
                packet_elems=64)
    base.update(kw)
    return OptiReduceConfig(**base)


def _buckets(elems=1000, seed=1):
    return np.random.default_rng(seed).standard_normal(
        (N, elems)).astype(np.float32)


def test_inproc_no_drop_allreduce_is_the_mean_and_deterministic():
    cfg = _cfg()
    buckets = _buckets()
    out1, tel1 = HostRing(N, cfg, backend="inproc").allreduce(buckets, KEY)
    out2, tel2 = HostRing(N, cfg, backend="inproc").allreduce(buckets, KEY)
    np.testing.assert_array_equal(out1, out2)        # fully deterministic
    assert tel1.loss_frac == 0.0 and not tel1.timed_out
    true = buckets.mean(axis=0)
    for p in range(N):
        np.testing.assert_allclose(out1[p], true, atol=1e-5)
    # every peer decodes identical bytes (stage 2 is authoritative)
    for p in range(1, N):
        np.testing.assert_array_equal(out1[0], out1[p])
    # telemetry fully populated: one round entry per exchange round per
    # stage, one stage-time entry per peer
    assert len(tel1.peer_stage_times) == N
    assert all(t == t for t in tel1.peer_stage_times)    # no NaNs: all seen
    assert len(tel1.round_times) == 2 * (N - 1)          # stage 1 + stage 2
    assert tel1.round_frac_received == (1.0,) * (2 * (N - 1))


def test_scripted_drops_produce_the_exact_drops_masks():
    """The wire-observed mask at each receiver is bitwise the core/drops.py
    mask the script was derived from (the parity mechanism, single
    process)."""
    cfg = _cfg(drop_rate=0.1)
    spec = resolve_spec(cfg)
    padded, _ = tar_lib.pad_for_tar(jnp.zeros(1000), N,
                                    spec.codec.block(cfg))
    s = padded.shape[0] // N
    masks = {me: np.asarray(drops_lib.make_mask(
        cfg.drop_pattern, jax.random.fold_in(KEY, me), N, s,
        rate=cfg.drop_rate, packet_elems=cfg.packet_elems,
        self_index=jnp.asarray(me))) for me in range(N)}
    ring = HostRing(N, _cfg(), backend=InprocBackend(
        N, drop_fn=mask_scripted_drops(masks, cfg.packet_elems)))
    shards = {me: np.arange(N * s, dtype=np.float32).reshape(N, s) + me
              for me in range(N)}
    got: dict = {}

    def call_round(tag):
        def call(me):
            got[(tag, me)] = ring.bridge_exchange(me, shards[me])
        threads = [threading.Thread(target=call, args=(me,))
                   for me in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)

    call_round(0)                    # priming: masks are all-ones
    assert ring.flush()
    call_round(1)                    # consumes exchange 0's observed masks
    for me in range(N):
        np.testing.assert_array_equal(got[(0, me)],
                                      np.ones((N, s), np.float32))
        np.testing.assert_array_equal(got[(1, me)], masks[me])
    assert ring.bridge_misses == 0
    assert ring.flush()
    tel = ring.drain_telemetry()
    want_loss = 1.0 - np.mean([masks[me] for me in range(N)])
    assert tel.loss_frac == pytest.approx(want_loss, abs=1e-7)


def test_bernoulli_wire_loss_tracks_the_scripted_rate():
    ring = HostRing(N, _cfg(), backend="inproc",
                    drop_fn=bernoulli_drops(0.05, seed=3))
    dropped = total = 0.0
    for step in range(8):
        _, tel = ring.allreduce(_buckets(4096), KEY, step=step)
        dropped += tel.dropped
        total += tel.total
    assert 0.01 < dropped / total < 0.12


def test_late_packets_are_masked_never_blocked():
    """A peer slower than the receive deadline is equivalent to loss: its
    entries are masked, the round flags a timeout, and the result is still
    the compensated mean over the peers that made it."""
    slow = 2
    factors = tuple(50.0 if p == slow else 1.0 for p in range(N))
    ring = HostRing(N, _cfg(), backend=InprocBackend(
        N, delay_fn=peer_factor_delays(1e-4, factors)),
        default_deadline=1e-3)           # 50x base delay > deadline
    buckets = _buckets()
    out, tel = ring.allreduce(buckets, KEY)
    assert tel.timed_out
    assert tel.loss_frac > 0.0
    # the slow peer was charged the deadline (the straggler signal)
    assert tel.peer_stage_times[slow] == pytest.approx(1e-3)
    assert max(tel.peer_stage_times[p] for p in range(N) if p != slow) \
        < 1e-3
    # the exact degraded semantics: every receiver's aggregation excluded
    # the slow peer's stage-1 contributions (compensated mean over the 3
    # on-time peers — hadamard_block 256 == shard size, so regions align),
    # and the slow peer's own stage-2 shard region is a zero-filled hole
    # (stage-2 loss is a real gap; DESIGN §2/§7)
    s = 256                               # padded 1024 over 4 peers
    mean3 = buckets[[p for p in range(N) if p != slow]].mean(axis=0)
    np.testing.assert_allclose(out[0][:slow * s], mean3[:slow * s],
                               atol=1e-5)
    np.testing.assert_allclose(out[0][(slow + 1) * s:],
                               mean3[(slow + 1) * s:], atol=1e-5)
    np.testing.assert_array_equal(out[0][slow * s:(slow + 1) * s],
                                  np.zeros(s, np.float32))


def test_adaptive_timeout_drives_the_deadline():
    """Once the AdaptiveTimeout is profiled, the receive loop's budget is
    its round_deadline; before that, the configured default."""
    at = AdaptiveTimeout(warmup_iters=3)
    ring = HostRing(N, _cfg(), backend="inproc", timeout=at,
                    default_deadline=7.0)
    assert ring.peers[0].round_deadline() == 7.0
    for t in (0.1, 0.2, 0.3):
        at.observe_warmup(t)
    assert at.ready
    assert ring.peers[0].round_deadline() == at.round_deadline(False)
    assert ring.peers[0].round_deadline() < 7.0


def test_early_timeout_shaves_the_straggling_tail():
    """§3.2.1 engaged on the wire: once 99% of a stream's packets are in,
    the receiver waits only x%*t_C more — a single packet straggling far
    behind (a stalled flow's retransmit tail) is masked at ~t99 + x*t_C
    instead of burning the hard t_B bound."""
    from repro.net.wire import KIND_DATA1, n_packets

    elems, pe = 4096, 64
    s = elems // N                       # 1024 elems -> 16 packets/stream
    n_pkts = n_packets(s, pe)
    tail_seq = n_pkts - 1

    def delay(src, dst, hdr):
        if hdr.kind == KIND_DATA1 and hdr.seq == tail_seq:
            return 0.5                   # one packet stalls far behind
        return 1e-4

    at = AdaptiveTimeout()
    at.t_b, at.t_c, at.x = 1.0, 1e-3, 0.1
    ring = HostRing(N, _cfg(), backend=InprocBackend(N, delay_fn=delay),
                    timeout=at)
    out, tel = ring.allreduce(_buckets(elems), KEY)
    # stage-1 rounds expired early: charged ~1e-4 + 0.1*1e-3, not 0.5/1.0
    stage1 = tel.round_times[:N - 1]
    assert all(t < 5e-4 for t in stage1), stage1
    assert tel.timed_out
    # exactly the tail packet of each stage-1 stream is masked
    per_stream = 1.0 - (n_pkts - 1) / n_pkts
    want = per_stream * (N - 1) / N      # self rows never drop
    assert tel.loss_frac == pytest.approx(want, abs=1e-6)


def test_wire_telemetry_feeds_straggler_detection():
    """The closed loop the ROADMAP asked for: wire-observed per-peer stage
    times flow through StepTelemetry into the ControlPlane, whose detector
    ejects the persistent straggler."""
    slow = 1
    factors = tuple(6.0 if p == slow else 1.0 for p in range(N))
    ring = HostRing(N, _cfg(), backend=InprocBackend(
        N, delay_fn=peer_factor_delays(1e-4, factors)))
    control = ControlPlane.create(n_nodes=N)
    buckets = _buckets(512)
    for step in range(12):
        _, tel = ring.allreduce(buckets, KEY, step=step)
        assert tel.peer_stage_times is not None
        assert len(tel.peer_stage_times) == N
        control.observe(tel)
    policy = control.policy()
    assert policy.active_peers is not None
    assert slow not in policy.active_peers
    assert control.detector.ejected_peers() == (slow,)


def test_quantized_strategy_over_the_wire():
    """HTQuant codes cross the wire as uint8; the amax grids max-share over
    the control channel, so all peers decode identical bytes and the
    dequantized mean lands near the true mean."""
    cfg = _cfg(strategy="optireduce_q", quant_bits=8)
    buckets = _buckets(2048)
    out, tel = HostRing(N, cfg, backend="inproc").allreduce(buckets, KEY)
    for p in range(1, N):
        np.testing.assert_array_equal(out[0], out[p])
    true = buckets.mean(axis=0)
    scale = np.abs(buckets).max()
    assert np.abs(out[0] - true).max() < 0.05 * scale
    assert tel.loss_frac == 0.0


def test_non_tar_strategy_rejected():
    with pytest.raises(ValueError, match="TAR"):
        HostRing(N, _cfg(strategy="gloo_ring"), backend="inproc")


# ----------------------------------------------------------- the launcher
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_launcher_transport_inproc_feeds_peer_stage_times(tmp_path):
    """Acceptance pin: ``launch/train.py --transport=inproc`` produces
    StepTelemetry.peer_stage_times — one entry per peer, consumed by the
    ControlPlane/StragglerDetector — closing the ROADMAP item that the
    launcher only ever fed step wall-clock."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # virtual CPU devices
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--steps", "3", "--dp", "4", "--tp", "1",
         "--strategy", "optireduce", "--transport", "inproc",
         "--drop-rate", "0.02", "--log-every", "1",
         "--global-batch", "8", "--seq-len", "64"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    wire_lines = [l for l in proc.stdout.splitlines()
                  if l.startswith("wire[inproc]")]
    assert wire_lines, proc.stdout
    # one stage-time entry per peer, all populated
    assert "peers=4" in wire_lines[0]
    times = wire_lines[0].split("stage_times=[")[1].split("]")[0].split(",")
    assert len(times) == 4
    assert all(float(t) > 0 for t in times)
    # the wire really injected loss and the steps observed it
    losses = [float(l.split("loss_frac=")[1].split()[0]) for l in wire_lines]
    assert max(losses) > 0.0, wire_lines
    assert "done" in proc.stdout


# --------------------------------------------------------------------- UDP
needs_udp = pytest.mark.skipif(
    not udp_available(),
    reason="sandbox forbids UDP socket binding on localhost")


@needs_udp
def test_udp_two_peer_allreduce_quick():
    ring = HostRing(2, _cfg(), backend="udp", default_deadline=2.0)
    try:
        buckets = np.random.default_rng(0).standard_normal(
            (2, 600)).astype(np.float32)
        out, tel = ring.allreduce(buckets, KEY)
        np.testing.assert_allclose(out[0], buckets.mean(axis=0), atol=1e-5)
        np.testing.assert_array_equal(out[0], out[1])
        assert len(tel.peer_stage_times) == 2
    finally:
        ring.close()


@needs_udp
@pytest.mark.slow
def test_udp_four_peer_allreduce_end_to_end():
    """The real thing: 4 peers, real localhost sockets, injected loss, the
    adaptive timeout warming up from observed stage times — repeated steps
    so reassembly handles genuine kernel-scheduling reorder."""
    at = AdaptiveTimeout(warmup_iters=5)
    control = ControlPlane.create(n_nodes=4)
    control.state.timeout = at
    ring = HostRing(4, _cfg(), backend="udp", timeout=at,
                    default_deadline=2.0,
                    drop_fn=bernoulli_drops(0.02, seed=7))
    try:
        buckets = _buckets(4096)
        true = buckets.mean(axis=0)
        losses = []
        for step in range(8):
            out, tel = ring.allreduce(buckets, KEY, step=step)
            control.observe(tel)
            losses.append(tel.loss_frac)
            # sanity at every peer under injected loss + real-clock timing
            # (a loaded box can expire whole rounds): values stay finite
            # and bounded by the contributions — a zeroed span reads 0, a
            # compensated span is a mean over a subset of the buckets
            bound = np.abs(buckets).max() + 1e-5
            for p in range(4):
                assert np.isfinite(out[p]).all()
                assert np.abs(out[p] - true).max() <= bound
        assert any(l > 0 for l in losses)          # loss really injected
        assert at.ready                            # warmup profiled from wire
        assert ring.peers[0].round_deadline() <= 2.0
    finally:
        ring.close()


def test_burst_wire_drops_are_bursty_order_free_and_on_rate():
    """The Gilbert–Elliott wire drop schedule (DESIGN §8): header-pure
    (out-of-order replay gives identical answers), statistically on-rate,
    and with multi-packet loss runs along seq — the same chain the in-JAX
    burst masks use."""
    from repro.net import burst_drops
    from repro.net.wire import KIND_CTRL, KIND_DATA1, PacketHeader

    def hdr(seq, step=0):
        return PacketHeader(kind=KIND_DATA1, sender=0, step=step, bucket=0,
                            round=1, seq=seq, n_seq=4096)

    fn = burst_drops(0.1, seed=2)
    n_seq, streams = 4096, 24
    verdicts = {}
    for s in range(streams):
        for q in range(n_seq):
            verdicts[(s, q)] = fn(0, 1, hdr(q, step=s))
    # order-free: a fresh schedule queried in reverse agrees everywhere
    fn2 = burst_drops(0.1, seed=2)
    for s in reversed(range(streams)):
        for q in reversed(range(n_seq)):
            assert fn2(0, 1, hdr(q, step=s)) == verdicts[(s, q)]

    lost = np.array([[verdicts[(s, q)] for q in range(n_seq)]
                     for s in range(streams)], dtype=int)
    rate = lost.mean()
    assert 0.05 < rate < 0.15            # stationary loss tracks the rate
    runs = []
    for row in lost:
        edges = np.flatnonzero(np.diff(np.concatenate([[0], row, [0]])))
        runs.extend((edges[1::2] - edges[::2]).tolist())
    from repro.core.drops import BURST_MEAN_PKTS
    assert BURST_MEAN_PKTS * 0.6 < float(np.mean(runs)) < BURST_MEAN_PKTS * 1.4

    # CTRL packets are never dropped (drop scripts touch DATA only)
    ctrl = PacketHeader(kind=KIND_CTRL, sender=0, step=0, bucket=0,
                        round=1, seq=0, n_seq=1)
    assert not fn(0, 1, ctrl)


# ------------------------------------------------- ISSUE 8: link rewiring
def test_dead_link_relay_completes_bitwise_without_ejection():
    """A scripted dead directed edge: with ``dead_links`` configured the
    step completes through a two-hop relay — bitwise-identical to the
    fault-free baseline, zero observed loss, neither endpoint ejected."""
    from repro.net import KIND_DATA1, KIND_DATA2

    buckets = _buckets(2048)
    base, _ = HostRing(N, _cfg(), backend="inproc").allreduce(buckets, KEY)

    def kill(src, dst, hdr):
        return src == 2 and dst == 0 and hdr.kind in (KIND_DATA1, KIND_DATA2)

    ring = HostRing(N, _cfg(), backend="inproc", drop_fn=kill,
                    dead_links=((2, 0),))
    out, tel = ring.allreduce(buckets, KEY)
    np.testing.assert_array_equal(out, base)
    assert tel.loss_frac == 0.0
    # relayed traffic never crosses the dead physical edge, so the edge
    # is not re-reported as a fault (it is already being routed around)
    assert tel.dead_link_events == ()


def test_link_fault_detected_then_rerouted_closed_loop():
    """The full loop: an *untold* link fault shows up as a dead_link_event,
    the ControlPlane's patience turns it into SyncPolicy.dead_links, and a
    ring rebuilt under that policy completes the step bitwise-clean —
    without ejecting either endpoint."""
    from repro.net import KIND_DATA1, KIND_DATA2

    buckets = _buckets(2048)
    base, _ = HostRing(N, _cfg(), backend="inproc").allreduce(buckets, KEY)

    def kill(src, dst, hdr):
        return src == 2 and dst == 0 and hdr.kind in (KIND_DATA1, KIND_DATA2)

    control = ControlPlane.create(n_nodes=N, link_patience=2)
    faulty = HostRing(N, _cfg(), backend="inproc", drop_fn=kill)
    for step in range(2):
        _, tel = faulty.allreduce(buckets, KEY, step=step)
        assert (2, 0) in tel.dead_link_events      # receiver 0 flags src 2
        assert tel.loss_frac > 0.0                 # the fault really bit
        control.observe(tel)
    dead = control.policy().dead_links
    assert dead == ((2, 0),)
    # recompile under the policy: same fault, now rerouted
    healed = HostRing(N, _cfg(), backend="inproc", drop_fn=kill,
                      dead_links=dead)
    out, tel = healed.allreduce(buckets, KEY, step=2)
    np.testing.assert_array_equal(out, base)
    assert tel.loss_frac == 0.0 and tel.dead_link_events == ()
    # the point of rewiring: both endpoints stay in the job
    assert control.detector.ejected_peers() == ()


# ---------------------------------------------- ISSUE 8: weighted shards
def test_weighted_wire_bitwise_matches_uniform():
    """Straggler-proportional shard weights over the wire: same bytes, a
    different ownership split — bitwise-identical to the uniform exchange
    at zero drops (the same masked-mean row-order argument as in-JAX)."""
    buckets = _buckets(2048)
    base, _ = HostRing(N, _cfg(), backend="inproc").allreduce(buckets, KEY)
    ring = HostRing(N, _cfg(), backend="inproc", shard_weights=(2, 2, 1, 2))
    out, tel = ring.allreduce(buckets, KEY)
    np.testing.assert_array_equal(out, base)
    assert tel.loss_frac == 0.0
    # a uniform tuple normalizes away entirely (the parity discipline:
    # full weight everywhere is *the same policy* as no weights)
    uniform = HostRing(N, _cfg(), backend="inproc",
                       shard_weights=(3, 3, 3, 3))
    assert all(p.shard_weights is None for p in uniform.peers)


def test_weighted_wire_with_dead_link_still_bitwise():
    from repro.net import KIND_DATA1, KIND_DATA2

    buckets = _buckets(2048)
    base, _ = HostRing(N, _cfg(), backend="inproc").allreduce(buckets, KEY)

    def kill(src, dst, hdr):
        return src == 1 and dst == 3 and hdr.kind in (KIND_DATA1, KIND_DATA2)

    ring = HostRing(N, _cfg(), backend="inproc", drop_fn=kill,
                    shard_weights=(2, 1, 2, 2), dead_links=((1, 3),))
    out, tel = ring.allreduce(buckets, KEY)
    np.testing.assert_array_equal(out, base)
    assert tel.loss_frac == 0.0


def test_weighted_wire_rejects_quantized_codec():
    # HTQuant grids are keyed on uniform shard geometry — must refuse
    with pytest.raises(ValueError, match="linear"):
        HostRing(N, _cfg(strategy="optireduce_q"), backend="inproc",
                 shard_weights=(2, 2, 1, 2))

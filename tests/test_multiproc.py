"""Launch-to-allreduce tests for the multi-process peer runtime
(repro/launch/multiproc.py).

The load-bearing claims (DESIGN §9): a multi-worker launch through the
rendezvous produces *bitwise* the same per-rank results as the
single-process :class:`~repro.net.HostRing` driver under the same scripted
loss; a worker crash mid-step lets the survivors complete that step
degraded and eject the corpse; a restarted worker is readmitted through
PROBATION and resumes from its checkpoint.  The inproc backend runs the
whole machinery in-process (threads over the LocalCoordinator — fast,
deterministic); the UDP path is the real thing: one OS process per rank,
TCP rendezvous, datagrams on localhost (slow-marked).
"""
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core.pipeline import OptiReduceConfig
from repro.launch import multiproc as mp
from repro.net import HostRing, bernoulli_drops, udp_available

pytestmark = [pytest.mark.net, pytest.mark.multiproc]

needs_udp = pytest.mark.skipif(not udp_available(),
                               reason="sandbox forbids UDP sockets")

N, DROP_RATE, DROP_SEED = 4, 0.1, 3


def _checksum(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def _reference_checksums(elems, steps, seed=0):
    """Per-rank per-step checksums from the single-process HostRing driver
    under the identical scripted wire (the parity oracle)."""
    import jax

    cfg = OptiReduceConfig(strategy="optireduce", drop_rate=0.0,
                           hadamard_block=256, packet_elems=256)
    ring = HostRing(N, cfg, backend="inproc",
                    drop_fn=bernoulli_drops(DROP_RATE, seed=DROP_SEED))
    out = {}
    for step in range(steps):
        data = np.random.default_rng(seed + step).standard_normal(
            (N, elems)).astype(np.float32)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        res, _ = ring.allreduce(data, key, step=step, bucket=0)
        out[step] = [_checksum(np.asarray(res[r])) for r in range(N)]
    return out


def _by_rank(report):
    return {w["rank"]: w for w in report["workers"] if "steps" in w}


# ---------------------------------------------------------------- inproc
def test_inproc_launch_matches_hostring_bitwise(tmp_path):
    """4 launched workers over the LocalCoordinator == single-process
    HostRing, checksum-for-checksum."""
    elems, steps = 2048, 3
    report = mp.main(["--backend", "inproc", "--nprocs", str(N),
                      "--steps", str(steps), "--elems", str(elems),
                      "--drop-rate", str(DROP_RATE),
                      "--drop-seed", str(DROP_SEED)])
    ref = _reference_checksums(elems, steps)
    by_rank = _by_rank(report)
    assert sorted(by_rank) == list(range(N))
    for step in range(steps):
        got = [by_rank[r]["steps"][step]["checksum"] for r in range(N)]
        assert got == ref[step], f"step {step} diverged from HostRing"
        # stage-1 loss really flowed (scripted wire, not a lossless path)
        assert any(by_rank[r]["steps"][step]["loss_frac"] > 0
                   for r in range(N))


def test_inproc_crash_ejection_and_probation_readmission():
    """Thread-mode SIGKILL at step 1: the step completes degraded, the
    victim is ejected, its restart restores the checkpoint and walks
    EJECTED -> PROBATION -> ACTIVE in the survivors' detectors."""
    kill_rank, kill_step, steps = 1, 1, 6
    # paced like the udp case below: unpaced survivors can finish every
    # step before the respawned victim has rejoined, leaving it none to run
    report = mp.main(["--backend", "inproc", "--nprocs", str(N),
                      "--steps", str(steps), "--elems", "1024",
                      "--drop-rate", str(DROP_RATE), "--step-sleep", "0.25",
                      "--kill-rank", str(kill_rank),
                      "--kill-step", str(kill_step), "--restart"])
    killed = [w for w in report["workers"] if w.get("exit") == "killed"]
    assert len(killed) == 1 and killed[0]["rank"] == kill_rank
    by_rank = _by_rank(report)
    assert sorted(by_rank) == list(range(N))

    rejoiner = by_rank[kill_rank]
    assert rejoiner["resumed_from"] == kill_step - 1   # checkpointed step
    assert rejoiner["start_step"] > kill_step
    assert rejoiner["steps"][-1]["step"] == steps - 1

    for r in range(N):
        if r == kill_rank:
            continue
        recs = by_rank[r]["steps"]
        trail = [s["statuses"][kill_rank] for s in recs]
        # the kill step itself completes (degraded), ejection lands next
        assert trail[kill_step] == "active"
        assert recs[kill_step + 1]["skipped"] == [kill_rank]
        assert "ejected" in trail[kill_step + 1:]
        # the rejoin readmits through probation, never straight to active
        post = trail[trail.index("ejected"):]
        assert "probation" in post
        assert post.index("probation") < len(post) - 1 or \
            post[-1] == "probation"


# ------------------------------------------------------------------- udp
def _run_udp(argv, timeout):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "report.json")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.multiproc",
             "--report", path] + argv,
            env=env, timeout=timeout, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(path) as f:
            return json.load(f)


@pytest.mark.slow
@needs_udp
def test_udp_4proc_launch_matches_hostring_bitwise():
    """The acceptance pin: a 4-process UDP run over the TCP rendezvous is
    bitwise identical to the single-process inproc HostRing under the same
    scripted loss.  The generous --deadline keeps real wall-clock out of
    the arrival masks (a 0.25s deadline can expire under CPU contention
    from 4 concurrent jax processes, masking packets the script delivered).
    """
    elems, steps = 4096, 2
    report = _run_udp(["--backend", "udp", "--nprocs", str(N),
                       "--steps", str(steps), "--elems", str(elems),
                       "--drop-rate", str(DROP_RATE),
                       "--drop-seed", str(DROP_SEED),
                       "--deadline", "2.0", "--timeout", "240"],
                      timeout=300)
    ref = _reference_checksums(elems, steps)
    by_rank = _by_rank(report)
    assert sorted(by_rank) == list(range(N))
    for step in range(steps):
        got = [by_rank[r]["steps"][step]["checksum"] for r in range(N)]
        assert got == ref[step], f"step {step} diverged from HostRing"


@pytest.mark.slow
@needs_udp
def test_udp_sigkill_ejection_and_readmission():
    """Real SIGKILL mid-run: survivors eject the corpse and keep stepping;
    the relaunched process rejoins via the rendezvous, restores its
    checkpoint, and at least one survivor records its probationary
    readmission (detector re-ejection on real timing noise is legal)."""
    kill_rank, kill_step, steps = 1, 1, 12
    report = _run_udp(["--backend", "udp", "--nprocs", str(N),
                       "--steps", str(steps), "--elems", "1024",
                       "--drop-rate", "0.05", "--deadline", "1.0",
                       "--step-sleep", "2", "--kill-rank", str(kill_rank),
                       "--kill-step", str(kill_step), "--restart",
                       "--timeout", "400"],
                      timeout=480)
    assert report["scenario"]["kill_rank"] == kill_rank
    killed = [w for w in report["workers"] if w.get("exit") == "killed"]
    assert len(killed) == 1
    by_rank = _by_rank(report)
    assert sorted(by_rank) == list(range(N))

    rejoiner = by_rank[kill_rank]
    assert rejoiner["resumed_from"] == kill_step - 1
    assert rejoiner["start_step"] > kill_step
    assert rejoiner["steps"][-1]["step"] == steps - 1

    survivors = [by_rank[r] for r in range(N) if r != kill_rank]
    for w in survivors:
        trail = [s["statuses"][kill_rank] for s in w["steps"]]
        assert "ejected" in trail[kill_step:]
        assert w["steps"][-1]["step"] == steps - 1
    # the kill step completed degraded everywhere (no survivor aborted it)
    assert all(any(s["step"] == kill_step for s in w["steps"])
               for w in survivors)
    assert any("probation" in [s["statuses"][kill_rank] for s in w["steps"]]
               for w in survivors)


def test_sigkill_helper_uses_sigkill():
    """The scripted kill must be a real SIGKILL (no atexit, no TCP FIN) —
    the rendezvous EOF/heartbeat path is what detects it."""
    src = mp._sigkill_self.__code__.co_names
    assert "SIGKILL" in src and "kill" in src
    assert signal.SIGKILL == 9


# ----------------------------------------------------- one process per chip
def test_spawned_workers_run_jax_on_the_cpu(monkeypatch):
    """Workers model other hosts' host-side codecs: each runs JAX on the
    CPU, so none reaches for a chip the launching host may own."""
    seen = {}

    class _Popen:
        def __init__(self, cmd, env):
            seen["cmd"], seen["env"] = cmd, env

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(mp.subprocess, "Popen", _Popen)
    mp._spawn(mp.build_parser().parse_args([]), 0, "127.0.0.1:1", "r.json")
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--worker" in seen["cmd"]


@needs_udp
def test_udp_parent_initialises_no_jax_backend():
    """A whole udp launch leaves the parent without a JAX backend (it
    would hold the chip its workers are kept off)."""
    code = ("from jax._src import xla_bridge\n"
            "from repro.launch import multiproc\n"
            "multiproc.main(['--nprocs', '2', '--steps', '1',\n"
            "                '--elems', '1024', '--timeout', '240'])\n"
            "print('BACKENDS', len(xla_bridge._backends))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKENDS 0" in proc.stdout

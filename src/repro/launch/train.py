"""Training launcher: end-to-end training with OptiReduce gradient sync.

With ``--smoke`` it drives a reduced same-family config (CPU tests and
examples); without it, the full published config, e.g. gpt2-paper on one
TPU v5e chip or 4-way replicated DP on a 2x2 host (``chip_smoke.py`` drives
both). ``main`` returns the per-step losses and wall times so an in-process
caller can check the run. Compiles go to the persistent cache chosen by
``launch/compile_cache.py``.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-paper --smoke \\
      --steps 50 --dp 4 --tp 2 --strategy optireduce --drop-rate 0.01
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, get_smoke
from repro.core.allreduce import OptiReduceConfig, strategies
from repro.core.safeguards import LossMonitor
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_params
from repro.obs import span
from repro.optim.optimizers import OptimizerConfig
from repro.train import checkpoint as ckpt_lib
from repro.train.trainer import TrainConfig, arena_plan, build_train_step

DEVICE_TRACED_STEPS = 8


class DeviceCapture:
    """A JAX device trace of ``n`` steps from step ``first`` into
    ``<out_dir>/device``, each step annotated ``train`` as the benchmark
    annotates its steps. The host spans (``batch``, ``step``, ``control``,
    ``rebuild``, ``ckpt``) are profiler annotations too, so every idle gap
    of the device lines up with what the host was doing."""

    def __init__(self, out_dir: str, first: int, n: int = DEVICE_TRACED_STEPS):
        self.dir = os.path.join(out_dir, "device")
        self.first, self.last = first, first + n - 1
        self.active = False
        self._step = None

    def begin(self, step: int) -> None:
        if step == self.first:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host annotations, no calls
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True
        if self.active:
            self._step = jax.profiler.StepTraceAnnotation("train",
                                                          step_num=step)
            self._step.__enter__()

    def end(self, step: int) -> None:
        if self._step is not None:
            self._step.__exit__(None, None, None)
            self._step = None
        if step >= self.last:
            self.close()

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False


def main(argv=None) -> dict:
    """Train; returns per-step ``losses`` and wall seconds ``step_times``
    (step 0 includes the compile), the jitted ``step`` with its final
    ``params``, ``opt_state`` and last ``batch``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-paper")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the config's depth to this many layers "
                         "(widths unchanged)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--dp", type=int, default=0, help="0 = all devices")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--strategy", default="optireduce",
                    help=f"one of {', '.join(strategies())} or any "
                         "register_strategy'd composition")
    ap.add_argument("--drop-rate", type=float, default=0.0)
    ap.add_argument("--drop-pattern", default="tail")
    ap.add_argument("--recovery", default="none",
                    choices=("none", "stale", "ef", "ef+budget"),
                    help="gradient-loss recovery beyond zero-fill (DESIGN "
                         "§8): 'stale' fills zero-arrival wire spans from "
                         "the previous step's decoded bucket; 'ef' adds "
                         "per-rank error-feedback residuals; 'ef+budget' "
                         "adds the phase-aware loss budget (deadlines "
                         "stretch while observed loss overruns the "
                         "convergence-tightening budget)")
    ap.add_argument("--transport", default="lossy",
                    choices=("lossy", "inproc", "udp"),
                    help="stage-1 arrival masks: 'lossy' = the synthetic "
                         "drop model (core/drops.py); 'inproc'/'udp' really "
                         "exchange the shard bytes between host peers over "
                         "the wire backend (repro/net) and mask by what "
                         "arrived — per-peer stage times, timeout flags and "
                         "received fractions feed the ControlPlane")
    ap.add_argument("--wire-deadline", type=float, default=None,
                    help="receive deadline before the AdaptiveTimeout is "
                         "profiled (backend clock units)")
    ap.add_argument("--rendezvous", default=None,
                    help="with --transport=udp: coordinate the ring's peers "
                         "through the socket rendezvous (repro/net/"
                         "rendezvous.py) — 'auto' starts an in-process "
                         "coordinator, host:port joins an external one; the "
                         "peers consume the live membership view (a rank "
                         "that leaves or dies is skipped, not waited on)")
    ap.add_argument("--incast", type=int, default=1,
                    help="round-schedule incast I (rounds topologies)")
    ap.add_argument("--adaptive", action="store_true",
                    help="drive next-step Hadamard/incast/participation "
                         "from the runtime ControlPlane (paper §3.2 + the "
                         "straggler detector) fed by observed telemetry")
    ap.add_argument("--rebalance", action="store_true",
                    help="with --adaptive: emit straggler-proportional "
                         "shard weights (a slow-but-alive peer owns a "
                         "smaller contiguous slice of each bucket) and "
                         "link-avoiding schedules (a failed directed edge "
                         "is relayed/rerouted) instead of relying on "
                         "ejection alone")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write a per-step JSON report (per-peer straggler "
                         "scores, shard weights, dead-link events) for "
                         "offline analysis")
    ap.add_argument("--trace", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="record a structured trace (DESIGN §12) — host "
                         "spans of each step (batch/step/control/rebuild/"
                         "ckpt), wire round/phase spans, every ControlPlane "
                         "transition — and write Perfetto trace_event JSON "
                         "into DIR (default '.') at exit; merge/inspect "
                         "with python -m repro.obs.report. Also captures a "
                         "JAX device trace of 8 steps from a third of the "
                         "run into DIR/device")
    ap.add_argument("--trace-capacity", type=int, default=None,
                    help="trace ring-buffer capacity in records (default "
                         "65536; oldest records drop on wraparound)")
    ap.add_argument("--policy-cache", type=int, default=4,
                    help="compiled train steps kept per SyncPolicy (LRU), "
                         "so an eject -> readmit cycle never recompiles")
    ap.add_argument("--dp-mode", default="replicated")
    ap.add_argument("--sync-mode", default="pipelined",
                    choices=("pipelined", "scan", "vmap"),
                    help="bucket schedule: stage-skewed software pipeline "
                         "(overlap encode/exchange/decode across buckets), "
                         "strict scan, or batched vmap — bitwise-identical")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = None
    if args.trace is not None:
        from repro import obs
        tracer = obs.configure(
            True, capacity=args.trace_capacity or obs.trace.DEFAULT_CAPACITY)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    if args.production_mesh:
        mesh = make_production_mesh()
    else:
        dp = args.dp or (len(jax.devices()) // args.tp)
        mesh = make_host_mesh(dp=dp, tp=args.tp)
    tp = mesh.shape.get("model", 1)
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} strategy={args.strategy} "
          f"drop_rate={args.drop_rate} transport={args.transport}")

    # host wire transport (DESIGN §7): a HostRing of per-rank peers really
    # exchanges the stage-1 shard bytes (in-memory loopback or localhost
    # UDP); --drop-rate becomes injected *wire* loss instead of the
    # synthetic mask model, and the ring's telemetry finally feeds the
    # ControlPlane per-peer stage times (not just step wall-clock).
    control = ring = rdv_server = None
    rdv_clients = []
    with_budget = args.recovery == "ef+budget"
    need_control = (args.adaptive or args.transport != "lossy" or with_budget
                    or bool(args.report))
    if need_control:
        from repro.runtime import ControlPlane, StepTelemetry
        control = ControlPlane.create(n_nodes=mesh.shape.get("data", 1),
                                      rebalance=args.rebalance,
                                      **({"budget": {}} if with_budget
                                         else {}))
    if args.transport != "lossy":
        if args.dp_mode != "replicated":
            ap.error("--transport needs --dp-mode=replicated (fsdp grads "
                     "reduce through rs_spec, which has no wire bridge)")
        if args.sync_mode == "vmap":
            ap.error("--transport bridges per-bucket io_callbacks; vmap "
                     "would batch them (use --sync-mode pipelined or scan)")
        if args.recovery in ("ef", "ef+budget"):
            ap.error("--recovery=ef/ef+budget reconstructs sender-arrival "
                     "masks from the synthetic drop model; with wire "
                     "transports use --recovery=stale")
        if mesh.shape.get("model", 1) != 1:
            ap.error("--transport needs --tp=1: with model parallelism "
                     "every tp sibling of a data rank would run the "
                     "io_callback, advancing the ring's per-rank exchange "
                     "counter tp times per bucket and pairing deposits "
                     "from different buckets into one wire exchange")
        if args.rendezvous and args.transport != "udp":
            ap.error("--rendezvous coordinates real socket peers; it needs "
                     "--transport=udp")
        from repro.core.pipeline import WireTransport
        from repro.net import HostRing, bernoulli_drops
        n_wire = mesh.shape.get("data", 1)
        membership = None
        if args.rendezvous:
            from repro.net import RendezvousClient, RendezvousServer
            if args.rendezvous == "auto":
                rdv_server = RendezvousServer(n_wire)
                rdv_addr = rdv_server.addr
            else:
                host, _, port = args.rendezvous.rpartition(":")
                rdv_addr = (host or "127.0.0.1", int(port))
            # one client per ring peer; joins are sequential so rank i is
            # peer i, and every client heartbeats — the shared membership
            # view is live, not a snapshot
            for uid in range(n_wire):
                c = RendezvousClient(rdv_addr, uid=uid)
                c.join()
                rdv_clients.append(c)
            membership = rdv_clients[0]
            print(f"rendezvous: {n_wire} peers joined at "
                  f"{rdv_addr[0]}:{rdv_addr[1]} "
                  f"generation={membership.generation}")
        ring = HostRing(
            n_wire,
            OptiReduceConfig(strategy=args.strategy, incast=args.incast,
                             hadamard_block=1024),
            backend=args.transport,
            timeout=control.state.timeout,
            default_deadline=args.wire_deadline,
            budget=control.state.budget,
            drop_fn=(bernoulli_drops(args.drop_rate, seed=args.seed)
                     if args.drop_rate > 0 else None),
            membership=membership)

    tc = TrainConfig(
        sync=OptiReduceConfig(strategy=args.strategy,
                              # wire mode: drops are observed, not synthetic
                              drop_rate=0.0 if ring else args.drop_rate,
                              drop_pattern=args.drop_pattern,
                              incast=args.incast,
                              recovery=args.recovery,
                              hadamard_block=1024),
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr),
        dp_mode=args.dp_mode, microbatch=args.microbatch,
        sync_mode=args.sync_mode,
        transport_override=(WireTransport(ring.bridge_exchange)
                            if ring else None),
        seq_chunk=min(512, args.seq_len))

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.global_batch,
                                  seed=args.seed))
    key = jax.random.PRNGKey(args.seed)
    fsdp_axes = ("data",) if args.dp_mode == "fsdp" else None
    params = init_params(key, cfg, tp=tp, fsdp_axes=fsdp_axes)

    make_step, opt, _ = build_train_step(cfg, tc, mesh)
    batch0 = data.host_batch(0, 0, 1)
    step_fn, shardings = make_step(jax.eval_shape(opt.init, params), batch0)
    params = jax.device_put(params, shardings["params"])
    opt_state = jax.jit(opt.init, out_shardings=shardings["opt"])(params)
    donate = (0, 1, 2) if args.recovery != "none" else (0, 1)
    jf = jax.jit(step_fn, donate_argnums=donate)

    rec_state = None
    if args.recovery != "none":
        from repro.core import recovery as recovery_lib
        plan = arena_plan(params, tc)
        rec_state = recovery_lib.init_state(
            recovery_lib.parse(args.recovery), plan.arena_shape,
            n_dp=mesh.shape.get("data", 1))
        rec_state = jax.device_put(rec_state, shardings["rec"])

    start_step = 0
    ckpt = ckpt_lib.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    # recovery state checkpoints alongside params/optimizer: a resume under
    # ef continues from the carried residual instead of silently dropping
    # the undelivered mass (the manifest's leaf-count guard catches a
    # resume with a different --recovery setting)
    ckpt_tree = ((params, opt_state) if rec_state is None
                 else (params, opt_state, rec_state))
    if args.resume and args.ckpt_dir:
        try:
            start_step, restored, _ = ckpt_lib.restore(
                args.ckpt_dir, ckpt_tree)
            if rec_state is None:
                params, opt_state = restored
            else:
                params, opt_state, rec_state = restored
                rec_state = jax.device_put(rec_state, shardings["rec"])
            params = jax.device_put(params, shardings["params"])
            opt_state = jax.device_put(opt_state, shardings["opt"])
            print(f"resumed from step {start_step}")
        except FileNotFoundError:
            pass

    monitor = LossMonitor(skip_threshold=tc.sync.skip_threshold)
    # §3.2 control plane (DESIGN §5): telemetry (observed loss + step wall
    # clock) feeds the runtime ControlPlane; when its SyncPolicy (Hadamard
    # on/off, advertised incast, active-peer set) moves, the step switches
    # to the policy's compiled step — from the bounded LRU cache when the
    # policy was seen before (eject -> readmit never recompiles), rebuilt
    # and cached otherwise (host-side — XLA itself cannot drop packets).
    if args.adaptive:
        from repro.core.pipeline import (RingTopology, TarTopology,
                                         resolve_spec)
        from repro.runtime import PolicyStepCache, SyncPolicy
        # start from the configured codec so step 0 never rebuilds, and
        # learn which knobs this spec can even observe: incast only lowers
        # rounds schedules; use_hadamard only matters if toggling it
        # resolves to a different spec (cfg-dependent factories); degraded
        # participation needs a mask-capable or reschedulable topology
        control.use_hadamard = tc.sync.use_hadamard
        topo = resolve_spec(tc.sync).topology
        incast_matters = (isinstance(topo, TarTopology)
                          and topo.schedule == "rounds")
        ht_matters = (resolve_spec(dataclasses.replace(
            tc.sync, use_hadamard=True)) is not resolve_spec(
                dataclasses.replace(tc.sync, use_hadamard=False)))
        participation_matters = (isinstance(topo, TarTopology) or
                                 (isinstance(topo, RingTopology)
                                  and topo.kind == "ring"))
        if ring is not None and isinstance(topo, TarTopology) \
                and topo.schedule == "rounds":
            # degraded rounds schedules exchange over a virtual ring the
            # wire bridge does not model (WireTransport raises); keep the
            # detector observing but hold full participation
            participation_matters = False
        # weighted shards / dead-link rewiring need a resizable schedule
        # (rounds TAR or a true ring) and the in-JAX transport — the wire
        # bridge's deposit geometry is fixed per compile, so the launcher
        # holds those knobs at default there (the detector still observes)
        reschedulable = ((isinstance(topo, TarTopology)
                          and topo.schedule == "rounds") or
                         (isinstance(topo, RingTopology)
                          and topo.kind == "ring"))
        rebalance_matters = args.rebalance and reschedulable and ring is None
        deadlink_matters = reschedulable and ring is None

        def policy_of(sync: OptiReduceConfig) -> SyncPolicy:
            return SyncPolicy(use_hadamard=sync.use_hadamard,
                              incast=sync.incast,
                              active_peers=sync.active_peers,
                              shard_weights=sync.shard_weights,
                              dead_links=sync.dead_links)

        step_cache = PolicyStepCache(maxsize=max(1, args.policy_cache))
        step_cache.put(policy_of(tc.sync), (jf, shardings))
        stable_rec, stable_for = None, 0
    report_rows: list[dict] = []
    losses: list[float] = []
    step_times: list[float] = []
    capture = None
    if tracer is not None:
        capture = DeviceCapture(
            args.trace, start_step + (args.steps - start_step) // 3)
    t0 = time.time()
    try:
        for step in range(start_step, args.steps):
            if capture is not None:
                capture.begin(step)
            with span("batch"):
                batch = data.host_batch(step, 0, 1)
                batch = jax.device_put(batch, shardings["batch"])
            t_step = time.time()
            with span("step", step=step) as sp:
                if rec_state is not None:
                    params, opt_state, rec_state, metrics = jf(
                        params, opt_state, rec_state, batch,
                        jnp.asarray(step, jnp.int32), key)
                else:
                    params, opt_state, metrics = jf(
                        params, opt_state, batch,
                        jnp.asarray(step, jnp.int32), key)
                loss_frac = float(metrics["loss_frac"])
                losses.append(float(metrics["loss"]))
                sp.set(loss_frac=round(loss_frac, 6))
            step_times.append(time.time() - t_step)
            if with_budget:
                # phase-aware budget (DESIGN §8): the phase follows the LR
                # schedule's progress and the observed loss curve; the EMA
                # itself is fed through control.observe below
                control.state.budget.update_phase(
                    progress=(step + 1) / max(args.steps, 1),
                    train_loss=float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.tree.map(float, metrics)
                rate = (step - start_step + 1) / (time.time() - t0)
                print(f"step {step:5d} loss {m['loss']:.4f} "
                      f"gnorm {m['grad_norm']:.3f} loss_frac {m['loss_frac']:.5f}"
                      f" skipped {int(m['skipped'])} ({rate:.2f} it/s)",
                      flush=True)
            with span("control"):
                if control is not None:
                    wire_t = None
                    if ring is not None:
                        # let in-flight exchanges land; a wedged or dead
                        # wire layer must be loud, not silently degrade to
                        # all-ones masks
                        if not ring.flush():
                            print(f"wire[{args.transport}] WARNING: "
                                  f"exchanges still in flight at step "
                                  f"{step} (flush timed out)", flush=True)
                        if ring.bridge_error is not None:
                            print(f"wire[{args.transport}] ERROR: bridge "
                                  f"worker died: {ring.bridge_error!r} — "
                                  "masks degrade to all-ones and telemetry "
                                  "stops", flush=True)
                            ring.bridge_error = None
                        wire_t = ring.drain_telemetry(step)
                    if wire_t is not None:
                        # the real thing the ROADMAP asked for: per-peer
                        # stage times, per-round deadlines/timeouts/received
                        # fractions — observed on the wire, consumed by
                        # detector + controllers
                        control.observe(wire_t)
                        if (step % args.log_every == 0
                                or step == args.steps - 1):
                            pst = ", ".join(
                                f"{t:.3g}"
                                for t in (wire_t.peer_stage_times or ()))
                            print(f"wire[{args.transport}] peers="
                                  f"{len(wire_t.peer_stage_times or ())} "
                                  f"stage_times=[{pst}] "
                                  f"loss_frac={wire_t.loss_frac:.5f} "
                                  f"deadline="
                                  f"{ring.peers[0].round_deadline():.4g}"
                                  + (f" misses={ring.bridge_misses}"
                                     if ring.bridge_misses else ""),
                                  flush=True)
                    elif ring is None:
                        # wall-clock only makes sense for the synthetic
                        # transport: a wire-fed AdaptiveTimeout is profiled in
                        # the backend's clock units, and one wall-clock sample
                        # during warmup would inflate t_B/t_C by orders of
                        # magnitude
                        control.observe(StepTelemetry(
                            step=step, loss_frac=loss_frac,
                            step_time=time.time() - t_step))
                    if args.report:
                        det = control.detector
                        report_rows.append({
                            "step": step,
                            "scores": [float(s) for s in det.scores()],
                            "weights": [int(w) for w in det.weights()],
                            "active": [int(p) for p in det.active_peers()],
                            "dead_links": [list(l)
                                           for l in control.dead_links()],
                            "dead_link_events": [
                                list(l) for l in
                                ((wire_t.dead_link_events or ())
                                 if wire_t is not None else ())],
                        })
                if args.adaptive:
                    new_sync = control.apply(tc.sync)
                    if not incast_matters:   # incast only lowers rounds forms
                        new_sync = dataclasses.replace(
                            new_sync, incast=tc.sync.incast)
                    if not ht_matters:
                        new_sync = dataclasses.replace(
                            new_sync, use_hadamard=tc.sync.use_hadamard)
                    if not participation_matters:
                        new_sync = dataclasses.replace(
                            new_sync, active_peers=tc.sync.active_peers)
                    if not rebalance_matters:
                        new_sync = dataclasses.replace(new_sync,
                                                       shard_weights=None)
                    if not deadlink_matters:
                        new_sync = dataclasses.replace(new_sync,
                                                       dead_links=())
                    # debounce: a growing incast ramps one step at a time, and
                    # each rebuild recompiles the whole step — wait for the
                    # controller to settle. A Hadamard toggle is an accuracy
                    # decision and an ejection stops the straggler wait: both
                    # immediate.
                    stable_for = (stable_for + 1 if new_sync == stable_rec
                                  else 1)
                    stable_rec = new_sync
                    # a link failure (or recovery probe) reroutes immediately
                    # — waiting three steps on a dead edge loses three
                    # deadlines
                    urgent = (new_sync.use_hadamard != tc.sync.use_hadamard or
                              new_sync.active_peers != tc.sync.active_peers or
                              new_sync.dead_links != tc.sync.dead_links)
                    if new_sync != tc.sync and (urgent or stable_for >= 3):
                        tc = dataclasses.replace(tc, sync=new_sync)
                        cached = step_cache.get(policy_of(new_sync))
                        if cached is not None:
                            jf, shardings = cached
                            how = "cached step reused"
                        else:
                            with span("rebuild"):
                                make_step, opt, _ = build_train_step(
                                    cfg, tc, mesh)
                                step_fn, shardings = make_step(
                                    jax.eval_shape(opt.init, params), batch0)
                                jf = jax.jit(step_fn, donate_argnums=donate)
                                step_cache.put(policy_of(new_sync),
                                               (jf, shardings))
                            how = "step rebuilt"
                        print(f"adaptive: "
                              f"use_hadamard={new_sync.use_hadamard} "
                              f"incast={new_sync.incast} "
                              f"active={new_sync.active_peers} "
                              f"weights={new_sync.shard_weights} "
                              f"dead={new_sync.dead_links} ({how})",
                              flush=True)
            if tracer is not None:
                tracer.counter("loss_frac", loss_frac)
            monitor.observe(step, loss_frac, bool(metrics["skipped"] > 0))
            if monitor.halted:
                print("HALT: excessive gradient loss (§3.4); rolling back")
                rb = monitor.rollback()
                if rb is not None:
                    _, params = rb
            if ckpt and step > 0 and step % args.ckpt_every == 0:
                with span("ckpt"):
                    ckpt.save(step, (params, opt_state) if rec_state is None
                              else (params, opt_state, rec_state))
            if capture is not None:
                capture.end(step)
        if ckpt:
            with span("ckpt"):
                ckpt.save(args.steps, (params, opt_state)
                          if rec_state is None
                          else (params, opt_state, rec_state))
                ckpt.wait()
    finally:
        if capture is not None:
            capture.close()
        if ring is not None:
            ring.close()          # UDP sockets + the bridge worker
        for c in rdv_clients:
            c.leave()
        if rdv_server is not None:
            rdv_server.close()
    if args.report and control is not None:
        import json
        with open(args.report, "w") as f:
            json.dump({"n_peers": control.detector.n_peers,
                       "rebalance": bool(args.rebalance),
                       "steps": report_rows}, f, indent=1)
        print(f"report: {len(report_rows)} steps -> {args.report}",
              flush=True)
    if tracer is not None:
        from repro.obs import export as obs_export
        path = obs_export.write_trace(args.trace, tracer,
                                      meta={"transport": args.transport,
                                            "strategy": args.strategy})
        print(f"trace: {len(tracer)} records ({tracer.dropped} dropped) "
              f"-> {path}; device trace -> {capture.dir}", flush=True)
    print("done")
    return {"losses": losses, "step_times": step_times, "step": jf,
            "params": params, "opt_state": opt_state, "batch": batch}


if __name__ == "__main__":
    main()

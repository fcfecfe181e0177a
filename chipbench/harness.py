"""One run of one cell: set-up, checked steps, measured window, reference.

Set-up follows ``repro.launch.train``: ``make_host_mesh``, the program's
``build_train_step`` with a ``TrainConfig`` made from the cell's files, the
same donation, and the program's persistent compile cache. Weights and
batches come from :mod:`chipbench.inputs`. The first ``CHECKED_STEPS``
steps go through the window's own call and feed; they warm the step (the
first compiles) and give the program's side of the comparison. The same
compiled step and state then run the window: steps back to back until
``seconds`` have passed, each ending in ``block_until_ready`` on its
outputs. After the window the peak memory is read, the program's state is
freed and the plain reference runs the checked steps again.

With ``trace`` a short steady part of the window is profiled, and every
step, its batch and its host wait are annotated, so idle device time can
be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import math
import shutil
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp

from chipbench import compare, inputs, manifest, reference

CHECKED_STEPS = 3
TRACED_STEPS = 8
TRACE_DIR = manifest.BENCH_DIR / ".trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


def model_config(cell: manifest.Cell):
    from repro.configs import get_config
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    over = {k: v for k, v in cell.model.items() if k in fields}
    over["param_dtype"] = jnp.dtype(cell.model["param_dtype"])
    return dataclasses.replace(get_config(cell.config["arch"]), **over)


def train_config(cell: manifest.Cell):
    from repro.core.allreduce import OptiReduceConfig
    from repro.optim.optimizers import OptimizerConfig
    from repro.train.trainer import TrainConfig

    w, t = cell.workload, cell.traffic
    return TrainConfig(
        sync=OptiReduceConfig(strategy=w["strategy"],
                              drop_rate=t["drop_rate"],
                              drop_pattern=t["drop_pattern"],
                              hadamard_block=w["hadamard_block"],
                              use_kernels=w["use_kernels"]),
        optimizer=OptimizerConfig(name="adamw", **w["optimizer"]),
        sync_mode=w["sync_mode"],
        seq_chunk=min(w["seq_chunk"], t["seq_len"]))


@dataclasses.dataclass
class Program:
    """The compiled step with its state, as the window drives it."""
    step: Any
    make_batch: Any
    init_state: Any          # seed -> (params, opt_state), on the device
    params: Any = None
    opt_state: Any = None
    data_key: Any = None
    step_key: Any = None
    next_step: int = 0

    def reseed(self, seed: int) -> None:
        """Fresh weights, optimizer state and streams from ``seed``."""
        self.params = self.opt_state = None
        self.params, self.opt_state = self.init_state(seed)
        key = inputs.seed_key(seed)
        self.data_key = jax.random.fold_in(key, 1)
        self.step_key = jax.random.fold_in(key, 2)
        self.next_step = 0


def build_program(cell: manifest.Cell, seed: int | None = None) -> Program:
    from repro.launch.mesh import make_host_mesh
    from repro.models import abstract_params
    from repro.train.trainer import build_train_step

    cfg = model_config(cell)
    shapes = inputs.param_shapes(cell.model)
    theirs = jax.tree.map(lambda a: tuple(a.shape), abstract_params(cfg))
    if theirs != shapes:
        raise ValueError(f"the program's weight layout {theirs} is not the "
                         f"configuration's {shapes}")
    mesh = make_host_mesh(dp=cell.chips, tp=1)
    make_step, opt, _ = build_train_step(cfg, train_config(cell), mesh)
    traffic = cell.traffic
    batch_shape = {k: jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"]), jnp.int32)
        for k in ("tokens", "labels")}
    step_fn, shardings = make_step(
        jax.eval_shape(opt.init, abstract_params(cfg)), batch_shape)
    opt_init = jax.jit(opt.init, out_shardings=shardings["opt"])

    def init_state(seed):
        params = inputs.make_params(seed, cell.model, cfg.param_dtype,
                                    out_shardings=shardings["params"])
        return params, opt_init(params)

    prog = Program(
        step=jax.jit(step_fn, donate_argnums=(0, 1)),
        make_batch=inputs.batch_maker(traffic, cell.model["vocab_size"],
                                      out_shardings=shardings["batch"]),
        init_state=init_state)
    if seed is not None:
        prog.reseed(seed)
    return prog


def run_step(prog: Program, annotate: bool = False) -> dict:
    """One step through the window's call and feed; host copy of its
    metrics once every output is ready."""
    ann = (jax.profiler.TraceAnnotation if annotate
           else lambda _: contextlib.nullcontext())
    i = prog.next_step
    with ann("batch"):
        batch = prog.make_batch(prog.data_key, i)
    with ann("dispatch"):
        prog.params, prog.opt_state, metrics = prog.step(
            prog.params, prog.opt_state, batch, jnp.asarray(i, jnp.int32),
            prog.step_key)
    with ann("host_wait"):
        jax.block_until_ready((prog.params, prog.opt_state, metrics))
        metrics = jax.device_get(metrics)
    prog.next_step = i + 1
    return metrics


def step_failed(metrics: dict) -> bool:
    return (not math.isfinite(float(metrics["loss"]))
            or float(metrics["skipped"]) > 0)


def checked_steps(prog: Program, cell: manifest.Cell, seed: int) -> dict:
    """The first ``CHECKED_STEPS`` steps, read for the comparison: each
    step's loss and the first step's gradient from AdamW's first moment.
    The weights after the last of them are copied to the host, so that
    their change is read (``read_change``) once the memory peak has been
    taken and the program's state freed."""
    beta1 = cell.workload["optimizer"]["beta1"]
    out = {"losses": [], "failed_steps": 0}
    for k in range(CHECKED_STEPS):
        m = run_step(prog)
        out["losses"].append(float(m["loss"]))
        out["failed_steps"] += step_failed(m)
        if k == 0:
            out["grad"] = {n: v / (1 - beta1) for n, v in
                           compare.leaf_norms(prog.opt_state.m).items()}
    out["weights"] = jax.device_get(prog.params)
    return out


def read_change(checked: dict, seed: int, model: dict) -> dict:
    """``checked`` with the weights' change after the checked steps
    (per-leaf norms) in place of its host copy of the weights."""
    checked["change"] = compare.change_norms(checked.pop("weights"), seed,
                                             model)
    return checked


class CompileCounter:
    """Counts compilations, retraces and compile-cache loads while
    ``active``."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _trace_options():
    """The device trace and the host's annotations, without a Python
    function trace (which the reduction never reads)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@dataclasses.dataclass
class Window:
    step_times: list
    window_s: float
    steps: int
    failed: int
    compiles: int
    trace_file: str | None = None


def measure_window(prog: Program, seconds: float, *,
                   trace: bool = False) -> Window:
    """Steps back to back for ``seconds``. Only steps that end inside the
    window count, and the window ends where the last of them ends. With
    ``trace``, ``TRACED_STEPS`` steps from a third of the way in are
    profiled. Python's cyclic collector is kept out of the window: the
    set-up's objects are frozen and collection is off until it closes."""
    gc.collect()
    gc.freeze()
    gc.disable()
    counter = CompileCounter()
    times, failed, trace_file = [], 0, None
    tracing = traced = 0
    t0 = time.perf_counter()
    end = t0
    counter.active = True
    try:
        while True:
            ts = time.perf_counter()
            if ts - t0 >= seconds:
                break
            if trace and not tracing and not traced \
                    and ts - t0 >= seconds / 3:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=_trace_options())
                tracing = TRACED_STEPS
            if tracing:
                with jax.profiler.StepTraceAnnotation(
                        "train", step_num=prog.next_step):
                    m = run_step(prog, annotate=True)
                tracing -= 1
                if not tracing:
                    jax.profiler.stop_trace()
                    traced = 1
            else:
                m = run_step(prog)
            te = time.perf_counter()
            if te - t0 > seconds:
                break
            times.append(te - ts)
            failed += step_failed(m)
            end = te
    finally:
        counter.active = False
        counter.close()
        if tracing:
            jax.profiler.stop_trace()
        gc.enable()
        gc.unfreeze()
    if trace:
        files = glob.glob(str(TRACE_DIR / "plugins/profile/*/*.xplane.pb"))
        trace_file = files[0] if files else None
    return Window(step_times=times, window_s=end - t0, steps=len(times),
                  failed=failed, compiles=counter.count,
                  trace_file=trace_file)


@dataclasses.dataclass
class RunRecord:
    """What a metric reader reads (``metrics/<name>.py``)."""
    cell: manifest.Cell
    window: Window
    tokens: int
    setup_s: float
    peak_bytes: int
    device_kind: str
    trace: Any = None          # trace_reduce.Reduced of the traced steps


def peak_bytes(devices) -> int:
    """Peak HBM of the fullest chip: its arrays at their peak
    (``peak_bytes_in_use``) plus the region the runtime reserves for the
    compiled programs' temporaries (``peak_bytes_reserved``), which the
    first leaves out. The reserve stays while the step is loaded, so the
    sum is what the chip holds while the step runs."""
    def one(d):
        stats = d.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))
    return max(map(one, devices))


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices=None) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    from chipbench import trace_reduce
    from repro.launch.compile_cache import enable_compile_cache

    devices = devices if devices is not None else require_chips(cell.chips)
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)}"
        f" on {cell.chips} x {devices[0].device_kind}; cache {cache_dir}")

    prog = build_program(cell, seed)
    checked = checked_steps(prog, cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; peak {peak_bytes(devices)} B; checked "
        f"losses {checked['losses']}")

    win = measure_window(prog, seconds, trace=trace)
    log(f"window {win.window_s:.3f} s, {win.steps} steps, "
        f"{win.failed} failed, {win.compiles} compilations inside")
    if win.step_times:
        times = sorted(win.step_times)
        med = times[len(times) // 2]
        slow = [t for t in win.step_times if t > 1.5 * med]
        log(f"steps: median {med * 1e3:.3f} ms, max {times[-1] * 1e3:.3f} "
            f"ms, {len(slow)} over 1.5x median ({sum(slow) * 1e3:.1f} ms; "
            f"at {[i for i, t in enumerate(win.step_times) if t > 1.5 * med][:8]})")
    peak = peak_bytes(devices)
    for d in devices:
        log(f"memory {d.id}: {d.memory_stats()}")
    del prog
    gc.collect()
    read_change(checked, seed, cell.model)

    record = RunRecord(cell=cell, window=win,
                       tokens=win.steps * cell.traffic["global_batch"]
                       * cell.traffic["seq_len"],
                       setup_s=setup_s, peak_bytes=peak,
                       device_kind=devices[0].device_kind)
    breakdown = None
    if trace:
        if win.trace_file is None:
            raise RuntimeError("the profiler wrote no trace")
        record.trace = trace_reduce.load(win.trace_file, n_devices=cell.chips)
        breakdown = trace_reduce.breakdown(record.trace)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference.run(cell.model, cell.workload["optimizer"], cell.traffic,
                        seed, cell.chips, steps=CHECKED_STEPS)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; losses "
        f"{ref['losses']}")
    checked["failed_steps"] += win.failed
    verdict = compare.judge(compare.readings(checked, ref),
                            cell.workload["limits"])
    for name, value in verdict["info"].items():
        log(f"not compared: {name} {value!r}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = record.trace.busy_s()
        device["window_s"] = record.trace.window_s
    result = {"correct": verdict["correct"],
              "attempted": CHECKED_STEPS + win.steps,
              "failed": checked["failed_steps"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    return result

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell (EXPERIMENTS.md §Dry-run / §Roofline inputs):
  * FULL compile (production depth, scan-over-layers):
      - compiled.memory_analysis()  — proves the cell fits 16 GB/chip
      - wall-clock lower/compile times
  * COST MODEL (four shallow *unrolled* compiles): XLA's cost_analysis
    counts while-loop bodies once, so scanned layers/microbatches would be
    undercounted. We therefore compile unrolled variants at two depths
    (1 and 2 stage-repeats) x two per-device batch sizes (1 and 2) and fit
    the exact linear form
        M(L, B) = fix_base + B*tok_base + L*fix_layer + L*B*tok_layer
    per metric (FLOPs, bytes, per-collective bytes), then evaluate at the
    production (L, B). The model is exact because every metric is affine in
    depth and batch by construction of the program.

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --all --multi-pod --out results/dryrun_mp.json
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config, input_specs, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.allreduce import OptiReduceConfig
from repro.core.pipeline import resolve_spec
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_params, active_params, count_params
from repro.optim.optimizers import OptimizerConfig
from repro.serve.engine import (abstract_state, build_serve_step,
                                plan_serving)
from repro.train.trainer import TrainConfig, abstract_opt_state, build_train_step

# ---------------------------------------------------------------- cell plans
# Per-arch training plan: dp_mode, microbatch (per-device), moments dtype.
# fsdp whenever params don't comfortably replicate; bf16 moments for the
# giants (16 GB/chip budget — see EXPERIMENTS.md §Dry-run).
PLAN = {
    "arctic-480b":          dict(dp_mode="fsdp", microbatch=1, mom="bf16",
                                 opt="momentum", accum="bf16",
                                 serve_fsdp=True),
    "qwen2-moe-a2.7b":      dict(dp_mode="fsdp", microbatch=4, mom="f32"),
    "mamba2-1.3b":          dict(dp_mode="fsdp", microbatch=4, mom="f32"),
    "command-r-plus-104b":  dict(dp_mode="fsdp", microbatch=1, mom="bf16",
                                 accum="bf16", serve_fsdp=True),
    "stablelm-1.6b":        dict(dp_mode="fsdp", microbatch=4, mom="f32"),
    "smollm-360m":          dict(dp_mode="replicated", microbatch=8, mom="f32"),
    "glm4-9b":              dict(dp_mode="fsdp", microbatch=2, mom="f32"),
    "llava-next-mistral-7b": dict(dp_mode="fsdp", microbatch=2, mom="f32"),
    "musicgen-medium":      dict(dp_mode="fsdp", microbatch=4, mom="f32"),
    "jamba-v0.1-52b":       dict(dp_mode="fsdp", microbatch=1, mom="bf16"),
}

SHAPE_RE = re.compile(
    r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "s8": 1, "u8": 1, "pred": 1}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        base = 1 if dt.startswith("f8") else DTYPE_BYTES.get(dt, 2)
        total += n * base
    return total


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by each collective kind (result-shape proxy;
    '-start' forms counted once, '-done' skipped)."""
    out: dict[str, float] = {}
    pat = re.compile(r"(" + "|".join(COLLECTIVES) + r")(?:-start)?\(")
    for line in hlo_text.splitlines():
        line = line.strip()
        if "-done(" in line or "=" not in line:
            continue
        m = pat.search(line)
        if not m:
            continue
        kind = m.group(1)
        nbytes = _shape_bytes(line.split("=", 1)[1].split(m.group(0))[0])
        out[kind] = out.get(kind, 0) + nbytes
        out["count_" + kind] = out.get("count_" + kind, 0) + 1
    return out


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {"available": False}
    if ma is None:
        return {"available": False}
    keys = ["generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes"]
    out = {"available": True}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    peak = (out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    out["peak_bytes_per_device"] = int(peak)
    return out


def dp_total_of(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


# -------------------------------------------------------------- cell builders
def make_train_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                    strategy: str, drop_rate: float, plan: dict,
                    unroll: bool = False, donate: bool = True):
    mom = jnp.bfloat16 if plan["mom"] == "bf16" else jnp.float32
    dp_tot = dp_total_of(mesh)
    if plan.get("pure_dp"):
        dp_tot *= mesh.shape.get("model", 1)
    b_local = max(1, shape.global_batch // dp_tot)
    # unroll (cost-model) mode: no microbatch scan at all, so every FLOP is
    # visible to cost_analysis; production mode: grad-accumulate
    microbatch = None if unroll else min(plan["microbatch"], b_local)

    tc = TrainConfig(
        sync=OptiReduceConfig(strategy=strategy, drop_rate=drop_rate,
                              rs_wire_bits=plan.get("rs_wire_bits", 0)),
        optimizer=OptimizerConfig(name=plan.get("opt", "adamw"),
                                  state_dtype=mom,
                                  # lax.map over the update breaks donation
                                  # aliasing through the loop (+2x param
                                  # memory) — measured worse; keep it off
                                  scan_update=False),
        dp_mode=plan["dp_mode"], microbatch=microbatch,
        seq_chunk=min(plan.get("seq_chunk", 512), shape.seq_len),
        remat=plan.get("remat", True), unroll=unroll,
        pure_dp=plan.get("pure_dp", False),
        seq_parallel=plan.get("seq_parallel", False),
        accum_dtype=(jnp.bfloat16 if plan.get("accum") == "bf16"
                     else jnp.float32),
        bucket_elems=plan.get("bucket_elems", 6_553_600))

    make_step, opt, _ = build_train_step(cfg, tc, mesh)
    tp = 1 if tc.pure_dp else mesh.shape["model"]
    if tc.dp_mode != "fsdp":
        fsdp_axes = None
    elif tc.pure_dp:
        fsdp_axes = ("model", "data")
    else:
        fsdp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    a_params = abstract_params(cfg, tp=tp, fsdp_axes=fsdp_axes)
    a_opt = abstract_opt_state(opt, a_params)
    a_batch = input_specs(cfg, shape)
    step_fn, _ = make_step(a_opt, a_batch)
    key_arg = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (a_params, a_opt, a_batch, jax.ShapeDtypeStruct((), jnp.int32),
            key_arg)
    jf = jax.jit(step_fn, donate_argnums=(0, 1) if donate else ())
    return jf, args, tc


def make_serve_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                    unroll: bool = False, donate: bool = True,
                    weight_fsdp: bool = False, moe_stationary: bool = False):
    plan = plan_serving(mesh, shape.global_batch)
    make = build_serve_step(cfg, mesh, plan, unroll=unroll,
                            weight_fsdp=weight_fsdp,
                            moe_stationary=moe_stationary)
    a_state = abstract_state(cfg, shape, plan)
    step_fn, _ = make(a_state)
    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    a_params = abstract_params(cfg, tp=tp,
                               fsdp_axes=dp_axes if weight_fsdp else None)
    a_tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    key_arg = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (a_params, a_state, a_tokens, jax.ShapeDtypeStruct((), jnp.int32),
            key_arg)
    jf = jax.jit(step_fn, donate_argnums=(1,) if donate else ())
    return jf, args, plan


def make_prefill_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                      unroll: bool = False, weight_fsdp: bool = False):
    from jax.sharding import PartitionSpec as P

    from repro.models import param_specs, prefill_step
    from repro.models.layers import KVCache
    from repro.models.parallel import ParallelCtx
    from repro.models.ssm import SSMState
    from repro.models.transformer import TpLayout, _period

    names = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    gather = None
    if weight_fsdp:
        def gather(w, dim, key):
            del key
            for ax in reversed(dp_axes):
                w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
            return w
    pctx = ParallelCtx(tp_axis="model", dp_axis="data",
                       pod_axis="pod" if "pod" in names else None,
                       fsdp=weight_fsdp, gather=gather)
    tp = mesh.shape["model"]

    def body(params, batch, key):
        return prefill_step(params, batch, cfg, pctx, key=key, unroll=unroll)

    a_batch = input_specs(cfg, shape)
    b_ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    batch_spec = jax.tree.map(lambda _: P(b_ax), a_batch)
    p_specs = param_specs(cfg, tp=tp,
                          fsdp_axes=dp_axes if weight_fsdp else None)
    lay = TpLayout.build(cfg, tp)
    st_specs = []
    for pos in range(_period(cfg)):
        if cfg.is_attn_layer(pos):
            kv_sharded = not lay.kv_replicated or lay.kv_single
            sp = P(None, b_ax, None, "model" if kv_sharded else None, None)
            st_specs.append(KVCache(k=sp, v=sp))
        else:
            st_specs.append(SSMState(conv=P(None, b_ax, None, "model"),
                                     ssm=P(None, b_ax, "model", None, None)))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(p_specs, batch_spec, P()),
                       out_specs=(P(b_ax, None), st_specs),
                       check_vma=False)
    a_params = abstract_params(cfg, tp=tp,
                               fsdp_axes=dp_axes if weight_fsdp else None)
    key_arg = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.jit(fn), (a_params, a_batch, key_arg), None


def _build(kind: str, cfg, shape, mesh, *, strategy, drop_rate, plan,
           unroll=False, donate=True):
    if kind == "train":
        return make_train_cell(cfg, shape, mesh, strategy=strategy,
                               drop_rate=drop_rate, plan=plan, unroll=unroll,
                               donate=donate)
    if kind == "prefill":
        return make_prefill_cell(cfg, shape, mesh, unroll=unroll,
                                 weight_fsdp=plan.get("serve_fsdp", False))
    return make_serve_cell(cfg, shape, mesh, unroll=unroll, donate=donate,
                           weight_fsdp=plan.get("serve_fsdp", False),
                           moe_stationary=plan.get("moe_stationary", False))


# --------------------------------------------------------------- cost model
def _metrics(compiled) -> dict[str, float]:
    out: dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes"] = float(ca.get("bytes accessed", 0.0))
        out["transcendentals"] = float(ca.get("transcendentals", 0.0))
    except Exception:
        pass
    for k, v in parse_collective_bytes(compiled.as_text()).items():
        out["coll_" + k] = float(v)
    return out


def cost_model(cfg: ModelConfig, shape: ShapeConfig, mesh, kind: str, *,
               strategy: str, drop_rate: float, plan: dict) -> dict:
    """Fit M(L,B) = fix_base + B*tok_base + L*fix_layer + L*B*tok_layer per
    metric from four shallow unrolled compiles; evaluate at production L,B."""
    from repro.models.transformer import _period
    period = _period(cfg)
    d1, d2 = period, 2 * period
    dp = dp_total_of(mesh)
    if kind == "train" and plan.get("pure_dp"):
        dp *= mesh.shape.get("model", 1)     # both axes carry batch
    b1, b2 = dp, 2 * dp
    if shape.global_batch < dp:              # long_500k: B=1 global
        b1, b2 = 1, 2

    meas: dict[tuple[int, int], dict] = {}
    for d in (d1, d2):
        for b in (b1, b2):
            # dense attention (attn_chunk=0): no scan inside the layer, so
            # cost_analysis sees every attention FLOP (compile-only; the
            # S^2 scores are never allocated)
            c = dataclasses.replace(cfg, n_layers=d, attn_chunk=0)
            s = dataclasses.replace(shape, global_batch=b)
            jf, args, _ = _build(kind, c, s, mesh, strategy=strategy,
                                 drop_rate=drop_rate, plan=plan, unroll=True,
                                 donate=False)
            with mesh:
                compiled = jf.lower(*args).compile()
            meas[(d, b)] = _metrics(compiled)

    keys = set()
    for m in meas.values():
        keys.update(m.keys())
    L, B = cfg.n_layers, shape.global_batch
    out = {}
    for k in sorted(keys):
        f = {db: meas[db].get(k, 0.0) for db in meas}
        lay_b1 = (f[(d2, b1)] - f[(d1, b1)]) / (d2 - d1)
        lay_b2 = (f[(d2, b2)] - f[(d1, b2)]) / (d2 - d1)
        tok_layer = (lay_b2 - lay_b1) / (b2 - b1)
        fix_layer = lay_b1 - b1 * tok_layer
        base_b1 = f[(d1, b1)] - d1 * lay_b1
        base_b2 = f[(d1, b2)] - d1 * lay_b2
        tok_base = (base_b2 - base_b1) / (b2 - b1)
        fix_base = base_b1 - b1 * tok_base
        val = fix_base + B * tok_base + L * (fix_layer + B * tok_layer)
        out[k] = max(val, 0.0)
    out["_model"] = {"depths": [d1, d2], "batches": [b1, b2],
                     "eval_at": [L, B]}
    return out


# ------------------------------------------------------------------ run cell
def run_cell(arch: str, shape_name: str, mesh, *, strategy: str = "optireduce",
             drop_rate: float = 0.01, overrides: dict | None = None,
             with_cost_model: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    plan = dict(PLAN[arch])
    plan.update(overrides or {})
    ok, why = shape_applicable(cfg, shape)
    rec: dict = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "params": count_params(cfg), "active_params": active_params(cfg),
        "strategy": strategy, "drop_rate": drop_rate,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    t0 = time.time()
    try:
        jf, args, extra = _build(shape.kind, cfg, shape, mesh,
                                 strategy=strategy, drop_rate=drop_rate,
                                 plan=plan)
        if shape.kind == "train":
            rec["dp_mode"] = extra.dp_mode
            rec["microbatch"] = extra.microbatch
        elif shape.kind == "decode":
            rec["serve_plan"] = dataclasses.asdict(extra)
        with mesh:
            lowered = jf.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        rec["lower_s"] = round(t1 - t0, 1)
        rec["compile_s"] = round(t2 - t1, 1)
        rec["memory"] = memory_summary(compiled)
        rec["full_hlo_metrics"] = _metrics(compiled)   # body-once caveat
        if with_cost_model:
            rec["cost_model"] = cost_model(cfg, shape, mesh, shape.kind,
                                           strategy=strategy,
                                           drop_rate=drop_rate, plan=plan)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="optireduce")
    ap.add_argument("--drop-rate", type=float, default=0.01)
    ap.add_argument("--dp-mode", default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--seq-chunk", type=int, default=None)
    ap.add_argument("--bucket-elems", type=int, default=None)
    ap.add_argument("--no-cost-model", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    # fail fast (with the registered-name list) before any compile work
    resolve_spec(OptiReduceConfig(strategy=args.strategy))

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    overrides = {}
    for k in ("dp_mode", "microbatch", "seq_chunk", "bucket_elems"):
        v = getattr(args, k)
        if v is not None:
            overrides[k] = v

    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        print(f"=== {arch} x {shape} "
              f"({'2x16x16' if args.multi_pod else '16x16'}) ===", flush=True)
        rec = run_cell(arch, shape, mesh, strategy=args.strategy,
                       drop_rate=args.drop_rate, overrides=overrides,
                       with_cost_model=not args.no_cost_model)
        results.append(rec)
        status = rec["status"]
        mem = rec.get("memory", {}).get("peak_bytes_per_device")
        mem_s = f" peak={mem/2**30:.2f}GiB" if mem else ""
        flops = rec.get("cost_model", {}).get("flops")
        fl_s = f" flops/dev={flops:.3e}" if flops else ""
        print(f"  -> {status}{mem_s}{fl_s} "
              f"(lower {rec.get('lower_s')}s compile {rec.get('compile_s')}s)",
              flush=True)
        if status == "error":
            print("  " + rec["error"], flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"done: {len(results)} cells, {n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())

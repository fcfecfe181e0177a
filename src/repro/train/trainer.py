"""Distributed trainer: OptiReduce integrated as the gradient-sync layer.

Two data-parallel modes (DESIGN §4):

* ``replicated`` — paper-faithful: parameters replicated over the data
  axis/axes; after (micro-batched) backward, the flat gradient stream is
  bucketized (25 MB, like PyTorch DDP) and every bucket runs the selected
  strategy from ``core.allreduce`` (Ring / Tree / BCube / TAR / OptiReduce).

* ``fsdp`` — ZeRO-3 scaling path for the multi-billion-parameter archs:
  every large weight is sharded over the fsdp axes; the scan body gathers it
  just-in-time through a custom-VJP all_gather whose *backward is the
  OptiReduce reduce-scatter* (TAR stage 1 + HT + drop-compensated mean) —
  the paper's collective becomes the ZeRO gradient reduction, and the
  deferred stage-2 broadcast is the next step's weight all_gather.
  Replicated leaves (norms, routers, ...) still sync via bucketed strategy.

Cross-bucket overlap is explicit, not hoped-for: ``TrainConfig.sync_mode``
defaults to ``"pipelined"``, the stage-skewed software schedule in
``core.allreduce.sync_packed`` where iteration k encodes bucket k, exchanges
bucket k-1, and decodes bucket k-2 — the paper's "two in-flight buckets"
(§5) expressed as a depth-2 skew whose in-flight payloads ride in the scan
carry, so the exchange collectives overlap neighboring buckets' codec
kernels by construction (see PERF.md for the skew diagram).

The replicated path also keeps the whole gradient stream in a **packed
arena**: micro-batch accumulation adds each microbatch's grads directly
into the ``(B, bucket_elems)`` batch (no per-leaf zeros tree, one pack
fused into the first add), the arena feeds ``sync_packed`` without a
repack, and the §3.4 guard + global-norm + clip run as one fused reduction
and one multiply over the arena before a single unpack for the optimizer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.allreduce import (OptiReduceConfig, SyncContext, rs_spec,
                                  sync_packed, sync_pytree)
from repro.core.bucket_plan import BucketPlan
from repro.core.pipeline import resolve_spec
from repro.core.safeguards import guard_scale, guard_update
from repro.kernels import runtime as kernel_runtime
from repro.models import lm_loss, param_specs, param_table
from repro.models.parallel import ParallelCtx
from repro.models.transformer import _tree_map_table
from repro.optim.optimizers import OptimizerConfig, make_optimizer


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    sync: OptiReduceConfig = OptiReduceConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    dp_mode: str = "replicated"          # 'replicated' | 'fsdp'
    microbatch: int | None = None        # per-device microbatch (grad accum)
    seq_chunk: int = 1024                # xent sequence chunking
    remat: bool = True
    bucket_elems: int = 6_553_600        # 25 MB fp32 buckets
    # bucket schedule: 'pipelined' (stage-skewed software pipeline, overlaps
    # exchange collectives with neighboring buckets' encode/decode kernels),
    # 'scan' (strictly serial), 'vmap' (batched collectives). All three are
    # bitwise-identical per bucket (pinned by the parity suite).
    sync_mode: str = "pipelined"
    guard: bool = True                   # §3.4 skip-update safeguard
    unroll: bool = False                 # Python-unrolled layers (cost model)
    accum_dtype: Any = jnp.float32       # grad-accumulation dtype (bf16 for
                                         # the 480B arch: 16 GB/chip budget)
    # pure data parallelism on a single-pod mesh: treat the 'model' axis as
    # a second data level (hierarchical 2D TAR over (model, data)) — no TP
    # activation psums at all. The right logical mapping for small archs
    # (§Perf hillclimb H1); single-pod meshes only.
    pure_dp: bool = False
    # sequence parallelism (Megatron-SP): residual stream sharded over tp
    # along seq between blocks; shrinks the per-layer saved residual by
    # 1/tp (§Perf H3 memory lever). Requires seq_len % tp == 0.
    seq_parallel: bool = False
    # host wire transport (DESIGN §7): a transport instance (typically
    # net.WireTransport bridged to a HostRing) that replaces the resolved
    # spec's transport, so stage-1 arrival masks come from a real packet
    # exchange instead of the synthetic drop model. Replicated DP only.
    transport_override: Any = None
    # Pallas kernel dispatch (DESIGN §11): 'interpret' | 'compile' | 'auto'
    # (auto = Mosaic-compile iff running on a TPU backend). None leaves the
    # process-level policy (REPRO_KERNEL_MODE / kernels.runtime) untouched.
    kernel_mode: str | None = None


def mesh_axis_names(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def dp_axes_of(mesh) -> tuple[str, ...]:
    names = mesh_axis_names(mesh)
    return tuple(a for a in ("pod", "data") if a in names)


def make_fsdp_gather(sync_cfg: OptiReduceConfig, fsdp_axes: tuple[str, ...]):
    """(w_local, dim, key) -> w_full gather with OptiReduce reduce-scatter
    as its VJP. Gathers inner axis first so the layout matches a dim sharded
    by P(('pod','data')) (pod-major)."""
    inner_to_outer = tuple(reversed(fsdp_axes))   # ('data', 'pod')
    # one resolved reduce-scatter spec per axis: drops are modeled only on
    # the data axis (the pod hop is the reliable inter-pod aggregation)
    axis_specs = {ax: rs_spec(sync_cfg, with_drops=ax == sync_cfg.data_axis)
                  for ax in fsdp_axes}

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def gather(w, dim, key):
        for ax in inner_to_outer:
            w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
        return w

    def fwd(w, dim, key):
        return gather(w, dim, key), key

    def bwd(dim, key, g):
        ctx = SyncContext(cfg=sync_cfg, key=key)
        out_dtype = g.dtype
        for ax in fsdp_axes:              # outer (pod) first, mirrors fwd
            g = axis_specs[ax].reduce_scatter(g, ax, dim, ctx)
        return (g.astype(out_dtype), None)

    gather.defvjp(fwd, bwd)
    return gather


def _fsdp_leaf_mask(cfg: ModelConfig, tp: int, fsdp_axes):
    """Pytree of bools: which leaves are fsdp-sharded (grads arrive reduced
    through the gather VJP) vs replicated (need explicit bucket sync)."""
    table = param_table(cfg, tp=tp, fsdp_axes=fsdp_axes)
    return _tree_map_table(lambda l: l.fsdp_dim is not None, table)


def _spec_axes(spec: P) -> tuple[str, ...]:
    axes: list[str] = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)


def _summed_groups(pairs) -> dict[tuple[str, ...], jnp.ndarray]:
    """Sum (axes, squared-sum) pairs into one accumulator per distinct
    sharded-axes set (canonicalized by sort, so P('a','b') and P('b','a')
    share a group)."""
    groups: dict[tuple[str, ...], jnp.ndarray] = {}
    for axes, ss in pairs:
        key = tuple(sorted(axes))
        prev = groups.get(key)
        groups[key] = ss if prev is None else prev + ss
    return groups


def _psum_group_total(groups: dict[tuple[str, ...], jnp.ndarray]) -> jnp.ndarray:
    total = jnp.zeros((), jnp.float32)
    for axes, ss in groups.items():
        if axes:
            ss = jax.lax.psum(ss, axes)
        total = total + ss
    return total


def sharded_global_norm(grads, specs) -> jnp.ndarray:
    """Global L2 norm of a gradient tree whose leaves are sharded per
    ``specs`` — per-leaf squared sums are psum'd over exactly the axes each
    leaf is sharded on, so replicated leaves are not double-counted and the
    result is identical on every device.  Leaves are grouped by their
    sharded-axes set and each group issues ONE psum (a model with hundreds
    of leaves pays #distinct-axes-sets collectives, not #leaves)."""
    g_leaves = jax.tree.leaves(grads)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    groups = _summed_groups(
        (_spec_axes(s), jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g, s in zip(g_leaves, s_leaves))
    return jnp.sqrt(_psum_group_total(groups))


def packed_global_norm(batch: jnp.ndarray, plan: BucketPlan,
                       specs) -> jnp.ndarray:
    """:func:`sharded_global_norm` over the packed gradient arena.

    Adjacent leaves sharing a sharded-axes set coalesce into one contiguous
    arena run, so the common all-replicated case is a single fused
    sum-of-squares over the whole flat stream (one HBM pass, no per-leaf
    Python loop) with no psum at all; mixed-sharding trees pay one reduction
    per contiguous run and one psum per distinct axes set.  The zero-padded
    arena tail is excluded (runs stop at ``plan.total`` — after a quantized
    sync the tail carries codec noise, not zeros)."""
    flat = batch.reshape(-1)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    runs: list[tuple[tuple[str, ...], int, int]] = []
    for off, size, s in zip(plan.offsets, plan.sizes, s_leaves):
        axes = tuple(sorted(_spec_axes(s)))
        if runs and runs[-1][0] == axes and runs[-1][2] == off:
            runs[-1] = (axes, runs[-1][1], off + size)
        else:
            runs.append((axes, off, off + size))
    groups = _summed_groups(
        (axes, jnp.sum(jnp.square(flat[a:b].astype(jnp.float32))))
        for axes, a, b in runs)
    return jnp.sqrt(_psum_group_total(groups))


def build_train_step(cfg: ModelConfig, tc: TrainConfig, mesh):
    """Returns (step_fn, shardings) where step_fn(params, opt_state, batch,
    step, key) -> (params, opt_state, metrics), jit-able under ``mesh``."""
    if tc.kernel_mode is not None:
        # set before any kernel shim resolves (trace time), so the whole
        # step traces under one dispatch mode
        kernel_runtime.set_kernel_mode(tc.kernel_mode)
    names = mesh_axis_names(mesh)
    if tc.pure_dp:
        assert "pod" not in names, "pure_dp is a single-pod remap"
        tp_axis = None
        data_axis = "data"
        pod_axis = "model"            # second data level (2D TAR hierarchy)
        dp_axes = ("model", "data")
    else:
        tp_axis = "model" if "model" in names else None
        dp_axes = dp_axes_of(mesh)
        data_axis = "data" if "data" in names else None
        pod_axis = "pod" if "pod" in names else None
    tp = mesh.shape["model"] if tp_axis else 1
    fsdp = tc.dp_mode == "fsdp"
    fsdp_axes = dp_axes if fsdp else None

    sync_cfg = dataclasses.replace(
        tc.sync, data_axis=data_axis or "data",
        pod_axis=pod_axis)
    sync_spec = resolve_spec(sync_cfg)   # fail fast on unknown strategies
    rec_policy = None
    if sync_cfg.recovery != "none":
        from repro.core import recovery as recovery_lib
        rec_policy = recovery_lib.parse(sync_cfg.recovery)
        if fsdp:
            raise ValueError(
                "recovery rides the bucketed sync path (stale arena + "
                "EF residuals are arena-shaped); fsdp grads reduce "
                "through rs_spec — use dp_mode='replicated'")
        if pod_axis is not None:
            raise ValueError(
                "recovery does not compose with the 2D (pod, data) "
                "hierarchy yet: the stale/EF wire-space layout assumes "
                "a single flat TAR shard order")
        if data_axis is None:
            raise ValueError("recovery needs a 'data' mesh axis")
        if rec_policy.ef and tc.transport_override is not None:
            raise ValueError(
                "recovery='ef'/'ef+budget' reconstructs sender-arrival "
                "masks from the synthetic drop model; wire-observed masks "
                "(transport_override) are not reproducible at the sender "
                "— use recovery='stale' with wire transports")
    if tc.transport_override is not None:
        if fsdp:
            raise ValueError("transport_override drives the bucketed sync "
                             "path; fsdp grads reduce through rs_spec "
                             "(wire transports are replicated-DP only)")
        sync_spec = dataclasses.replace(sync_spec,
                                        transport=tc.transport_override)
    opt = make_optimizer(tc.optimizer)
    gather = make_fsdp_gather(sync_cfg, dp_axes) if fsdp else None
    pctx = ParallelCtx(tp_axis=tp_axis, dp_axis=data_axis, pod_axis=pod_axis,
                       fsdp=fsdp, gather=gather,
                       sp=tc.seq_parallel and tp_axis is not None)

    p_specs = param_specs(cfg, tp=tp, fsdp_axes=fsdp_axes)
    fsdp_mask = _fsdp_leaf_mask(cfg, tp, fsdp_axes) if fsdp else None
    batch_dim_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0]) \
        if dp_axes else P()

    def _body(params, opt_state, batch, step, key, rec_state):
        skey = jax.random.fold_in(key, step)
        new_rec = None

        def loss_fn(p, mb):
            return lm_loss(p, mb, cfg, pctx, key=skey,
                           seq_chunk=tc.seq_chunk, remat=tc.remat,
                           unroll=tc.unroll)

        b_local = batch["tokens"].shape[0]
        mb = tc.microbatch or b_local
        n_micro = max(1, b_local // mb)
        ctx = SyncContext(cfg=sync_cfg, key=jax.random.fold_in(skey, 7))
        if n_micro > 1:
            mbatches = jax.tree.map(
                lambda x: x.reshape(n_micro, mb, *x.shape[1:]), batch)

        if fsdp:
            # large leaves arrive pre-reduced through the gather VJP, so the
            # packed arena cannot span the whole stream — keep the per-leaf
            # accumulator and bucket-sync only the replicated leaves
            if n_micro > 1:
                def micro(carry, mbatch):
                    gacc, lacc = carry
                    l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                    gacc = jax.tree.map(
                        lambda a, b_: a + b_.astype(tc.accum_dtype), gacc, g)
                    return (gacc, lacc + l), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, tc.accum_dtype), params)
                (grads, loss), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros(())), mbatches)
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = loss / n_micro
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)

            flat_g, tdef = jax.tree.flatten(grads)
            flat_m = jax.tree.leaves(fsdp_mask)
            small = [g for g, m_ in zip(flat_g, flat_m) if not m_]
            if small:
                synced_small = sync_pytree(small, ctx,
                                           bucket_elems=tc.bucket_elems,
                                           mode=tc.sync_mode, spec=sync_spec)
                it = iter(synced_small)
                flat_g = [next(it) if not m_ else g
                          for g, m_ in zip(flat_g, flat_m)]
            grads = jax.tree.unflatten(tdef, flat_g)
            loss_frac = ctx.loss_fraction()

            # ---- safeguards (§3.4), clip, optimizer ----------------------
            if tc.guard:
                grads, skipped = guard_update(
                    grads, loss_frac, skip_threshold=sync_cfg.skip_threshold)
            else:
                skipped = jnp.zeros((), jnp.bool_)
            gnorm = sharded_global_norm(grads, p_specs)
            clip_scale = jnp.minimum(
                1.0, tc.optimizer.grad_clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * clip_scale.astype(g.dtype),
                                 grads)
        else:
            # ---- packed gradient arena (replicated DP) -------------------
            # the (B, bucket_elems) batch IS the accumulator: micro-batch
            # grads pack straight into it (the pack concat fuses into the
            # add — no per-leaf zeros tree, no second full-gradient copy),
            # the sync engine consumes it without a repack, and guard +
            # global-norm + clip are one fused reduction and one multiply
            # over the arena before the single unpack the optimizer needs
            plan = BucketPlan.for_tree(params, tc.bucket_elems)
            if n_micro > 1:
                def micro(carry, mbatch):
                    acc, lacc = carry
                    l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                    return (acc + plan.pack(g, dtype=tc.accum_dtype),
                            lacc + l), None

                arena0 = jnp.zeros((plan.num_buckets, plan.bucket_elems),
                                   tc.accum_dtype)
                (arena, loss), _ = jax.lax.scan(
                    micro, (arena0, jnp.zeros(())), mbatches)
                # accumulate in accum_dtype (bitwise vs the seed per-leaf
                # accumulator), then take the micro-batch mean in fp32 wire
                # space: identical for fp32 accum, and for bf16 it drops the
                # seed's extra accum-dtype rounding of the mean
                arena = arena.astype(jnp.float32) / n_micro
                loss = loss / n_micro
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                arena = plan.pack(grads)

            if rec_state is not None and "ef" in rec_state:
                # the carried EF residual joins this rank's contribution
                # (per-rank state: each data rank dropped different wire
                # spans last step; the local shard is (1, B, E))
                arena = arena + rec_state["ef"][0]

            synced = sync_packed(arena, ctx, mode=tc.sync_mode,
                                 spec=sync_spec,
                                 stale=None if rec_state is None
                                 else rec_state.get("stale"))
            loss_frac = ctx.loss_fraction()

            if rec_state is not None:
                new_rec = dict(rec_state)
                if "stale" in new_rec:
                    # next step's prediction for lost wire spans: this
                    # step's decoded arena, pre-guard/clip (the sync output
                    # is replicated — every rank caches identical buckets)
                    new_rec["stale"] = synced
                if "ef" in new_rec:
                    n_dp = mesh.shape[data_axis]
                    me = jax.lax.axis_index(data_axis)
                    # residual vs the *pre-update* stale cache: that is
                    # what the fill applied in this rank's stead, so the
                    # carried mass is only the gap (no double counting)
                    new_rec["ef"] = recovery_lib.ef_residual_arena(
                        arena, ctx.key, sync_cfg, n_dp, me,
                        stale=rec_state["stale"])[None]

            # ---- safeguards (§3.4), clip: fused over the arena -----------
            # norm and clip read the fp32 wire values with ONE param-dtype
            # round at unpack; for non-fp32 params the seed instead rounded
            # at unpack and then squared/multiplied in param dtype — same
            # math, one fewer low-bit rounding here (fp32 params: identical)
            if tc.guard:
                gscale, skipped = guard_scale(
                    loss_frac, skip_threshold=sync_cfg.skip_threshold)
            else:
                gscale = jnp.ones(())
                skipped = jnp.zeros((), jnp.bool_)
            # norm-after-guard == guard_scale * norm (the scale is 0 or 1)
            gnorm = gscale * packed_global_norm(synced, plan, p_specs)
            clip_scale = jnp.minimum(
                1.0, tc.optimizer.grad_clip / jnp.maximum(gnorm, 1e-9))
            synced = synced * (gscale * clip_scale)
            grads = plan.unpack(synced)
        lr = jnp.asarray(tc.optimizer.lr, jnp.float32)
        new_params, new_opt = opt.update(grads, opt_state, params, lr, step)

        metrics = {
            "loss": jax.lax.pmean(loss, dp_axes) if dp_axes else loss,
            "grad_norm": gnorm,
            "loss_frac": loss_frac,
            "skipped": skipped.astype(jnp.float32),
        }
        return new_params, new_opt, new_rec, metrics

    if rec_policy is None:
        def body(params, opt_state, batch, step, key):
            p, o, _, m = _body(params, opt_state, batch, step, key, None)
            return p, o, m
    else:
        def body(params, opt_state, rec_state, batch, step, key):
            p, o, r, m = _body(params, opt_state, batch, step, key,
                               rec_state)
            return p, o, r, m

    # optimizer state specs mirror parameter specs leaf-for-leaf
    def opt_specs_like(p_specs_tree, opt_state_tree):
        flat_specs = jax.tree.leaves(p_specs_tree,
                                     is_leaf=lambda x: isinstance(x, P))
        n = len(flat_specs)
        flat_state = jax.tree.leaves(opt_state_tree)
        if len(flat_state) % n == 0 and opt.state_like_params:
            reps = len(flat_state) // n
            specs = flat_specs * reps
            treedef = jax.tree.structure(opt_state_tree)
            return jax.tree.unflatten(treedef, specs)
        return jax.tree.map(lambda _: P(), opt_state_tree)

    def make_step(opt_state_example, batch_example):
        o_specs = opt_specs_like(p_specs, opt_state_example)
        batch_spec = jax.tree.map(lambda _: batch_dim_spec, batch_example)
        metric_specs = {"loss": P(), "grad_norm": P(), "loss_frac": P(),
                        "skipped": P()}
        if rec_policy is None:
            in_specs = (p_specs, o_specs, batch_spec, P(), P())
            out_specs = (p_specs, o_specs, metric_specs)
        else:
            # stale cache is replicated (every rank decodes the same
            # buckets); the EF residual is per-data-rank, leading axis
            rec_specs = {}
            if rec_policy.stale:
                rec_specs["stale"] = P()
            if rec_policy.ef:
                rec_specs["ef"] = P(data_axis)
            in_specs = (p_specs, o_specs, rec_specs, batch_spec, P(), P())
            out_specs = (p_specs, o_specs, rec_specs, metric_specs)
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        shardings = {
            "params": jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            "opt": jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                                is_leaf=lambda x: isinstance(x, P)),
            "batch": jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  batch_spec,
                                  is_leaf=lambda x: isinstance(x, P)),
        }
        if rec_policy is not None:
            shardings["rec"] = jax.tree.map(
                lambda s: NamedSharding(mesh, s), rec_specs,
                is_leaf=lambda x: isinstance(x, P))
        return fn, shardings

    return make_step, opt, pctx


def abstract_opt_state(opt, abstract_params_tree):
    """Optimizer state ShapeDtypeStructs via eval_shape (no allocation)."""
    return jax.eval_shape(opt.init, abstract_params_tree)

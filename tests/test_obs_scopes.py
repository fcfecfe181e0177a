"""Device scopes (DESIGN §12, ``repro.obs.scopes``) and host spans on the
profiler's timeline.

The scopes reach the ``op_name`` of the compiled step's instructions in
every sync schedule and DP mode, leave the optimized program unchanged,
and a traced ``Span`` shows up on the profiler's host plane; the launcher's
``--trace`` writes the Perfetto JSON and a device trace.
"""
import contextlib
import glob
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke
from repro.core.allreduce import OptiReduceConfig
from repro.kernels import runtime
from repro.launch.mesh import make_host_mesh
from repro.models import abstract_params
from repro.obs import scopes, trace
from repro.optim.optimizers import OptimizerConfig
from repro.train.trainer import TrainConfig, build_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9-]*)\(")
OP_NAME = re.compile(r'op_name="(jit\([^"]*)"')
TRIVIAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
SCOPE = {s: re.compile(rf"(^|\(){s}(\)|$)") for s in scopes.ALL}

# (strategy, sync_mode, dp_mode, microbatch)
PATHS = {
    "replicated-optireduce_q-pipelined": ("optireduce_q", "pipelined",
                                          "replicated", None),
    "replicated-optireduce_q-scan-micro": ("optireduce_q", "scan",
                                           "replicated", 2),
    "fsdp-optireduce-pipelined": ("optireduce", "pipelined", "fsdp", None),
}


def _compiled_text(strategy, sync_mode, dp_mode, microbatch) -> str:
    cfg = get_smoke("gpt2-paper")
    tc = TrainConfig(
        sync=OptiReduceConfig(strategy=strategy, drop_rate=0.01,
                              hadamard_block=256),
        optimizer=OptimizerConfig(name="adamw"), sync_mode=sync_mode,
        dp_mode=dp_mode, microbatch=microbatch, seq_chunk=32,
        bucket_elems=1 << 14)
    make_step, opt, _ = build_train_step(cfg, tc, make_host_mesh(dp=1, tp=1))
    params = abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32)
             for k in ("tokens", "labels")}
    step_fn, _ = make_step(opt_state, batch)
    # the quantized paths trace the codec kernels (interpreted here)
    with runtime.impl_scope(
            "interpret" if strategy == "optireduce_q" else "jnp"):
        return jax.jit(step_fn).lower(
            params, opt_state, batch, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()


def _innermost_scope(op_name: str):
    for comp in reversed(op_name.split("/")):
        for s, pat in SCOPE.items():
            if pat.search(comp):
                return s
    return None


@pytest.mark.parametrize("path", list(PATHS))
def test_every_scope_reaches_the_compiled_step(path):
    """Each scope names some instruction of the optimized step, and at most
    5% of the program's own non-trivial instructions (those with an
    ``op_name``; layout copies the compiler adds have none) carry no
    scope."""
    found, unscoped, total = set(), [], 0
    for line in _compiled_text(*PATHS[path]).splitlines():
        m = INSTR.match(line)
        name = OP_NAME.search(line)
        if not m or not name or m.group(1) in TRIVIAL:
            continue
        total += 1
        s = _innermost_scope(name.group(1))
        if s is None:
            unscoped.append(name.group(1))
        found.add(s)
    assert set(scopes.ALL) <= found, set(scopes.ALL) - found
    assert len(unscoped) <= 0.05 * total, (len(unscoped), total,
                                           unscoped[:10])


def test_attention_scope_lies_inside_the_step_scope():
    """The attention core's scope names instructions of the optimized
    step, forward and backward. Each of them also lies under the step's
    forward/backward scope, whose time therefore still holds attention's,
    except the causal mask's constant, which JAX builds outside autodiff
    and which lies under no layer of the step. (The test's name must not
    hold the step scope's name: it reaches the HLO's stack frames.)"""
    attention = re.compile(rf"(^|\(){scopes.ATTENTION}(\)|$)")
    paths = set()
    for line in _compiled_text(
            *PATHS["replicated-optireduce_q-pipelined"]).splitlines():
        name = OP_NAME.search(line)
        if name and any(attention.search(c)
                        for c in name.group(1).split("/")):
            paths.add(name.group(1))
    inside = {p for p in paths if _innermost_scope(p) == scopes.FWD_BWD}
    assert any("transpose(jvp(" in p for p in inside), sorted(paths)[:5]
    outside = paths - inside
    assert all(_innermost_scope(p) is None
               and ("/jit(tril)/" in p or "/jit(_where)/" in p)
               for p in outside), sorted(outside)


def _op_names(text: str, scope: str) -> set:
    """The ``op_name`` paths of a compiled step that have a component
    naming ``scope``."""
    pat = re.compile(rf"(^|\(){scope}(\)|$)")
    return {m.group(1) for m in OP_NAME.finditer(text)
            if any(pat.search(c) for c in m.group(1).split("/"))}


HEAD_MATMUL = re.compile(
    r"^\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([0-9,]*)\]\S* (?:dot|convolution)\(")


def _head_matmuls(text: str) -> list:
    """(output shape with the leading dims merged, op_name) of each matmul
    of a compiled step that carries the ``lm_head`` scope."""
    pat = re.compile(rf"(^|\(){scopes.LM_HEAD}(\)|$)")
    out = []
    for line in text.splitlines():
        m, name = HEAD_MATMUL.match(line), OP_NAME.search(line)
        if m and name and any(pat.search(c)
                              for c in name.group(1).split("/")):
            dims = [int(n) for n in m.group(1).split(",")]
            out.append(((math.prod(dims[:-1]), dims[-1]), name.group(1)))
    return out


@pytest.mark.parametrize("path", ["replicated-optireduce_q-pipelined",
                                  "fsdp-optireduce-pipelined"])
def test_embed_and_lm_head_scopes_lie_inside_the_step_scope(path):
    """The token lookup's and the LM head's scopes name instructions of
    the optimized step, each under the step's forward/backward scope, and
    no instruction of the sync or the optimizer. The lookup's gradient
    is a ``transpose(jvp(...))`` path. The head forms its gradient in the
    forward pass (``lm_loss``'s custom VJP): its three matmuls, the
    logits (b·c, V), dX (b·c, d) and dW (d, V) of one sequence chunk,
    carry the head's scope, and no other matmul does."""
    text = _compiled_text(*PATHS[path])
    for scope in (scopes.EMBED, scopes.LM_HEAD):
        paths = _op_names(text, scope)
        if scope == scopes.EMBED:
            assert any("transpose(jvp(" in p for p in paths), (scope, paths)
        assert {_innermost_scope(p) for p in paths} == {scopes.FWD_BWD}, \
            (scope, sorted(paths)[:5])
    cfg = get_smoke("gpt2-paper")
    bc, d, v = 4 * 32, cfg.d_model, cfg.vocab_size    # batch 4, chunk 32
    matmuls = _head_matmuls(text)
    assert sorted(shape for shape, _ in matmuls) == sorted(
        [(bc, v), (bc, d), (d, v)]), matmuls
    assert {_innermost_scope(p) for _, p in matmuls} == {scopes.FWD_BWD}


def _strip_metadata(hlo: str) -> str:
    """The HLO text without ``metadata={...}`` and the source-location
    tables."""
    hlo = re.sub(r", metadata=\{[^{}]*\}", "", hlo)
    tables = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}
    out, in_table = [], False
    for line in hlo.splitlines():
        if line.strip() in tables:
            in_table = True
            continue
        if in_table and re.match(r"^\s*\d+ ", line):
            continue
        in_table = False
        out.append(line)
    return "\n".join(out)


def test_scopes_change_only_metadata(monkeypatch):
    """The optimized step with every named scope (the step's layers and
    those inside ``fwd_bwd``: attention, embed, lm_head) replaced by a
    no-op is the same program once metadata is stripped."""
    path = PATHS["replicated-optireduce_q-pipelined"]
    scoped = _compiled_text(*path)
    assert "fwd_bwd" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(*path)
    assert "fwd_bwd" not in plain
    for scope in (scopes.EMBED, scopes.LM_HEAD):
        assert _op_names(scoped, scope) and not _op_names(plain, scope)
    assert _strip_metadata(scoped) == _strip_metadata(plain)


def _host_events(xplane_path: str) -> set:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    return {e.name for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}


def test_traced_span_lands_on_the_profiler_host_plane(tmp_path):
    tr = trace.configure(True, capacity=64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("obs_probe_span", step=3) as sp:
            jnp.ones(4).block_until_ready()
            sp.set(done=1)
    finally:
        jax.profiler.stop_trace()
        trace.reset()
    (xplane,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert "obs_probe_span" in _host_events(xplane)
    (rec,) = tr.records()
    assert rec[0] == "X" and rec[3] == "obs_probe_span"
    assert rec[6] == {"step": 3, "done": 1}
    assert trace.span("off") is trace._NOP_SPAN


def test_launcher_trace_writes_host_and_device_traces(tmp_path):
    out = tmp_path / "tr"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_TRACE", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--steps", "12", "--global-batch", "4", "--seq-len", "64",
         "--log-every", "100", "--trace", str(out)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr[-3000:]
    events = json.loads((out / "trace_rank00.json").read_text())
    spans = [e for e in events["traceEvents"] if e.get("ph") == "X"]
    names = [e["name"] for e in spans]
    assert names.count("step") == names.count("batch") == 12
    assert names.count("control") == 12
    steps = [e for e in spans if e["name"] == "step"]
    assert [e["args"]["step"] for e in steps] == list(range(12))
    assert all("loss_frac" in e["args"] for e in steps)
    (xplane,) = glob.glob(str(out / "device/plugins/profile/*/*.xplane.pb"))
    host = _host_events(xplane)
    assert {"train", "batch", "step", "control"} <= host

"""The ``attention_ms`` reader: which ``tf_op`` paths are the attention
core, which operations are the fused kernel's calls, and the traces
recorded on a TPU v5e before the program had an ``attention`` scope."""
import importlib.util
import types

import pytest

from chipbench import manifest, op_scopes
from chipbench import trace_reduce as tr

from .test_chipbench_op_scopes import _run

_spec = importlib.util.spec_from_file_location(
    "attention_ms", manifest.BENCH_DIR / "metrics" / "attention_ms.py")
attention_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(attention_ms)


@pytest.mark.parametrize("path, inside", [
    ("jit(body)/fwd_bwd/while/body/closed_call/checkpoint/attention/"
     "dot_general:", True),
    ("jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/closed_call/"
     "transpose(jvp(attention))/dot_general:", True),
    ("jit(body)/fwd_bwd/jvp(fwd_bwd)/while/body/jvp(attention)/"
     "jvp(causal_attention_fwd)/pallas_call:", True),
    ("jit(body)/attention_decode/dot_general:", False),
    ("jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/mul:", False),
    ("jit(body)/fwd_bwd/causal_attention_bwd/pallas_call:", False),
    (None, False),
], ids=["bare", "transposed", "jvp", "decode", "fwd_bwd", "kernel_name",
        "no_op_name"])
def test_attention_component(path, inside):
    assert attention_ms.in_attention(path) is inside


@pytest.mark.parametrize("text, kernel", [
    ("%jvp_causal_attention_fwd_.1 = (bf16[64,1024,768]) custom-call(...)",
     True),
    ("%transpose_jvp_causal_attention_bwd__.1 = (bf16[64,1024,768]) "
     "custom-call(...)", True),
    ("%fusion.688 = f32[64,12,1024,1024] fusion(%causal_attention_fwd)",
     False),
], ids=["fwd", "bwd", "operand_only"])
def test_kernel_call_names(text, kernel):
    assert attention_ms.is_kernel(text) is kernel


def test_sums_attention_ops_per_step(capsys, monkeypatch):
    """By hand: two traced steps of a kernel call, a dense fusion under the
    scope and an operation outside it."""
    E = tr.Event
    kern = "%jvp_causal_attention_fwd_.1 = (bf16[8]) custom-call(...)"
    ops = [E(kern, 0, 300), E("%fusion.1 = f32[8] fusion(...)", 300, 400),
           E("%fusion.2 = f32[8] fusion(...)", 400, 1000),
           E(kern, 1000, 1300), E("%fusion.1 = f32[8] fusion(...)", 1300,
                                  1400)]
    red = tr.from_events([ops], [E("train", 0, 1000), E("train", 1000,
                                                         2000)])
    tf_ops = {kern: {"jit(body)/fwd_bwd/jvp(attention)/pallas_call:"},
              "%fusion.1 = f32[8] fusion(...)":
              {"jit(body)/fwd_bwd/transpose(jvp(attention))/mul:"},
              "%fusion.2 = f32[8] fusion(...)": {"jit(body)/fwd_bwd/add:"}}
    run = types.SimpleNamespace(
        trace=red, window=types.SimpleNamespace(trace_file="t.xplane.pb"))
    monkeypatch.setattr(op_scopes, "read_tf_ops", lambda _: tf_ops)
    got = manifest.metric_reader("attention_ms")(run)
    assert got == pytest.approx((300 + 100) * 1e-6)
    assert "(75.0%)" in capsys.readouterr().err


def test_no_trace_reads_nothing():
    run = types.SimpleNamespace(trace=None,
                                window=types.SimpleNamespace(trace_file=None))
    assert manifest.metric_reader("attention_ms")(run) is None


@pytest.mark.parametrize("name", ["small_1chip.xplane.pb.gz",
                                  "small_1chip_scoped.xplane.pb.gz"])
def test_traces_without_the_scope_read_nothing(tmp_path, name):
    assert manifest.metric_reader("attention_ms")(_run(tmp_path, name)) \
        is None

"""The ``embed_ms`` and ``lm_head_ms`` readers: which ``tf_op`` paths lie
under the ``embed`` or ``lm_head`` scope, a sum counted by hand, and the
traces recorded on a TPU v5e before the program had either scope."""
import types

import pytest

from chipbench import component_ms, manifest, op_scopes
from chipbench import trace_reduce as tr

from .test_chipbench_op_scopes import _run

READERS = {"embed_ms": "embed", "lm_head_ms": "lm_head"}

PATHS = {
    "bare": "jit(body)/fwd_bwd/{s}/gather:",
    "jvp": "jit(body)/fwd_bwd/jvp(fwd_bwd)/{s}/while/body/closed_call/"
           "checkpoint/dot_general:",
    "transpose": "jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/"
                 "transpose(jvp({s}))/scatter-add:",
    "absent": "jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/mul:",
    "inside_a_word": "jit(body)/fwd_bwd/{s}_extra/mul:",
    "no_op_name": None,
}
INSIDE = {"bare": True, "jvp": True, "transpose": True, "absent": False,
          "inside_a_word": False, "no_op_name": False}


@pytest.mark.parametrize("scope", sorted(READERS.values()))
@pytest.mark.parametrize("case", list(PATHS))
def test_scope_component(scope, case):
    path = PATHS[case] and PATHS[case].format(s=scope)
    assert component_ms.component(scope)(path) is INSIDE[case]


def test_scopes_are_the_programs():
    from repro.obs import scopes
    assert sorted(READERS.values()) == sorted([scopes.EMBED,
                                               scopes.LM_HEAD])
    assert not set(READERS.values()) & set(op_scopes.SCOPES)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_sums_scoped_ops_per_step(metric, monkeypatch):
    """By hand: two traced steps, each of an operation under the scope,
    one under its transpose and one under the other scope."""
    s = READERS[metric]
    other = "lm_head" if s == "embed" else "embed"
    E = tr.Event
    fwd, bwd, out = ("%gather.1 = f32[8] gather(...)",
                     "%scatter.1 = f32[8] scatter(...)",
                     "%fusion.2 = f32[8] fusion(...)")
    ops = [E(fwd, 0, 300), E(bwd, 300, 400), E(out, 400, 1000),
           E(fwd, 1000, 1300), E(bwd, 1300, 1400), E(out, 1400, 2000)]
    red = tr.from_events([ops], [E("train", 0, 1000),
                                 E("train", 1000, 2000)])
    tf_ops = {fwd: {f"jit(body)/fwd_bwd/{s}/gather:"},
              bwd: {f"jit(body)/fwd_bwd/transpose(jvp({s}))/scatter-add:"},
              out: {f"jit(body)/fwd_bwd/{other}/add:"}}
    run = types.SimpleNamespace(
        trace=red, window=types.SimpleNamespace(trace_file="t.xplane.pb"))
    monkeypatch.setattr(op_scopes, "read_tf_ops", lambda _: tf_ops)
    got = manifest.metric_reader(metric)(run)
    assert got == pytest.approx((300 + 100) * 1e-6)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_no_trace_reads_nothing(metric):
    run = types.SimpleNamespace(trace=None,
                                window=types.SimpleNamespace(trace_file=None))
    assert manifest.metric_reader(metric)(run) is None


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("name", ["small_1chip.xplane.pb.gz",
                                  "small_1chip_scoped.xplane.pb.gz"])
def test_traces_without_the_scope_read_nothing(tmp_path, metric, name):
    assert manifest.metric_reader(metric)(_run(tmp_path, name)) is None

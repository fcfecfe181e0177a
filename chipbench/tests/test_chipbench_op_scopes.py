"""The train step's layers read from a device trace by the program's scope
names (``op_scopes.py``): path matching, the metadata decode, and the
readers on traces recorded on a TPU v5e by ``record_trace.py``, before
the program had scopes and after."""
import types

import pytest

from chipbench import manifest, op_scopes
from chipbench import trace_reduce as tr
from chipbench.codec_kernels import kernel_events

from .test_chipbench_trace import _recorded

READERS = ("fwd_bwd_ms", "sync_ms", "optimizer_ms")


@pytest.mark.parametrize("path, scope", [
    ("jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/closed_call/"
     "checkpoint/dot_general:", "fwd_bwd"),
    ("jit(body)/sync/jvp(decode)/mul:", "decode"),
    ("jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/sync/all_to_all:", "sync"),
    ("jit(body)/sync/while/body/encode/jit(_ht_quant)/jit(_ht_quant_call)/"
     "reshape:", "encode"),
    ("jit(body)/optimizer/mul:", "optimizer"),
    ("jit(body)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "concatenate:", op_scopes.UNSCOPED),
    ("jit(body)/fwd_bwd_extra/syncing/add:", op_scopes.UNSCOPED),
    (None, op_scopes.UNSCOPED),
], ids=["wrapped", "wrapped_stage", "innermost_wins", "stage_in_sync",
        "bare", "no_scope", "name_inside_a_word", "no_op_name"])
def test_scope_of_path(path, scope):
    assert op_scopes.scope_of(path) == scope


def test_scope_names_are_the_programs():
    from repro.obs import scopes
    assert op_scopes.SCOPES == scopes.ALL
    assert sorted(s for ss in op_scopes.LAYERS.values() for s in ss) \
        == sorted(scopes.ALL)


def test_split_counts_compiler_made_time_with_the_operation_before():
    """By hand: a loop of two scoped operations with bookkeeping between
    them, a compiler-made copy after each layer, an operation of another
    program, and a text whose two paths disagree."""
    E = tr.Event
    ops = [E("%while.1 = (f32[8]) while(...)", 0, 300),
           E("%fusion.1 = f32[8] fusion(...)", 0, 100),
           E("%fusion.2 = f32[8] fusion(...)", 120, 200),
           E("%copy.1 = f32[8] copy(...)", 200, 300),
           E("%fusion.3 = f32[8] fusion(...)", 300, 350),
           E("%copy.2 = f32[8] copy(...)", 350, 360),
           E("%fusion.4 = f32[8] fusion(...)", 400, 450),
           E("%copy.3 = f32[8] copy(...)", 450, 470),
           E("%fusion.5 = f32[8] fusion(...)", 470, 480)]
    red = tr.from_events([ops], [E("train", 0, 500)])
    tf_ops = {"%fusion.1 = f32[8] fusion(...)":
              {"jit(body)/fwd_bwd/transpose(jvp(fwd_bwd))/while/body/dot:"},
              "%fusion.2 = f32[8] fusion(...)":
              {"jit(body)/fwd_bwd/while/body/mul:"},
              "%fusion.3 = f32[8] fusion(...)":
              {"jit(body)/sync/exchange/all_to_all:"},
              "%fusion.4 = f32[8] fusion(...)": {"jit(make)/gather:"},
              "%fusion.5 = f32[8] fusion(...)":
              {"jit(body)/optimizer/mul:", "jit(body)/sync/add:"}}
    for n in ("%copy.1", "%copy.2", "%copy.3", "%while.1"):
        tf_ops[f"{n} = " + ("(f32[8]) while(...)" if "while" in n
                            else "f32[8] copy(...)")] = {None}
    got = op_scopes.split(red, tf_ops)
    assert got["fwd_bwd"] == 100 + 20 + 80 + 100   # ops, gap, ops, copy
    assert got["exchange"] == 50 + 10
    assert got[op_scopes.UNSCOPED] == 50 + 20 + 10   # make, copy, mixed
    assert got["ambiguous"] == 10
    assert got["compiler_made"] == 100 + 10 + 20
    assert got["between_ops"] == 20
    assert sum(got[s] for s in op_scopes.SCOPES + (op_scopes.UNSCOPED,)) \
        == red.busy_ns(0)


def _run(tmp_path, name):
    path = _recorded(tmp_path, name)
    red = tr.from_events(*tr.read_xplane(path, n_devices=1))
    return types.SimpleNamespace(trace=red,
                                 window=types.SimpleNamespace(
                                     trace_file=path))


def _read(run, metric):
    return manifest.metric_reader(metric)(run)


def test_no_trace_reads_nothing():
    run = types.SimpleNamespace(trace=None,
                                window=types.SimpleNamespace(trace_file=None))
    assert [_read(run, m) for m in READERS] == [None] * 3


def test_unscoped_trace_has_op_names_and_no_scope(tmp_path):
    """The trace recorded before the program had scopes: every operation
    of the traced steps is found in the metadata, most carry an op_name,
    none names a scope, and the readers return nothing."""
    run = _run(tmp_path, "small_1chip.xplane.pb.gz")
    tf_ops = op_scopes.read_tf_ops(run.window.trace_file)
    texts = {e.name for e in run.trace.devices[0]}
    assert texts <= set(tf_ops)
    named = {t for t in texts if tf_ops[t] != {None}}
    assert len(named) > len(texts) / 2
    assert {op_scopes.scope_of(p) for t in texts for p in tf_ops[t]} \
        == {op_scopes.UNSCOPED}
    assert [_read(run, m) for m in READERS] == [None] * 3


def test_scoped_trace_splits_the_busy_time(tmp_path):
    """The same cell recorded with the program's scopes: each layer reads
    a time, the layers plus the unscoped remainder make up the device's
    busy time per step (operations the compiler made and a loop's time
    between its operations counted with the operation before them), and
    the codec kernels lie inside ``sync``."""
    run = _run(tmp_path, "small_1chip_scoped.xplane.pb.gz")
    red = run.trace
    got = {m: _read(run, m) for m in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    ms = op_scopes.per_step_ms(run)
    assert all(ms[s] > 0 for s in ("encode", "exchange", "decode"))
    busy = red.busy_ns(0) * 1e-6 / red.n_steps
    assert sum(got.values()) + ms[op_scopes.UNSCOPED] \
        == pytest.approx(busy, rel=1e-3)
    assert 0 < ms["compiler_made"] + ms["between_ops"] < busy
    assert _read(run, "sync_ms") >= _read(run, "codec_kernel_ms")

    tf_ops = op_scopes.read_tf_ops(run.window.trace_file)
    kernels = kernel_events(red, 0)
    assert kernels
    for _, e in kernels:
        (scope,) = {op_scopes.scope_of(p) for p in tf_ops[e.name]}
        assert scope in op_scopes.LAYERS["sync"], (e.name, scope)

"""The reduction from a profiler trace to busy time, collectives, kernels
and idle gaps: on events counted by hand, and on a trace recorded on a
TPU v5e (``record_trace.py``)."""
import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr
from chipbench.codec_kernels import kernel_events

E = tr.Event
DATA = Path(__file__).parent / "data"


def _step(t0):
    """One step of 700 ns: a fusion, an all-to-all that half overlaps it,
    a loop (container) holding a fusion and an all-gather, then idle."""
    ops = [E("%fusion.1 = f32[8] fusion(...)", t0, t0 + 100),
           E("%all-to-all.1 = f32[8] all-to-all(...)", t0 + 50, t0 + 200),
           E("%while.1 = (f32[8]) while(...)", t0 + 300, t0 + 600),
           E("%fusion.2 = f32[8] fusion(...)", t0 + 300, t0 + 400),
           E("%all-gather.1 = f32[8] all-gather(...)", t0 + 450,
             t0 + 550)]
    host = [E("train", t0, t0 + 700), E("batch", t0, t0 + 10),
            E("dispatch", t0 + 10, t0 + 20),
            E("host_wait", t0 + 20, t0 + 700)]
    return ops, host


def _hand():
    o1, h1 = _step(0)
    o2, h2 = _step(700)
    return tr.from_events([o1 + o2], h1 + h2)


def test_busy_union_and_window_by_hand():
    red = _hand()
    assert red.window == (0, 1400) and red.n_steps == 2
    # per step busy: [0, 200) and [300, 600) -> 500 ns
    assert red.busy_ns(0) == 1000
    assert red.busy_s() == pytest.approx(1000e-9)
    assert len(red.devices[0]) == 8          # the two loops are containers


def test_collectives_and_exposed_part_by_hand():
    red = _hand()
    # all-to-all 150 + all-gather 100, per step
    assert red.op_ns(0, tr.COLLECTIVE) == 500
    # all-to-all [100, 200) has no other op; the all-gather sits alone in
    # its loop (the loop's own event is no op): 100 + 100 per step
    assert red.exposed_ns(0, tr.COLLECTIVE) == 400


def test_idle_gaps_named_by_host_span_by_hand():
    red = _hand()
    gaps = red.idle_gaps(0)
    assert [round(s * 1e9) for _, s in gaps] == [100] * 4
    assert {n for n, _ in gaps} == {"host_wait"}


def test_edge_steps_disturbed_by_the_profiler_are_dropped():
    o1, h1 = _step(0)
    o2, h2 = _step(700)
    o3, h3 = _step(1400)
    late = [E("train", 2100, 6000), E("host_wait", 2120, 6000)]
    o4 = [E("%fusion.9 = f32[8] fusion(...)", 2100, 2600)]
    red = tr.from_events([o1[:1] + o2 + o3 + o4],
                         [E("train", 0, 700)] + h2 + h3 + late)
    assert red.window == (700, 2100) and red.n_steps == 2


def test_kernels_are_matched_by_their_own_name():
    red = tr.from_events(
        [[E("%_ht_quant_call.3 = u8[6400,1024] custom-call(f32[6400,1024]"
            "{1,0:T(8,128)S(1)} %x)", 0, 50),
          E("%fusion.4 = f32[8] fusion(u8[6400,1024] %_ht_quant_call.3)",
            50, 60)]],
        [E("train", 0, 100)])
    assert [k for k, _ in kernel_events(red, 0)] == ["ht_quant"]


def _recorded(tmp_path, name):
    """Unzip a trace recorded on the chip by ``record_trace.py``."""
    out = tmp_path / name.removesuffix(".gz")
    with gzip.open(DATA / name, "rb") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over start (+1) and end (-1) points: a count
    independent of trace_reduce.merge."""
    pts = sorted([(max(e.start, lo), 1) for e in events
                  if e.end > lo and e.start < hi]
                 + [(min(e.end, hi), -1) for e in events
                    if e.end > lo and e.start < hi])
    depth, last, busy = 0, None, 0
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    return busy


def test_recorded_one_chip_trace(tmp_path):
    """Six steps of gpt2-paper's block at d_model 64 with the quantized
    codec kernels on one v5e: one bucket, so each of the five kernels
    runs once a step; a tiny step leaves the device idle most of the
    window, waiting on the host."""
    devices, host = tr.read_xplane(
        _recorded(tmp_path, "small_1chip.xplane.pb.gz"), n_devices=1)
    red = tr.from_events(devices, host)
    assert red.n_steps == 6
    assert red.window == (42766628.0, 61343516.0)
    assert red.busy_ns(0) == _sweep_busy(devices[0], *red.window) == 727478
    kinds = [k for k, _ in kernel_events(red, 0)]
    assert sorted(set(kinds)) == ["dequant_masked_mean", "fwht", "grid_quant",
                                  "ht_amax", "ht_quant"]
    assert all(kinds.count(k) == 6 for k in set(kinds))
    assert red.op_ns(0, tr.COLLECTIVE) == 0
    gaps = red.idle_gaps(0)
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_ns(0) * 1e-9)
    assert gaps[0][0] == "host_wait"

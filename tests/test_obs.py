"""Observability layer (DESIGN §12): trace recorder ring semantics,
disabled-path gating, tail histogram quantile/merge contracts, Perfetto
export schema, report merging, control-plane transition events, and the
end-to-end multiproc-inproc trace -> report flagship.
"""
import json
import math
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.obs import (TailHistogram, TraceSchemaError, to_trace_events,
                       trace, trace_payload, validate_trace, write_trace)
from repro.obs import report as obs_report


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing globally off."""
    trace.reset()
    yield
    trace.reset()


# --------------------------------------------------------------------- tracer
class TestTracer:
    def test_disabled_by_default(self):
        assert trace.get_tracer() is None
        assert not trace.is_enabled()

    def test_configure_enable_disable(self):
        tr = trace.configure(True, capacity=8)
        assert trace.get_tracer() is tr
        assert trace.is_enabled()
        assert trace.configure(False) is None
        assert trace.get_tracer() is None

    def test_records_in_arrival_order(self):
        tr = trace.configure(True, capacity=16)
        tr.complete("round", "wire", ts=1.0, dur=0.5, tid=3,
                    args={"sender": 3})
        tr.event("timeout", "wire", ts=2.0, tid=3)
        tr.counter("loss_frac", 0.25, ts=3.0)
        recs = tr.records()
        assert [r[0] for r in recs] == ["X", "i", "C"]
        ph, ts, dur, name, cat, tid, args = recs[0]
        assert (name, cat, tid) == ("round", "wire", 3)
        assert (ts, dur) == (1.0, 0.5)
        assert args == {"sender": 3}
        assert recs[2][6] == {"value": 0.25}

    def test_negative_duration_clamped(self):
        tr = trace.configure(True, capacity=4)
        tr.complete("x", "wire", ts=0.0, dur=-1.0)
        assert tr.records()[0][2] == 0.0

    def test_ring_wraparound_drops_oldest(self):
        tr = trace.configure(True, capacity=4)
        for i in range(10):
            tr.event(f"e{i}", "trainer", ts=float(i))
        assert len(tr) == 4
        assert tr.dropped == 6
        # oldest surviving first
        assert [r[3] for r in tr.records()] == ["e6", "e7", "e8", "e9"]

    def test_clear_resets_ring_and_dropped(self):
        tr = trace.configure(True, capacity=2)
        for i in range(5):
            tr.event("e", "trainer")
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0
        assert tr.records() == []

    def test_span_nesting_and_set(self):
        tr = trace.configure(True, capacity=8)
        with tr.span("outer", "trainer", tid=1, step=3) as outer:
            with tr.span("inner", "trainer"):
                pass
            outer.set(loss=0.1)
        recs = tr.records()
        # inner exits (and records) first
        assert [r[3] for r in recs] == ["inner", "outer"]
        outer_rec = recs[1]
        assert outer_rec[6] == {"step": 3, "loss": 0.1}
        assert outer_rec[2] >= recs[0][2] >= 0.0  # outer spans inner

    def test_convenience_span_noop_when_disabled(self):
        s = trace.span("x", "trainer")
        # the shared no-op: no allocation, chainable set, context-manages
        assert trace.span("y") is s
        with s.set(a=1) as inner:
            assert inner is s
        trace.event("e")                 # must not raise
        assert trace.get_tracer() is None

    def test_convenience_apis_record_when_enabled(self):
        tr = trace.configure(True, capacity=8)
        with trace.span("step", "trainer", step=1):
            pass
        trace.event("tick", "trainer")
        assert [r[3] for r in tr.records()] == ["step", "tick"]

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            trace.configure(True, capacity=0)

    def test_thread_local_tracer_separates_ranks(self):
        """configure_thread gives each worker thread its own ring — the
        multiproc inproc mode's per-rank separation."""
        global_tr = trace.configure(True, capacity=8)
        seen = {}

        def worker(rank):
            t = trace.configure_thread(True, capacity=8, rank=rank)
            assert trace.get_tracer() is t
            t.event("mine", "trainer", args={"rank": rank})
            seen[rank] = t

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in (1, 2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # the main thread never called configure_thread: still global
        assert trace.get_tracer() is global_tr
        assert len(global_tr) == 0
        for rank in (1, 2):
            recs = seen[rank].records()
            assert len(recs) == 1 and recs[0][6] == {"rank": rank}
            assert seen[rank].rank == rank


# ----------------------------------------------------------------- histograms
class TestTailHistogram:
    def test_empty_is_nan(self):
        h = TailHistogram()
        assert math.isnan(h.quantile(0.5))
        assert math.isnan(h.mean())
        assert h.summary()["count"] == 0

    def test_quantile_within_one_log_bucket_of_numpy(self):
        bpo = 32
        h = TailHistogram(min_value=1e-7, max_value=1e4, bins_per_octave=bpo)
        rng = np.random.default_rng(7)
        vals = rng.lognormal(0.0, 2.0, 5000)
        for v in vals:
            h.record(v)
        tol = 2.0 ** (1.0 / bpo)         # one log-bucket of relative error
        for q in (0.5, 0.9, 0.99, 0.999):
            est = h.quantile(q)
            true = float(np.quantile(vals, q))
            assert true / tol <= est <= true * tol, (q, est, true)

    def test_quantile_clamped_to_observed_envelope(self):
        h = TailHistogram()
        h.record(3.0)
        assert h.quantile(0.0) == 3.0
        assert h.quantile(1.0) == 3.0

    def test_non_finite_sample_rejected(self):
        h = TailHistogram()
        with pytest.raises(ValueError):
            h.record(math.nan)
        with pytest.raises(ValueError):
            h.record(math.inf)

    def test_out_of_range_clamps_and_counts(self):
        h = TailHistogram(min_value=1.0, max_value=10.0)
        h.record(0.01)
        h.record(1000.0)
        assert h.clamped == 2
        assert h.count == 2

    def test_merge_associative_and_commutative(self):
        rng = np.random.default_rng(11)
        chunks = [rng.lognormal(0.0, 1.0, 400) for _ in range(3)]

        def hist(vals):
            h = TailHistogram()
            h.record_many(vals)
            return h

        a, b, c = (hist(ch) for ch in chunks)
        left = a.copy().merge(b).merge(c)            # (a+b)+c
        right = a.copy().merge(b.copy().merge(c))    # a+(b+c)
        swapped = c.copy().merge(b).merge(a)         # c+b+a
        direct = hist(np.concatenate(chunks))
        for other in (right, swapped, direct):
            assert np.array_equal(left.counts, other.counts)
            assert left.count == other.count
            assert left.quantile(0.99) == other.quantile(0.99)

    def test_merge_geometry_mismatch_raises(self):
        with pytest.raises(ValueError):
            TailHistogram(bins_per_octave=32).merge(
                TailHistogram(bins_per_octave=16))

    def test_summary_fields(self):
        h = TailHistogram()
        h.record_many([1.0, 2.0, 3.0, 4.0])
        s = h.summary()
        assert s["count"] == 4
        assert s["min"] == 1.0 and s["max"] == 4.0
        assert s["mean"] == pytest.approx(2.5)
        assert 1.0 <= s["p50"] <= 4.0


# property (satellite): a histogram never loses or invents samples —
# whatever streams in is exactly what count/summary report
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 400), st.floats(0.1, 3.0))
def test_hist_recorded_count_equals_fed(n, sigma):
    h = TailHistogram()
    vals = np.random.default_rng(n).lognormal(0.0, sigma, n)
    h.record_many(vals)
    assert h.count == n
    assert int(h.counts.sum()) == n
    assert h.summary()["count"] == n


# --------------------------------------------------------------------- export
class TestExport:
    def test_tuple_mapping_and_unit_scale(self):
        recs = [("X", 1.5, 0.25, "round", "wire", 3, {"sender": 3}),
                ("i", 2.0, 0.0, "timeout", "wire", 1, None),
                ("C", 3.0, 0.0, "loss", "metrics", 0, {"value": 0.5})]
        evs = to_trace_events(recs, pid=7)
        assert evs[0] == {"name": "round", "cat": "wire", "ph": "X",
                          "ts": 1.5e6, "dur": 0.25e6, "pid": 7, "tid": 3,
                          "args": {"sender": 3}}
        assert evs[1]["s"] == "p" and "dur" not in evs[1]
        assert evs[2]["args"]["value"] == 0.5

    def test_payload_has_process_metadata_and_validates(self):
        tr = trace.configure(True, capacity=8, rank=2)
        tr.event("tick", "policy")
        payload = trace_payload(tr, meta={"transport": "inproc"})
        first = payload["traceEvents"][0]
        assert first["ph"] == "M" and first["args"]["name"] == "rank 2"
        assert payload["otherData"] == {"rank": 2, "dropped": 0,
                                        "transport": "inproc"}
        validate_trace(payload)          # round-trips its own gate

    def test_write_trace_dir_convention(self, tmp_path):
        tr = trace.configure(True, capacity=8, rank=3)
        tr.complete("round", "wire", ts=0.0, dur=0.1)
        path = write_trace(str(tmp_path), tr)
        assert path.endswith("trace_rank03.json")
        with open(path) as fh:
            validate_trace(json.load(fh))

    @pytest.mark.parametrize("mutate,frag", [
        (lambda p: p.pop("traceEvents"), "traceEvents"),
        (lambda p: p["traceEvents"][1].pop("name"), "name"),
        (lambda p: p["traceEvents"][1].update(ph="Z"), "ph"),
        (lambda p: p["traceEvents"][1].update(ts=math.nan), "ts"),
        (lambda p: p["traceEvents"][1].update(pid="0"), "pid"),
        (lambda p: p["traceEvents"][1].update(dur=-1.0), "dur"),
        (lambda p: p["traceEvents"][1].update(args=[1]), "args"),
    ])
    def test_validate_rejects_malformed(self, mutate, frag):
        tr = trace.configure(True, capacity=8)
        tr.complete("round", "wire", ts=0.0, dur=1.0)
        payload = trace_payload(tr)
        mutate(payload)
        with pytest.raises(TraceSchemaError, match=frag):
            validate_trace(payload)

    def test_validate_rejects_nonfinite_counter(self):
        payload = {"traceEvents": [
            {"name": "c", "cat": "m", "ph": "C", "ts": 0.0, "pid": 0,
             "tid": 0, "args": {"value": math.inf}}]}
        with pytest.raises(TraceSchemaError, match="value"):
            validate_trace(payload)


# --------------------------------------------------------------------- report
def _payload_for(rank, round_durs_us, events=()):
    """Hand-build a validated per-rank payload (µs already)."""
    evs = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
            "ts": 0, "args": {"name": f"rank {rank}"}}]
    for i, d in enumerate(round_durs_us):
        evs.append({"name": "round", "cat": "wire", "ph": "X",
                    "ts": float(i), "dur": float(d), "pid": rank, "tid": 0})
    for name, cat, ts, args in events:
        evs.append({"name": name, "cat": cat, "ph": "i", "s": "p",
                    "ts": float(ts), "pid": rank, "tid": 0, "args": args})
    return validate_trace({"traceEvents": evs,
                           "otherData": {"rank": rank, "dropped": 0}})


class TestReport:
    def test_merge_tables_and_timeline(self):
        p0 = _payload_for(0, [100.0] * 99 + [5000.0],
                          events=[("eject", "policy", 7.0, {"peer": 3})])
        p1 = _payload_for(1, [110.0] * 100,
                          events=[("timeout", "wire", 3.0, {"round": 2})])
        rep = obs_report.merge_report([p0, p1])
        assert rep["ranks"] == [0, 1]
        tab = rep["tables"]["round"]
        assert tab["merged"]["count"] == 200
        assert set(tab["per_rank"]) == {"0", "1"}
        # the one 5ms outlier in 200 samples is the p999, not the p50
        assert tab["merged"]["p50"] < 200.0
        assert tab["merged"]["p999"] > 1000.0
        names = [(e["name"], e["rank"]) for e in rep["timeline"]]
        assert ("eject", 0) in names and ("timeout", 1) in names
        # timeline sorted by ts within each category (clock domain)
        for cat in ("policy", "wire"):
            ts = [e["ts"] for e in rep["timeline"] if e["cat"] == cat]
            assert ts == sorted(ts)

    def test_merged_equals_per_rank_merge(self):
        """The cross-rank table is the histogram-merge of the per-rank
        ones (associativity contract end to end)."""
        rng = np.random.default_rng(5)
        durs = [rng.lognormal(5.0, 1.0, 300) for _ in range(3)]
        rep = obs_report.merge_report(
            [_payload_for(r, d) for r, d in enumerate(durs)])
        manual = TailHistogram(**obs_report._HIST_KW)
        manual.record_many(np.concatenate(durs))
        assert rep["tables"]["round"]["merged"] == manual.summary()

    def test_empty_tables_skipped(self):
        # zero-duration spans (virtual clock) contribute nothing
        p = _payload_for(0, [0.0, 0.0])
        rep = obs_report.merge_report([p])
        assert rep["tables"] == {}

    def test_discover_and_cli(self, tmp_path, capsys):
        for rank in range(2):
            tr = trace.configure(True, capacity=32, rank=rank)
            for i in range(5):
                tr.complete("round", "wire", ts=float(i), dur=0.001,
                            tid=0, args={"round": i})
            tr.event("hadamard", "policy", ts=2.5,
                     args={"on": True, "cause": "loss_threshold"})
            write_trace(str(tmp_path), tr)
        trace.reset()
        found = obs_report.discover([str(tmp_path)])
        assert [p[-17:] for p in found] == ["trace_rank00.json",
                                           "trace_rank01.json"]
        rep = obs_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rep["tables"]["round"]["merged"]["count"] == 10
        assert "round completion time" in out
        assert "hadamard" in out
        rep2 = obs_report.main([str(tmp_path), "--json"])
        assert json.loads(capsys.readouterr().out)["ranks"] == [0, 1]
        assert rep2["ranks"] == [0, 1]

    def test_discover_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            obs_report.discover([str(tmp_path)])

    def test_load_trace_names_bad_file(self, tmp_path):
        bad = tmp_path / "trace_rank00.json"
        bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
        with pytest.raises(TraceSchemaError, match="trace_rank00"):
            obs_report.load_trace(str(bad))

    def test_render_reports_dropped_records(self):
        p = _payload_for(0, [10.0])
        p["otherData"]["dropped"] = 42
        rep = obs_report.merge_report([p])
        assert rep["dropped_records"] == 42
        assert "42 records dropped" in obs_report.render(rep)


# ----------------------------------------------- control-plane instrumentation
class TestControlPlaneEvents:
    def _plane(self, n=4):
        from repro.runtime import ControlPlane
        return ControlPlane.create(
            n, detector_kw=dict(alpha=0.5, patience=2, cooldown=4,
                                probation=2))

    def _policy_events(self, tr):
        return [(r[3], r[6]) for r in tr.records() if r[4] == "policy"]

    def test_eject_emits_policy_event_with_cause(self):
        from repro.runtime import StepTelemetry
        tr = trace.configure(True, capacity=256)
        plane = self._plane()
        times = (1.0, 1.0, 1.0, 9.0)
        for step in range(12):
            plane.observe(StepTelemetry(step=step, loss_frac=0.0,
                                        peer_stage_times=times))
        evs = self._policy_events(tr)
        ejects = [a for n_, a in evs if n_ == "eject"]
        assert ejects and ejects[0]["peer"] == 3
        assert ejects[0]["cause"] == "score" and ejects[0]["from"] == "active"
        # the policy flip itself is summarized too
        assert any(n_ == "policy_change" for n_, _ in evs)

    def test_probation_and_readmit_events(self):
        from repro.runtime import StepTelemetry
        tr = trace.configure(True, capacity=512)
        plane = self._plane()
        step = 0
        for _ in range(10):                       # eject peer 3
            plane.observe(StepTelemetry(step=step, loss_frac=0.0,
                                        peer_stage_times=(1., 1., 1., 9.)))
            step += 1
        for _ in range(20):                       # heal: probation+readmit
            plane.observe(StepTelemetry(step=step, loss_frac=0.0,
                                        peer_stage_times=(1., 1., 1., 1.)))
            step += 1
        names = [n_ for n_, _ in self._policy_events(tr)]
        assert names.index("eject") < names.index("probation") \
            < names.index("readmit")

    def test_membership_event(self):
        tr = trace.configure(True, capacity=64)
        plane = self._plane()
        assert plane.apply_membership("death", 2, generation=3)
        evs = self._policy_events(tr)
        assert evs and evs[-1][0] == "membership"
        assert evs[-1][1]["kind"] == "death" and evs[-1][1]["peer"] == 2
        assert evs[-1][1]["generation"] == 3

    def test_no_tracer_no_events_same_decisions(self):
        """Tracing off must not change control behaviour (pure observer)."""
        from repro.runtime import StepTelemetry

        def run(traced):
            trace.reset()
            if traced:
                trace.configure(True, capacity=512)
            plane = self._plane()
            flips = []
            for step in range(15):
                flips.append(plane.observe(
                    StepTelemetry(step=step, loss_frac=0.0,
                                  peer_stage_times=(1., 1., 1., 9.))))
            return flips, plane.policy()

        assert run(False) == run(True)


# ------------------------------------------------------------------- flagship
@pytest.mark.slow
def test_multiproc_inproc_trace_roundtrip(tmp_path):
    """The acceptance criterion: a 4-peer inproc multiproc run with
    --trace-dir emits one valid Perfetto JSON per rank, and the merged
    report reproduces round-time tails and control-plane transitions."""
    from repro.launch.multiproc import main as mp_main

    td = str(tmp_path / "traces")
    report = mp_main(["--backend", "inproc", "--nprocs", "4",
                      "--steps", "3", "--elems", "2048",
                      "--drop-rate", "0.02", "--trace-dir", td])
    assert len(report["traces"]) == 4
    payloads = [obs_report.load_trace(p) for p in report["traces"]]
    assert sorted((p["otherData"] or {})["rank"] for p in payloads) \
        == [0, 1, 2, 3]
    rep = obs_report.merge_report(payloads)
    assert rep["ranks"] == [0, 1, 2, 3]
    # every rank observed 3 steps x (n-1) senders x rounds >= 1 — the
    # merged round table must carry all ranks and a finite tail
    tab = rep["tables"]["round"]
    assert set(tab["per_rank"]) == {"0", "1", "2", "3"}
    assert tab["merged"]["count"] >= 4 * 3
    assert math.isfinite(tab["merged"]["p999"])
    # with 2% drops the loss controllers move: policy events recorded
    cats = {e["cat"] for e in rep["timeline"]}
    assert "policy" in cats
    text = obs_report.render(rep)
    assert "control timeline" in text

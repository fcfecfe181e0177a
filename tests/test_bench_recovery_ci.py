"""CI smoke for the loss-recovery bench: ``python -m benchmarks.run
--only bench_recovery`` in quick mode must keep producing the schema the
PR-over-PR trajectory diffs consume — cumulative-update MSE medians per
(pattern, rate, mechanism) with ``_mse_iqr`` dispersion siblings — and the
semantic claim DESIGN §8 makes: error feedback strictly beats zero-fill
at every swept loss rate, including 1% bursty loss.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_recovery.json baseline.
"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_recovery_quick_schema_and_ef_dominance(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_recovery"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr

    path = tmp_path / "BENCH_recovery.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_recovery"}

    keys = set(payload) - {"_meta"}
    cells = [f"recovery/{pat}_r{pct}" for pat in ("bernoulli", "burst")
             for pct in (1, 5)]
    for cell in cells:
        for mech in ("zero", "stale", "ef"):
            assert f"{cell}/{mech}_mse_median" in keys, (cell, mech)
            assert f"{cell}/{mech}_mse_iqr" in keys, (cell, mech)

    # the acceptance claim: EF strictly dominates zero-fill at every rate
    # — including >= 1% burst loss — because carried residuals re-apply the
    # dropped mass instead of letting the error random-walk
    for cell in cells:
        zero = payload[f"{cell}/zero_mse_median"]["value"]
        ef = payload[f"{cell}/ef_mse_median"]["value"]
        assert ef < zero, (cell, ef, zero)

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_recovery.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_recovery"

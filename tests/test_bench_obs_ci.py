"""CI smoke for the observability bench: ``python -m benchmarks.run --only
bench_obs`` in quick mode must keep producing the overhead rows the
PR-over-PR trajectory diffs (and the DESIGN §12 overhead contract) consume
— the disabled-gate / enabled-record / histogram primitives and the traced
vs untraced wire-step pair, each median with its ``_iqr_us`` dispersion
sibling.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_obs.json baseline.
"""
import json
import math
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MEDIANS = (
    "obs/disabled_gate_median_us",
    "obs/enabled_complete_median_us",
    "obs/enabled_event_median_us",
    "obs/hist_record_median_us",
    "obs/wire_step_untraced_median_us",
    "obs/wire_step_traced_median_us",
)


@pytest.mark.slow
def test_bench_obs_quick_schema(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_TRACE", None)          # the bench manages tracing itself
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_obs"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FAILED" not in proc.stdout, proc.stdout

    path = tmp_path / "BENCH_obs.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_obs"}

    keys = set(payload) - {"_meta"}
    for key in _MEDIANS:
        assert key in keys, key
        sibling = key[:-len("_median_us")] + "_iqr_us"
        assert sibling in keys, sibling
    assert "obs/wire_step_overhead_pct" in keys
    for key in keys:
        value = payload[key]["value"]
        assert isinstance(value, (int, float)) and math.isfinite(value), key
    # overhead contract sanity: the disabled gate is sub-microsecond per
    # call site even on a loaded CI box (the design budget is tens of ns)
    assert payload["obs/disabled_gate_median_us"]["value"] < 1.0

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_obs.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_obs"

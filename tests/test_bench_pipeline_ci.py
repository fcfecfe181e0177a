"""CI smoke for the bench harness: ``python -m benchmarks.run --only
bench_pipeline`` in quick mode must keep producing the schema the
PR-over-PR trajectory diffs consume — the ``pipeline/pipelined_*`` rows,
the dispersion sibling of every steady row, and the
``pipelined_vs_scan_steady_pct`` headline — so the harness cannot rot
silently between PRs.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_pipeline.json baseline.
"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_pipeline_quick_schema(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_pipeline"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FAILED" not in proc.stdout, proc.stdout

    path = tmp_path / "BENCH_pipeline.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_pipeline"}

    keys = set(payload) - {"_meta"}
    # the pipelined schedule rows the acceptance criteria pin
    for b in (1, 2, 4, 8):
        for suffix in ("trace_ms", "hlo_kb", "steady_us", "steady_iqr_us"):
            assert f"pipeline/pipelined_B{b}_{suffix}" in keys, (b, suffix)
    assert "pipeline/pipelined_vs_scan_steady_pct" in keys
    assert "pipeline/pipelined_per_bucket_us" in keys
    # every steady row carries its dispersion sibling (run.py schema)
    for key in keys:
        if key.endswith("_steady_us"):
            assert key[:-len("_steady_us")] + "_steady_iqr_us" in keys, key
    # values are finite numbers (mirrors run.py's gate end-to-end)
    for key in keys:
        value = payload[key]["value"]
        assert isinstance(value, (int, float)), key

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_pipeline.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_pipeline"

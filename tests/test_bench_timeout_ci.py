"""CI smoke for the timeout/ejection bench: ``python -m benchmarks.run
--only bench_timeout`` in quick mode must keep producing the schema the
PR-over-PR trajectory diffs consume — the early-timeout ablation rows, the
``ejection_vs_wait`` ablation, and an ``_iqr_ms`` dispersion sibling for
every median row — so the harness cannot rot silently between PRs.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_timeout.json baseline.
"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_timeout_quick_schema(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_timeout"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FAILED" not in proc.stdout, proc.stdout

    path = tmp_path / "BENCH_timeout.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_timeout"}

    keys = set(payload) - {"_meta"}
    # the early-timeout ablation and the ejection_vs_wait ablation rows
    for key in ("timeout/tb_only_median_ms", "timeout/early_tc_median_ms",
                "timeout/time_reduction_pct",
                "timeout/wait_for_all_median_ms",
                "timeout/ejection_median_ms", "timeout/ejection_vs_wait_pct",
                "timeout/ejection_drop_frac",
                "timeout/rebalance_median_ms",
                "timeout/rebalance_eject_median_ms",
                "timeout/rebalance_wait_median_ms",
                "timeout/rebalance_vs_eject_pct",
                "timeout/rebalance_contrib_frac"):
        assert key in keys, key
    # every median row carries its dispersion sibling (run.py schema)
    for key in keys:
        if key.endswith("_median_ms"):
            assert key[:-len("_median_ms")] + "_iqr_ms" in keys, key
    # values are finite numbers (mirrors run.py's gate end-to-end)
    for key in keys:
        value = payload[key]["value"]
        assert isinstance(value, (int, float)), key

    # the ablation's headline claims hold in the emitted numbers: ejection
    # beats wait-for-all under the persistent straggler, drops stay bounded
    assert payload["timeout/ejection_median_ms"]["value"] < \
        payload["timeout/wait_for_all_median_ms"]["value"]
    assert 0.0 <= payload["timeout/ejection_drop_frac"]["value"] < 0.01

    # rebalance ablation (ISSUE 8 acceptance): straggler-proportional
    # shards land within 15% of ejection's median while the straggler
    # keeps a nonzero gradient contribution (ejection zeroes it)
    reb = payload["timeout/rebalance_median_ms"]["value"]
    ej = payload["timeout/rebalance_eject_median_ms"]["value"]
    wait = payload["timeout/rebalance_wait_median_ms"]["value"]
    assert reb <= 1.15 * ej, (reb, ej)
    assert reb < wait, (reb, wait)
    assert payload["timeout/rebalance_contrib_frac"]["value"] > 0.05

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_timeout.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_timeout"

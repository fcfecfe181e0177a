"""CI smoke for the wire-transport bench: ``python -m benchmarks.run
--only bench_transport`` in quick mode must keep producing the schema the
PR-over-PR trajectory diffs consume — inproc/udp round-latency medians with
``_iqr_ms`` dispersion siblings, the scripted-loss fidelity sweep, and the
reassembly-overhead rows — so the harness cannot rot silently between PRs.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_transport.json baseline.
"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
@pytest.mark.net
def test_bench_transport_quick_schema(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_transport"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FAILED" not in proc.stdout, proc.stdout

    path = tmp_path / "BENCH_transport.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_transport"}

    keys = set(payload) - {"_meta"}
    for key in ("transport/inproc_64KB_roundtrip_median_ms",
                "transport/udp_64KB_roundtrip_median_ms",
                "transport/loss_sweep_rate_0_observed",
                "transport/loss_sweep_rate_0.01_observed",
                "transport/loss_sweep_rate_0.05_observed",
                "transport/reassembly_64KB_median_ms",
                "transport/inproc_scale_16p_median_ms",
                "transport/inproc_scale_32p_median_ms",
                "transport/inproc_scale_64p_median_ms",
                "transport/udp_scale_16p_median_ms",
                "transport/udp_scale_32p_median_ms"):
        assert key in keys, key
    # every median row carries its dispersion sibling (run.py schema)
    for key in keys:
        if key.endswith("_median_ms"):
            assert key[:-len("_median_ms")] + "_iqr_ms" in keys, key
    for key in keys:
        assert isinstance(payload[key]["value"], (int, float)), key

    # loss fidelity: the observed loss_fraction is monotone in the
    # scripted rate and zero at rate 0
    l0 = payload["transport/loss_sweep_rate_0_observed"]["value"]
    l1 = payload["transport/loss_sweep_rate_0.01_observed"]["value"]
    l5 = payload["transport/loss_sweep_rate_0.05_observed"]["value"]
    assert l0 == 0.0
    assert 0.0 < l1 < l5

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_transport.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_transport"

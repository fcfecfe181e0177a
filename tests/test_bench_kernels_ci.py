"""CI smoke for the kernel bench: ``python -m benchmarks.run --only
bench_kernels`` in quick mode must keep producing the schema the
PR-over-PR trajectory diffs consume — the parity rows for every kernel
family, an ``*_interpret_steady_us`` device row per family with its
dispersion sibling, and (only when a TPU backend exists) the
``*_compiled_steady_us`` rows. Off-TPU the compiled keys must simply be
absent — never present-but-bogus — so the checked-in CPU baseline stays
comparable across PRs.

Writes to a tmpdir via ``REPRO_BENCH_DIR`` so a test run never rewrites the
checked-in BENCH_kernels.json baseline.
"""
import json
import math
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one device-timed row per kernel family (quick-mode key set)
_DEVICE_FAMILIES = (
    "kernels/fwht_b1024",
    "kernels/masked_sum_L16384",
    "kernels/quant_b8",
    "kernels/ht_amax_b1024",
    "kernels/ht_quant_b1024",
    "kernels/dequant_masked_mean_L8192",
)


@pytest.mark.slow
def test_bench_kernels_quick_schema(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # schema run, never the chip
    env.pop("XLA_FLAGS", None)
    src = os.path.join(_REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, src, env.get("PYTHONPATH", "")])
    env["REPRO_BENCH_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "bench_kernels"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FAILED" not in proc.stdout, proc.stdout

    path = tmp_path / "BENCH_kernels.json"
    assert path.exists(), "run.py did not honor REPRO_BENCH_DIR"
    payload = json.loads(path.read_text())
    assert payload["_meta"] == {"mode": "quick", "bench": "bench_kernels"}

    keys = set(payload) - {"_meta"}
    # parity rows: the jnp-form timing row per family carries the
    # pallas-vs-oracle parity number in its derived column
    for key, tag in (("kernels/fwht_b1024_float32", "pallas_vs_oracle_err"),
                     ("kernels/masked_sum_L16384", "pallas_vs_oracle_err"),
                     ("kernels/quant_b8", "pallas_vs_oracle_maxdiff"),
                     ("kernels/ht_quant_b1024", "pallas_vs_oracle_maxdiff"),
                     ("kernels/dequant_masked_mean_L8192",
                      "pallas_vs_oracle_err")):
        assert key in keys, key
        assert tag in payload[key]["derived"], (key, payload[key]["derived"])

    # device rows: interpret timings exist everywhere; compiled timings are
    # TPU-only and must be absent (not zero/NaN) on other backends
    import jax
    on_tpu = jax.default_backend() == "tpu"
    for fam in _DEVICE_FAMILIES:
        assert f"{fam}_interpret_steady_us" in keys, fam
        assert f"{fam}_interpret_steady_iqr_us" in keys, fam
        if not on_tpu:
            assert f"{fam}_compiled_steady_us" not in keys, fam
        else:
            assert f"{fam}_compiled_steady_us" in keys, fam
            assert f"{fam}_compiled_steady_iqr_us" in keys, fam

    # every steady row carries its dispersion sibling (run.py schema)
    for key in keys:
        if key.endswith("_steady_us"):
            assert key[:-len("_steady_us")] + "_steady_iqr_us" in keys, key
    # values are finite numbers (mirrors run.py's gate end-to-end)
    for key in keys:
        value = payload[key]["value"]
        assert isinstance(value, (int, float)) and math.isfinite(value), key

    # the checked-in baseline at the repo root was NOT rewritten
    repo_json = os.path.join(_REPO, "BENCH_kernels.json")
    if os.path.exists(repo_json):
        with open(repo_json) as fh:
            baseline = json.load(fh)
        assert baseline["_meta"]["bench"] == "bench_kernels"

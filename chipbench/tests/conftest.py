"""Shared fixtures of the benchmark's CPU tests."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

# gpt2-paper's block at a width a CPU test can hold
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512}


@pytest.fixture
def no_persistent_cache(monkeypatch):
    """Keep the harness off the persistent compile cache and restore the
    process-wide JAX settings it touches."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.setattr(
        "repro.launch.compile_cache.enable_compile_cache", lambda: "off")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def small_bench(tmp_path: Path, *, chips: int = 1, dtype: str = "float32",
                global_batch: int = 4, seq_len: int = 64,
                limits: dict | None = None, **path) -> tuple[Path, Path]:
    """A throwaway benchmark root holding one small cell, ``small``, made
    only of files: a configuration, a traffic mix, a workload (the
    ``gpt2-q8k-1chip`` cell's, with ``path`` overriding its keys) and a
    BENCHMARK.json entry. Returns (root, bench_dir)."""
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench" / "metrics", bench / "metrics")
    for d in ("configs", "traffic", "workloads"):
        (bench / d).mkdir()
    config = json.loads((ROOT / "chipbench/configs/gpt2-paper.json")
                        .read_text())
    config["name"] = "gpt2-small"
    config["model"].update(SMALL, param_dtype=dtype)
    (bench / "configs/gpt2-small.json").write_text(json.dumps(config))
    from chipbench import manifest
    traffic = dict(manifest.load_cell("gpt2-q8k-1chip").traffic)
    traffic.update(global_batch=global_batch, seq_len=seq_len)
    (bench / "traffic/small-mix.json").write_text(json.dumps(traffic))
    wl = json.loads((ROOT / "chipbench/workloads/gpt2-q8k-1chip.json")
                    .read_text())
    wl.update(path)
    if limits is not None:
        wl["limits"] = limits
    (bench / "workloads/small.json").write_text(json.dumps(wl))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "gpt2-small", "source": "test",
                            "file": "chipbench/configs/gpt2-small.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "small", "config": "gpt2-small",
                              "traffic": "small-mix", "chips": chips,
                              "why": "test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path, bench

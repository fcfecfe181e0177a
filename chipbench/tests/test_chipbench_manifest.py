"""BENCHMARK.json against the benchmark's contract, and the loader."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import manifest

from .conftest import ROOT, small_bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["config"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        assert len({g["name"] for g in group}) == len(group)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_cells_report_what_it_moves(metric):
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_load(cell):
    c = manifest.load_cell(cell)
    assert c.chips in (1, 4)
    limits = set(c.workload["limits"])
    assert "failed_steps" in limits and len(limits) >= 2
    assert limits <= {"loss_gap", "grad_gap", "change_gap", "failed_steps"}
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.metric_reader(m["name"]))


def test_four_chip_cells_are_few():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_configs_list_what_they_cut():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        changed = sorted(k for k, v in cfg["published"].items()
                         if cfg["model"][k] != v)
        assert changed == sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))


def test_new_cell_is_found_by_name_without_harness_edit(tmp_path):
    root, bench = small_bench(tmp_path)
    cell = manifest.load_cell("small", root=root, bench_dir=bench)
    assert cell.model["d_model"] == 64 and cell.traffic["seq_len"] == 64
    assert cell.workload["strategy"] == "optireduce_q"
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]}
    with pytest.raises(KeyError):
        manifest.load_cell("absent", root=root, bench_dir=bench)


def test_command_fails_off_the_chip_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed",
                              str(2**33 + 1), "--seconds", "1",
                              "--trace", "0"]
    assert cmd[0] == "python3"
    p = subprocess.run([sys.executable] + cmd[1:], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr

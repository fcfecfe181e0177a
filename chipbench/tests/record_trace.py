"""Record the small trace that ``test_chipbench_trace.py`` reduces.

    python chipbench/tests/record_trace.py <chips> <out.xplane.pb.gz>

Runs gpt2-paper's block at a tiny width (2 layers, d_model 64, vocab 512)
through the harness on ``chips`` TPU chips with the quantized codec kernels
and traces 4 steps, then writes the ``.xplane.pb`` gzipped.
"""
import gzip
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512}


def main(chips: int, out: str) -> None:
    from chipbench import harness, manifest

    devices = harness.require_chips(chips)
    base = manifest.load_cell("gpt2-q8k-1chip")
    cell = manifest.Cell(
        name="trace-sample", chips=chips,
        model={**base.model, **SMALL}, config=base.config,
        traffic={**base.traffic, "global_batch": 2 * chips, "seq_len": 128},
        workload=base.workload, end_to_end=(), per_layer=())
    prog = harness.build_program(cell, seed=5)
    harness.checked_steps(prog, cell, 5)
    harness.TRACED_STEPS = 4
    win = harness.measure_window(prog, 1.5, trace=True)
    print(f"{win.steps} steps, trace {win.trace_file}, devices {devices}")
    with open(win.trace_file, "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)


if __name__ == "__main__":
    main(int(sys.argv[1]), sys.argv[2])

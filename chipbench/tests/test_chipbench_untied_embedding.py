"""An untied bf16 model on Zipf tokens through the harness's path agrees
with the plain reference on the embedding's gradient: the lookup's
gradient is summed in float32, as the reference sums it."""
import json
import statistics

from chipbench import harness, manifest, reference

from .conftest import small_bench

SEED = 2**33 + 17
# the proven cell's limit; a bf16 scatter-add of the lookup's gradient
# read 1.1e-2 here
LIMIT = manifest.load_cell("gpt2-q8k-1chip").workload["limits"]["grad_gap"]


def test_embedding_gradient_matches_the_reference(tmp_path,
                                                  no_persistent_cache):
    """d_model 128, a 4,096-token vocabulary with its own head, 8 x 256
    Zipf(1.1) tokens (the most frequent id about 370 times), unquantized
    OptiReduce on one chip: the embedding leaf's gap in the first step's
    gradient, measured as ``grad_gap`` measures a leaf, is under the
    limit."""
    root, bench = small_bench(tmp_path, dtype="bfloat16", global_batch=8,
                              seq_len=256, strategy="optireduce",
                              use_kernels=False)
    path = bench / "configs/gpt2-small.json"
    config = json.loads(path.read_text())
    config["model"].update(d_model=128, n_heads=4, n_kv_heads=4,
                           head_dim=32, d_ff=256, vocab_size=4096,
                           tie_embeddings=False)
    path.write_text(json.dumps(config))
    cell = manifest.load_cell("small", root=root, bench_dir=bench)
    assert cell.model["param_dtype"] == "bfloat16"

    prog = harness.build_program(cell, SEED)
    checked = harness.checked_steps(prog, cell, SEED)
    del prog
    ref = reference.run(cell.model, cell.workload["optimizer"],
                        cell.traffic, SEED, 1, steps=harness.CHECKED_STEPS)
    assert "lm_head" in ref["grad"]
    median = statistics.median(ref["grad"].values())
    gap = abs(checked["grad"]["embed"] - ref["grad"]["embed"]) \
        / max(ref["grad"]["embed"], median)
    assert gap < LIMIT, (gap, checked["grad"]["embed"],
                         ref["grad"]["embed"])

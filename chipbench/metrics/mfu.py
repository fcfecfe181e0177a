"""Model FLOP/s utilization of the traced steps, in %: the model's
operations per token (chipbench/flops.py, no recomputation) times the
tokens of the traced steps, over the traced window's seconds, the chips
and the chip's bf16 peak (chipbench/peaks.py)."""
from chipbench import flops, peaks


def read(run):
    red = run.trace
    if red is None or red.window_s <= 0:
        return None
    cell = run.cell
    per_tok = flops.model_flops_per_token(cell.model, cell.traffic["seq_len"])
    tokens = red.n_steps * cell.traffic["global_batch"] \
        * cell.traffic["seq_len"]
    peak = peaks.peak(run.device_kind)["bf16_flops"]
    return 100.0 * per_tok * tokens / (red.window_s * cell.chips * peak)

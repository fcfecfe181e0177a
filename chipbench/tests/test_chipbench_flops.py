"""The yardstick's arithmetic: model FLOPs, codec kernel bytes, peaks."""
import json

import pytest

from chipbench import flops, peaks

from .conftest import ROOT

GPT2 = json.loads((ROOT / "chipbench/configs/gpt2-paper.json").read_text())
BUCKET, BLOCK = 6_553_600, 1024


def test_gpt2_paper_model_flops_per_token_by_hand():
    d, f, v, L, s = 768, 3072, 50257, 12, 1024
    per_layer = 4 * d * d + 3 * d * f          # q, k, v, o; gate, up, down
    by_hand = 6 * (L * per_layer + v * d) + 12 * L * s * d
    got = flops.model_flops_per_token(GPT2["model"], s)
    assert got == by_hand
    assert got == pytest.approx(1.024e9, rel=1e-3)


def test_codec_bytes_at_a_25mb_bucket_match_roofline_points():
    from benchmarks.roofline import codec_kernel_points

    rows = BUCKET // BLOCK
    pts = {p["kernel"]: p for p in codec_kernel_points(rows=rows, n=BLOCK,
                                                       n_peers=1)}
    hbm = 819e9
    _, enc = flops.codec_kernel_cost("ht_quant", bucket_elems=BUCKET,
                                     block=BLOCK, n_peers=1)
    # the roofline point leaves out the per-row grids (2 fp32 per row),
    # the sign vector and the 128 x 128 lane factor
    small = 2 * rows * 4 + BLOCK * 4 + 128 * 128 * 4
    assert (enc - small) / hbm * 1e6 == pytest.approx(
        pts["ht_quant_fused"]["hbm_bound_us"])
    _, dec = flops.codec_kernel_cost("dequant_masked_mean",
                                     bucket_elems=BUCKET, block=BLOCK,
                                     n_peers=1)
    assert (dec - 2 * BUCKET * 4) / hbm * 1e6 == pytest.approx(
        pts["dequant_mean_fused"]["hbm_bound_us"])


@pytest.mark.parametrize("kernel", ["ht_amax", "ht_quant",
                                    "dequant_masked_mean", "grid_quant",
                                    "fwht"])
def test_codec_kernels_sit_under_the_ridge(kernel):
    f, b = flops.codec_kernel_cost(kernel, bucket_elems=BUCKET, block=BLOCK,
                                   n_peers=4)
    pk = peaks.peak("TPU v5 lite")
    assert f / b < pk["bf16_flops"] / pk["hbm_bytes_per_s"]
    assert flops.least_seconds(f, b, pk) == b / pk["hbm_bytes_per_s"]


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")

"""Operations and bytes the work needs, computed from shapes.

``model_flops_per_token`` is the MFU numerator: the forward and backward
matrix products of the dense block (6 per weight of every matrix product,
the LM head included; the embedding lookup is no product) plus
attention's 12 * L * S * (H * dh) (the whole score matrix, as MFU is
usually counted). Recomputation under remat is not counted: it is work the
chip does, not work the model needs.

``codec_kernel_cost`` gives (FLOPs, HBM bytes) of one call of each codec
kernel of the quantized path, as the kernel's own arithmetic and operand
sizes fix them: the MXU form of the rotation (one 128 x 128 product per
128-lane chunk, then a butterfly across the n/128 chunks) and every operand
read or written once, as the fused kernels stream them.

``hbm_bytes`` keeps of those bytes the operands that the compiled program
left in HBM. XLA places some operands of a custom call in on-chip memory
(memory space ``S(1)`` in the op's layout): those cost no HBM traffic, and
counting them would put a kernel above its HBM roofline.
"""
from __future__ import annotations

import math
import re

F32, U8 = 4, 1
LANES = 128                       # the MXU factor of the rotation


def matmul_params(model: dict) -> int:
    d, f, dh = model["d_model"], model["d_ff"], model["head_dim"]
    h, kv = model["n_heads"], model["n_kv_heads"]
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def model_flops_per_token(model: dict, seq_len: int) -> float:
    attn = 12 * model["n_layers"] * seq_len * model["n_heads"] \
        * model["head_dim"]
    return 6.0 * matmul_params(model) + attn


def _rotation_flops(elems: int, n: int) -> float:
    chunks = n // LANES
    return 2.0 * elems * LANES + elems * math.log2(chunks) + elems


def codec_kernel_operands(kernel: str, *, bucket_elems: int, block: int,
                          n_peers: int) -> list[int]:
    """Bytes of each operand of one call, in the kernel's argument order,
    then its result."""
    e, rows = bucket_elems, bucket_elems // block
    shard = e // n_peers
    hl = LANES * LANES * F32                     # the +-1 lane factor
    if kernel == "ht_amax":                      # x, sign, hl -> amax
        return [e * F32, block * F32, hl, rows * F32]
    if kernel == "ht_quant":                     # x, sign, noise, lo, step, hl
        return [e * F32, block * F32, e * F32, rows * F32, rows * F32, hl,
                e * U8]
    if kernel == "dequant_masked_mean":          # codes, lo, step, mask
        return [n_peers * shard * U8, shard * F32, shard * F32,
                n_peers * shard * F32, shard * F32]
    if kernel == "grid_quant":                   # x, noise, lo, step
        srows = shard // block
        return [shard * F32, shard * F32, srows * F32, srows * F32,
                shard * U8]
    if kernel == "fwht":                         # x, sign, hl
        return [e * F32, block * F32, hl, e * F32]
    raise KeyError(kernel)


def _kernel_flops(kernel: str, e: int, block: int, n_peers: int) -> float:
    shard = e // n_peers
    return {"ht_amax": lambda: _rotation_flops(e, block) + 2.0 * e,
            "ht_quant": lambda: _rotation_flops(e, block) + 5.0 * e,
            "dequant_masked_mean": lambda: 4.0 * n_peers * shard,
            "grid_quant": lambda: 5.0 * shard,
            "fwht": lambda: _rotation_flops(e, block)}[kernel]()


def hbm_bytes(kernel: str, in_hbm: list[bool], **shape) -> float:
    """Bytes of the operands and result that live in HBM; ``in_hbm`` is
    per operand, then the result, as :func:`memory_spaces` reads them."""
    sizes = codec_kernel_operands(kernel, **shape)
    if len(in_hbm) != len(sizes):
        raise ValueError(f"{kernel}: {len(in_hbm)} operands in the trace, "
                         f"{len(sizes)} in the model")
    return float(sum(s for s, h in zip(sizes, in_hbm) if h))


def memory_spaces(op_text: str) -> list[bool]:
    """Whether each operand, then the result, of a custom call lives in
    HBM, read from its HLO text: an ``S(n)`` in a layout means memory
    space n, none means HBM."""
    result, _, rest = op_text.partition(" custom-call(")
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    layouts = re.findall(r"\{[^{}]*\}", rest[:end]) \
        + re.findall(r"\{[^{}]*\}", result)[-1:]
    return [not re.search(r"S\(\d+\)", lay) for lay in layouts]


def codec_kernel_cost(kernel: str, *, bucket_elems: int, block: int,
                      n_peers: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call on one bucket of ``bucket_elems``.

    ht_amax:  x (f32) read, one amax per block row written.
    ht_quant: x and noise (f32) and the row grids read, u8 codes written.
    dequant_masked_mean: the N peers' u8 codes of one shard and their f32
              arrival mask read, the shard's grids read, the f32 mean written.
    grid_quant: the shard (f32) and its noise read, u8 codes written.
    fwht:     the decoded bucket (f32) read and written (sign-flip decode).
    """
    shape = dict(bucket_elems=bucket_elems, block=block, n_peers=n_peers)
    return (_kernel_flops(kernel, bucket_elems, block, n_peers),
            float(sum(codec_kernel_operands(kernel, **shape))))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline time: the larger of compute and HBM time at peak."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])

"""The codec kernels' share of their roofline, in %: the sum over their
calls of the least time the chip could take over the sum of their device
times. The least time of a call is the larger of its FLOPs over the bf16
peak and its HBM bytes over the HBM bandwidth (chipbench/flops.py at the
cell's bucket grid, peaks from chipbench/peaks.py); operands that the
compiled program keeps on chip (``S(1)`` in the trace) move no HBM bytes.
Each kernel's own share is logged on standard error."""
import sys

from chipbench import flops, peaks
from chipbench.codec_kernels import kernel_events

BUCKET_ELEMS = 6_553_600          # TrainConfig's 25 MB fp32 bucket


def read(run):
    red = run.trace
    if red is None:
        return None
    events = kernel_events(red, 0)
    if not events:
        return None
    pk = peaks.peak(run.device_kind)
    shape = dict(bucket_elems=BUCKET_ELEMS,
                 block=run.cell.workload["hadamard_block"],
                 n_peers=run.cell.chips)
    least, spent = {}, {}
    for kernel, e in events:
        f, _ = flops.codec_kernel_cost(kernel, **shape)
        b = flops.hbm_bytes(kernel, flops.memory_spaces(e.name), **shape)
        least[kernel] = least.get(kernel, 0.0) + flops.least_seconds(f, b, pk)
        spent[kernel] = spent.get(kernel, 0.0) + (e.end - e.start) * 1e-9
    for k in sorted(spent):
        print(f"chipbench: codec_roofline {k}: "
              f"{100 * least[k] / spent[k]!r} % of {spent[k]!r} s",
              file=sys.stderr)
    return 100.0 * sum(least.values()) / sum(spent.values())

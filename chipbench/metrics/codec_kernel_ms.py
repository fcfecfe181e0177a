"""Device time of the codec kernels (the Mosaic custom calls of
kernels/ht_quant, kernels/dequant_reduce, kernels/quant, kernels/fwht)
per traced step on device 0, in ms."""
from chipbench.codec_kernels import kernel_events


def read(run):
    red = run.trace
    if red is None:
        return None
    events = kernel_events(red, 0)
    if not events:
        return None
    return sum(e.end - e.start for _, e in events) * 1e-6 / red.n_steps

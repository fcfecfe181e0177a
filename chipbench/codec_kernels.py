"""Which device operations are the codec kernels, and which kernel each
is. A Pallas call appears in the trace as ``%_<kernel>_call.<n> = ...
custom-call(...)``; the match is on that name, not on the operands that
other operations take from it."""
from __future__ import annotations

import re

# trace name pattern -> chipbench/flops.py kernel
KERNELS = {
    "ht_amax": re.compile(r"^%?_ht_amax_call\b"),
    "ht_quant": re.compile(r"^%?_ht_quant_call\b"),
    "dequant_masked_mean": re.compile(r"^%?_dequant_masked_mean_call\b"),
    "grid_quant": re.compile(r"^%?_grid_quant_call\b"),
    "fwht": re.compile(r"^%?_fwht_call\b"),
}


def kernel_events(red, dev: int) -> list:
    """(kernel, Event) of every codec kernel call on device ``dev``."""
    out = []
    for e in red.devices[dev]:
        name = e.name.split(" = ", 1)[0]
        for kernel, pat in KERNELS.items():
            if pat.search(name):
                out.append((kernel, e))
                break
    return out

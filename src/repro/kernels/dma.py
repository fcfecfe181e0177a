"""Two-slot revolving-buffer DMA schedule for double-buffered Pallas kernels.

The double-buffered ht_quant kernels keep their bulk operands in ``ANY``
(HBM) memory space and stream one grid block of rows at a time into
two-slot VMEM scratch buffers with explicit async copies: while block i
computes out of slot ``i % 2``, block i+1's loads are already in flight into
slot ``(i + 1) % 2``.  This module holds the single copy of that schedule —
kernels differ only in the epilogue consuming the landed slots.

Because the revolving slots and in-flight DMAs are threaded through scratch
refs *across* grid iterations, any grid using this schedule must be marked
sequential (``SEQUENTIAL_GRID``) so Mosaic neither reorders nor parallelizes
the iterations.
"""
from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SEQUENTIAL_GRID = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def row_loads(streams, sem, slot: int, idx):
    """The async HBM->VMEM copies landing row block ``idx`` in ``slot``.

    ``streams`` is a list of (hbm_ref, vmem_buf, rows_per_block) triples all
    indexed by the same row-block axis; stream k signals ``sem[k, slot]``.
    """
    return [pltpu.make_async_copy(hbm.at[pl.ds(idx * br, br)],
                                  buf.at[slot], sem.at[k, slot])
            for k, (hbm, buf, br) in enumerate(streams)]


def revolving_pipeline(nblk: int, loads, epilogue):
    """One grid iteration of the two-slot revolving-buffer schedule.

    ``loads(slot, idx)`` returns the async copies landing block ``idx`` in
    ``slot`` (see :func:`row_loads`); block i+1's loads
    are issued *before* block i's are awaited, so the next block's HBM
    traffic overlaps this block's compute.  ``epilogue(slot)`` consumes the
    landed VMEM slots.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():                               # warm-up: land block 0
        for dma in loads(0, 0):
            dma.start()

    @pl.when(i + 1 < nblk)
    def _():                               # prefetch block i+1
        for dma in loads((i + 1) % 2, i + 1):
            dma.start()

    for dma in loads(i % 2, i):
        dma.wait()
    epilogue(i % 2)

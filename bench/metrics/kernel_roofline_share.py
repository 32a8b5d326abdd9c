"""Share of the kernels' own roofline: the least time the window's useful
work could take on the chips used (``bench/workcount.py``, against the
peaks of ``bench/peaks.json``), over the time the program's stencil
kernels ran in the window, averaged over the chips.

That is ``roofline_share`` (the least time over the device's busy time)
over ``kernel_share`` (the ``stencil_*`` kernels' time over the same busy
time): the kernels' rate against the chip's peak, with the write-backs
and copies between launches left out. Nothing to read where no kernel
ran, or no work was counted.
"""
from bench.metrics import kernel_share, roofline_share


def read(run):
    roof, share = roofline_share.read(run), kernel_share.read(run)
    return None if roof is None or not share else roof * 100.0 / share

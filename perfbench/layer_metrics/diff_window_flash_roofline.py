"""Pallas kernels: the least time the chip could take for the attention
the windowed differential layers need (perfbench/flops_sambay.py: **the
band only**, ``sum_i min(i + 1, sliding_window)`` pairs a head) over the
device time of the flash kernels whose names hold ``window``, in %."""
from perfbench.layer_metrics.diff_flash_roofline import share


def read(window, trace, config, peaks):
    return share(window, trace, config, peaks, windowed=True)

"""Pallas kernels: the least time the chip could take for the attention
the windowed layers of the traced steps need (perfbench/flops_gqa_moe.py:
**the band only**, ``sum_i min(i + 1, sliding_window)`` pairs a head, k
and v read once a group) over the device time of the flash kernels whose
names hold ``window``, in %."""
from perfbench.layer_metrics.gqa_flash_roofline import share


def read(window, trace, config, peaks):
    return share(window, trace, config, peaks, windowed=True)

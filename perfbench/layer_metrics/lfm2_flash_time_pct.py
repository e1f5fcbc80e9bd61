"""Pallas kernels: the flash-attention kernels' device time in an LFM2
model — its grouped-query layers at head width 64, q and k normed a head
before the kernels and rotated inside them — over the device's busy time
in the traced steps, in %."""
from perfbench.layer_metrics.gqa_flash_time_pct import FULL
from perfbench.layer_metrics.short_conv_time_pct import is_lfm2
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *FULL) if trace else 0
    if not spent or not is_lfm2(config):
        return None
    return 100.0 * spent / trace["busy_s"]

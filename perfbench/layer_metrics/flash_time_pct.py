"""Pallas kernels: the three flash-attention kernels' device time over the
device's busy time in the traced steps, in %."""
from perfbench.trace_reduce import seconds_of

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    return 100.0 * spent / trace["busy_s"] if spent else None

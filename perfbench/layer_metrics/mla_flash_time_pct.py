"""Pallas kernels: the flash-attention kernels' device time, run on latent
attention's 192/128-wide heads, over the device's busy time in the traced
steps, in %."""
from perfbench.trace_reduce import seconds_of

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or "kv_lora_rank" not in config:
        return None
    return 100.0 * spent / trace["busy_s"]

"""Pallas kernels: the least time the chip could take for the attention
the traced steps need (the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, perfbench/flops.py) over the three kernels' summed
device time, in %."""
from perfbench import flops
from perfbench.trace_reduce import seconds_of

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or peaks is None:
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops.flash_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

"""Pallas kernels: the least time the chip could take for the latent
attention the traced steps need (the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, perfbench/flops_mla_moe.py: q/k 192 wide, v/o 128,
``k_rope`` once a token) over the flash kernels' summed device time, in %."""
from perfbench import flops_mla_moe
from perfbench.layer_metrics.mla_flash_time_pct import KERNELS
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or peaks is None or "kv_lora_rank" not in config:
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_mla_moe.flash_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

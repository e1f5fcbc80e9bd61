"""Pallas kernels: the least time the chip could take for the attention an
LFM2 model's grouped-query layers need in the traced steps — the larger of
FLOPs over the bf16 peak and bytes over the HBM peak,
perfbench/flops_lfm2.py: the causal half square, k and v read once a group
— over the flash kernels' device time, in %."""
from perfbench import flops_lfm2
from perfbench.layer_metrics.gqa_flash_time_pct import FULL
from perfbench.layer_metrics.short_conv_time_pct import is_lfm2
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *FULL) if trace else 0
    if not spent or peaks is None or not is_lfm2(config):
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_lfm2.flash_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

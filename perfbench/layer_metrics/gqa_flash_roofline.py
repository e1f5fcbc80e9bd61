"""Pallas kernels: the least time the chip could take for the attention
the full (causal) grouped-query layers of the traced steps need — the
larger of FLOPs over the bf16 peak and bytes over the HBM peak,
perfbench/flops_gqa_moe.py: the causal half square, k and v read once a
group — over the device time of the flash kernels whose names hold no
``window``, in %."""
from perfbench import flops_gqa_moe
from perfbench.layer_metrics.gqa_flash_time_pct import (FULL, WINDOW,
                                                        is_grouped)
from perfbench.trace_reduce import seconds_of


def share(window, trace, config, peaks, windowed):
    """The roofline share of the windowed layers' kernels, or the full
    layers'."""
    spent = seconds_of(trace, *(WINDOW if windowed else FULL)) if trace else 0
    if not spent or peaks is None or not is_grouped(config):
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_gqa_moe.flash_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"], windowed)
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent


def read(window, trace, config, peaks):
    return share(window, trace, config, peaks, windowed=False)

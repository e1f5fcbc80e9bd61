"""Pallas kernels: the least time the chip could take for the attention
that the differential layers over the whole prefix need (the full layer
and the cross layers: the causal half square, q/k 64 and V 128 wide, k and
V read once a group; perfbench/flops_sambay.py) over the device time of the
flash kernels whose names hold no ``window``, in %."""
from perfbench import flops_sambay
from perfbench.layer_metrics.gqa_flash_time_pct import FULL, WINDOW
from perfbench.layer_metrics.ssm_scan_time_pct import is_hybrid
from perfbench.trace_reduce import seconds_of


def share(window, trace, config, peaks, windowed):
    spent = seconds_of(trace, *(WINDOW if windowed else FULL)) if trace else 0
    if not spent or peaks is None or not is_hybrid(config):
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_sambay.flash_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"], windowed)
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent


def read(window, trace, config, peaks):
    return share(window, trace, config, peaks, windowed=False)

"""Pallas kernels: the least time the chip could take for the selective
scans the traced steps need — the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, perfbench/flops_sambay.py: the bytes any
implementation of the layer's interface must move — over the ``ssm_scan``
kernels' device time, in %. The kernels are bound by the vector unit,
for which ``peaks.json`` has no row: the share reads low by construction."""
from perfbench import flops_sambay
from perfbench.layer_metrics.ssm_scan_time_pct import KERNELS, is_hybrid
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or peaks is None or not is_hybrid(config):
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_sambay.scan_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

"""Pallas kernels: the least time the chip could take for the gated short
convolutions the traced steps need — the larger of FLOPs over the bf16
peak and bytes over the HBM peak, perfbench/flops_lfm2.py: the bytes any
implementation of the op's interface must move, the recomputed forward
not counted — over the ``short_conv`` kernels' summed device time (the
recomputed forward included, as ``moe_gmm_roofline`` counts it), in %."""
from perfbench import flops_lfm2
from perfbench.layer_metrics.short_conv_time_pct import KERNELS, is_lfm2
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or peaks is None or not is_lfm2(config):
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_lfm2.short_conv_needed(
        config, sizes["batch_per_chip"], sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

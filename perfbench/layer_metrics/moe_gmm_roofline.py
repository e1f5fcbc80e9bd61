"""Pallas kernels: the least time the chip could take for the grouped
matmuls over the held experts that the traced steps need (pairs at their
expectation, perfbench/flops_mla_moe.py) over the ``moe_gmm`` kernels'
summed device time (the recomputed forward included), in %."""
from perfbench import flops_mla_moe
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, "moe_gmm") if trace else 0
    if not spent or peaks is None:
        return None
    sizes = window["sizes"]
    need_flops, need_bytes = flops_mla_moe.gmm_needed(
        config, sizes["batch_per_chip"] * sizes["seq_len"])
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * trace["steps"] * least / spent

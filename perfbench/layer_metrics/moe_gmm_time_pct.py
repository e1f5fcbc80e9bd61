"""Pallas kernels: the grouped-matmul (``moe_gmm``) kernels' device time
over the device's busy time in the traced steps, in %."""
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, "moe_gmm") if trace else 0
    return 100.0 * spent / trace["busy_s"] if spent else None

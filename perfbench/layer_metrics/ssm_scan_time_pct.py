"""Pallas kernels: the selective-scan kernels' device time (every event
whose name holds ``ssm_scan``, forward and backward) over the device's
busy time in the traced steps, in %."""
from perfbench.trace_reduce import seconds_of

KERNELS = ("ssm_scan",)


def is_hybrid(config):
    """Whether the configuration is one these metrics are defined on."""
    return "mamba_d_state" in config.get("assumed", {})


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or not is_hybrid(config):
        return None
    return 100.0 * spent / trace["busy_s"]

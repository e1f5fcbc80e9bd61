"""Pallas kernels: the gated short-convolution kernels' device time (every
event whose name holds ``short_conv``: forward, backward and the forward
again in the recomputed layer) over the device's busy time in the traced
steps, in %."""
from perfbench.trace_reduce import seconds_of

KERNELS = ("short_conv",)


def is_lfm2(config):
    """Whether the configuration is one these metrics are defined on."""
    return config.get("model_type") == "lfm2_moe"


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *KERNELS) if trace else 0
    if not spent or not is_lfm2(config):
        return None
    return 100.0 * spent / trace["busy_s"]

"""Pallas kernels: every flash-attention kernel's device time — the full
layers' and the windowed layers', on grouped-query heads — over the
device's busy time in the traced steps, in %."""
from perfbench.trace_reduce import seconds_of

#: the full layers' kernels; a windowed call's names hold ``window``
FULL = ("flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv")
WINDOW = ("flash_attention_window_",)


def is_grouped(config):
    """Whether the configuration is one these metrics are defined on."""
    return "num_attention_heads_per_layer" in config


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *FULL, *WINDOW) if trace else 0
    if not spent or not is_grouped(config):
        return None
    return 100.0 * spent / trace["busy_s"]

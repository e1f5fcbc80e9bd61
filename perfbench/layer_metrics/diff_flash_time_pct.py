"""Pallas kernels: every flash-attention kernel's device time — the
differential layers' two calls each, under a window and over the whole
prefix — over the device's busy time in the traced steps, in %."""
from perfbench.layer_metrics.gqa_flash_time_pct import FULL, WINDOW
from perfbench.layer_metrics.ssm_scan_time_pct import is_hybrid
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, *FULL, *WINDOW) if trace else 0
    if not spent or not is_hybrid(config):
        return None
    return 100.0 * spent / trace["busy_s"]

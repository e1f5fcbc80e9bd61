"""Start-up: tracing and lowering before the window, seconds — the union of
the ``jit.trace`` and ``jit.lower`` spans' intervals (traces nest: a
jitted function's trace holds those of the jitted functions it calls)."""
from perfbench.layer_metrics.startup import setup_events, union_s


def read(window, trace, config, peaks):
    spans = setup_events(window, trace, "setup_trace_s", "jit.trace",
                         "jit.lower")
    return None if spans is None else union_s(spans)

"""Start-up: garbage collection before the window, seconds — the sum of the
``python.gc`` spans (every generation-2 collection, and any of 1 ms or
more)."""
from perfbench.layer_metrics.startup import setup_events


def read(window, trace, config, peaks):
    spans = setup_events(window, trace, "setup_gc_s", "python.gc")
    return None if spans is None else sum(ev["dur"] for ev in spans) / 1e6

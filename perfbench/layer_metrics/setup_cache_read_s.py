"""Start-up: programs read from the persistent compile cache before the
window, seconds — the sum of the ``jit.compile`` spans with ``cache``
``hit``."""
from perfbench.layer_metrics.startup import compiles


def read(window, trace, config, peaks):
    spans = compiles(window, trace, "setup_cache_read_s", hit=True)
    return None if spans is None else sum(ev["dur"] for ev in spans) / 1e6

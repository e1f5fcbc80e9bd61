"""Start-up: compiling before the window, seconds — the sum of the
``jit.compile`` spans whose ``cache`` is not ``hit`` (a stored entry's
write included)."""
from perfbench.layer_metrics.startup import compiles


def read(window, trace, config, peaks):
    spans = compiles(window, trace, "setup_compile_s", hit=False)
    return None if spans is None else sum(ev["dur"] for ev in spans) / 1e6

"""Start-up: programs compiled before the window — the count of the
``jit.compile`` spans whose ``cache`` is not ``hit``."""
from perfbench.layer_metrics.startup import compiles


def read(window, trace, config, peaks):
    spans = compiles(window, trace, "setup_programs_compiled", hit=False)
    return None if spans is None else len(spans)

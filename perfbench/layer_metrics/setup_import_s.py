"""Start-up: the package's import, seconds (the ``mxnet_tpu.import`` span:
``mxnet_tpu/__init__.py``'s first line to its last)."""
from perfbench.layer_metrics.startup import left_out, setup_events


def read(window, trace, config, peaks):
    spans = setup_events(window, trace, "setup_import_s", "mxnet_tpu.import")
    if spans is None:
        return None
    if not spans:
        left_out("setup_import_s", "no mxnet_tpu.import span in the ring")
        return None
    return sum(ev["dur"] for ev in spans) / 1e6

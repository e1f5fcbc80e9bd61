"""Entry points: the program's own span around the compiled step's call
alone (``sharded_trainer.enqueue`` / ``transformer.enqueue``: argument
flattening, donation, the runtime's enqueue), mean duration over the
window's steps, in ms."""
from perfbench.layer_metrics.step_call_ms import window_spans


def read(window, trace, config, peaks):
    spans = window_spans(window, trace, config, "enqueue")
    return sum(spans) / len(spans) if spans else None

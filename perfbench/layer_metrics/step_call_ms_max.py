"""Entry points: the longest ``sharded_trainer.step`` /
``transformer.step`` span of the window, in ms. Beside ``step_ms_max`` /
``step_ms_p95`` it says whether a stalled window stalled inside the
program's call or outside it."""
from perfbench.layer_metrics.step_call_ms import window_spans


def read(window, trace, config, peaks):
    spans = window_spans(window, trace, config, "step")
    return max(spans) if spans else None

"""Entry points: the program's own span around one step call
(``sharded_trainer.step`` / ``transformer.step``, taken inside
``mxnet_tpu`` and read from its in-memory span ring), mean duration over
the window's steps, in ms. ``step_call_ms - enqueue_ms`` is the program's
Python per step. This file also holds what ``enqueue_ms`` and
``step_call_ms_max`` share: which spans are the window's."""
import sys

SPAN_OF_DRIVER = {"sharded_trainer": "sharded_trainer",
                  "lm_step": "transformer"}


def window_spans(window, trace, config, suffix):
    """Durations (ms) of the window's ``<entry point>.<suffix>`` spans,
    oldest first, or None where they cannot be told apart. The ring
    holds, in order: the three first steps, the window's ``n``, then the
    traced run's ``k``; so the window's are the ``n`` before the last
    ``k``. Fewer than ``n + k`` spans of that name (a parent without
    them, a ring that overflowed) gives None and a line on standard
    error; it never guesses."""
    from mxnet_tpu import profiler

    prefix = SPAN_OF_DRIVER.get(config.get("driver"))
    if prefix is None or trace is None:
        return None
    name = "%s.%s" % (prefix, suffix)
    n, k = window["steps"], trace["steps"]
    # a step leaves two events (the span and its enqueue child)
    spans = sorted((ev for ev in profiler.events_tail(4 * (n + k) + 64)
                    if ev.get("name") == name), key=lambda ev: ev["ts"])
    if not n or len(spans) < n + k:
        print("perfbench: %d %s spans in the ring, the window and the "
              "traced steps need %d; metric left out"
              % (len(spans), name, n + k), file=sys.stderr)
        return None
    return [ev["dur"] / 1e3 for ev in spans[-(n + k):][:n]]


def read(window, trace, config, peaks):
    spans = window_spans(window, trace, config, "step")
    return sum(spans) / len(spans) if spans else None

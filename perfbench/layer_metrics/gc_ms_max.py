"""Entry points: the longest garbage-collection pause in the window, ms —
the longest ``python.gc`` span (every generation-2 collection, and any of
1 ms or more) that starts between the window's first step span's start and
its last one's end; 0.0 where none does."""
from perfbench.layer_metrics.startup import window_and_ring


def read(window, trace, config, peaks):
    got = window_and_ring(window, trace, "gc_ms_max")
    if got is None:
        return None
    events, steps = got
    start, end = steps[0]["ts"], steps[-1]["ts"] + steps[-1]["dur"]
    return max((ev["dur"] / 1e3 for ev in events
                if ev["name"] == "python.gc" and start <= ev["ts"] <= end),
               default=0.0)

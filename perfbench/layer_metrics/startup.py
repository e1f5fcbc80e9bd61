"""Start-up: what the program did before the window's first step, read
from its span ring — ``mxnet_tpu.import``, the ``jit.trace`` /
``jit.lower`` / ``jit.compile`` spans JAX's own events give, and
``python.gc``. This file holds what the ``setup_*`` readers and
``gc_ms_max`` share: where the window lies in the ring, and which events
came before it.

The window is found by the program's step spans (category ``parallel``,
named ``<entry point>.step``; ``multi_step`` is not one): the ring holds,
in order, the first steps (a routed driver's probe adds one), the
window's ``n``, then the traced run's ``k``, so the window's first step is
the ``(n + k)``-th from the end. Set-up is every event that starts before
it; the reference's compiles, after the window, are left out."""
import sys


def left_out(metric, why):
    print("perfbench: %s; %s left out" % (why, metric), file=sys.stderr)


def window_and_ring(window, trace, metric):
    """(every ring event, the window's step spans oldest first), or None
    and a line on standard error where the ring cannot say: too few step
    spans, evicted events, or a program that records no ``jit.compile``
    span (a parent without the start-up spans). It never guesses."""
    import inspect

    from mxnet_tpu import profiler

    if trace is None:
        return None
    if profiler.dropped_events():
        left_out(metric, "the ring evicted %d events"
                 % profiler.dropped_events())
        return None
    if "compiles" not in inspect.signature(profiler.events_tail).parameters:
        left_out(metric, "the ring gives no jit spans")
        return None
    events = profiler.events_tail(sys.maxsize, compiles=True)
    steps = sorted((ev for ev in events if ev.get("cat") == "parallel"
                    and ev["name"].endswith(".step")),
                   key=lambda ev: ev["ts"])
    n, k = window["steps"], trace["steps"]
    if not n or len(steps) < n + k:
        left_out(metric, "%d step spans in the ring, the window and the "
                 "traced steps need %d" % (len(steps), n + k))
        return None
    if not any(ev["name"] == "jit.compile" for ev in events):
        left_out(metric, "no jit.compile span in the ring")
        return None
    return events, steps[-(n + k):][:n]


def setup_events(window, trace, metric, *names):
    """The ring's events of these names that start before the window's
    first step, or None (see :func:`window_and_ring`)."""
    got = window_and_ring(window, trace, metric)
    if got is None:
        return None
    events, steps = got
    start = steps[0]["ts"]
    return [ev for ev in events if ev["name"] in names and ev["ts"] < start]


def union_s(events):
    """Seconds covered by the events' intervals, each instant once: a
    trace nested in another counts once."""
    total, reach = 0.0, float("-inf")
    for ev in sorted(events, key=lambda ev: ev["ts"]):
        end = ev["ts"] + ev["dur"]
        if end > reach:
            total += end - max(ev["ts"], reach)
            reach = end
    return total / 1e6


def compiles(window, trace, metric, hit):
    """The set-up's ``jit.compile`` spans that read the persistent cache
    (``hit``) or that compiled (every other ``cache``), or None."""
    spans = setup_events(window, trace, metric, "jit.compile")
    if spans is None:
        return None
    return [ev for ev in spans if (ev["args"]["cache"] == "hit") == hit]

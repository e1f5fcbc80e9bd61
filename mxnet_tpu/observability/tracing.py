"""Span tracing: one span, three sinks, one record.

``trace_span(name, cat)`` wraps a host-side phase (a trainer step, the
enqueue of a compiled program, a kvstore push). While telemetry is
enabled or a profiler session runs, a span

* enters ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` where
  the span is given ``step=``) while a JAX trace runs —
  ``mx.profiler.set_state('run')`` or a caller's own
  ``jax.profiler.start_trace`` — so that it lies in the ``.xplane.pb``
  host plane, on the clock of the device planes beside it: that file is
  where a host span names a device idle gap (``tools/trace_report.py``);
* is appended to the profiler's bounded event ring
  (``profiler.events_tail``; ``dump_profile()`` writes it as chrome
  JSON, on the host's own ``perf_counter`` clock) with its parent — the
  span open around it on the same thread — and its step in ``args``,
  so self time (a span less its children) can be computed;
* feeds the ``span.<name>.ms`` histogram when telemetry is enabled; the
  histogram's count is the count of spans at that boundary.

With telemetry off and no session a span does nothing at all.

Code *inside* a jitted program cannot be timed from the host — use
:func:`device_scope`, whose label rides in the compiled program's
``op_name`` metadata and from there in the device trace.
"""
from __future__ import annotations

import threading

import jax

from .. import profiler
from . import metrics

__all__ = ["trace_span", "device_scope", "open_span"]

_open = threading.local()  # .stack: this thread's open, recording spans


def open_span():
    """The innermost span recording on this thread, or None: the parent
    of what the compile listeners record after the fact."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


class _Span:
    """Context manager for one span instance."""

    __slots__ = ("name", "cat", "args", "_t0", "_ring", "_telem", "_ann")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self._ring = False

    def __enter__(self):
        self._telem = metrics.enabled()
        self._ring = self._telem or profiler.spans_active()
        if not self._ring:
            return self
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        args = self.args
        own_step = "step" in args
        if stack:
            parent = stack[-1]
            args["parent"] = parent.name
            if not own_step and "step" in parent.args:
                args["step"] = parent.args["step"]
        stack.append(self)
        self._ann = None
        if jax.profiler.TraceAnnotation.is_enabled():  # a JAX trace runs
            if own_step:
                # the span that numbers a step: the TPU trace groups the
                # device's operations by it
                kw = dict(args, cat=self.cat)
                kw["step_num"] = kw.pop("step")
                self._ann = jax.profiler.StepTraceAnnotation(self.name, **kw)
            else:
                # the cat stat is what tells a trace_span from the
                # runtime's own annotations (tools/trace_report.py)
                self._ann = jax.profiler.TraceAnnotation(
                    self.name, cat=self.cat, **args)
            self._ann.__enter__()
        self._t0 = profiler._now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._ring:
            return False
        dur = profiler._now_us() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _open.stack.pop()
        profiler.record(self.name, self.cat, self._t0, dur, args=self.args)
        if self._telem:
            metrics.histogram("span.%s.ms" % self.name).observe(dur / 1e3)
        return False


def trace_span(name, cat="phase", step=None, **args):
    """Context manager: record ``name`` as a span of category ``cat``
    covering the with-block (nothing unless telemetry is enabled or the
    profiler runs). ``step`` numbers the training step the span belongs
    to (children inherit it); further keywords ride along as the span's
    ``args``."""
    if step is not None:
        args["step"] = int(step)
    return _Span(name, cat, args)


def device_scope(name):
    """Label the operations traced inside the with-block:
    ``jax.named_scope``. The label is HLO metadata (``op_name``), costs
    nothing at run time, and is what attributes device time to a phase
    or a layer in the device trace."""
    return jax.named_scope(name)

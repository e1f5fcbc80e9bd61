"""Built-in instruments: XLA compile accounting, garbage-collection
pauses, device memory watermarks.

Four groups of ready-made telemetry, writing into the metrics registry
(metrics.py) and, where a span says more than a number, the profiler's
event ring (what ``trace_span`` writes to):

* **Compile events** — ``install_jax_hooks()`` registers
  ``jax.monitoring`` listeners. Every backend compile increments
  ``jit.compile_count`` and feeds ``jit.compile.ms``; every jaxpr trace
  feeds ``jit.trace_count``/``jit.trace.ms``, every lowering
  ``jit.lower_count``/``jit.lower.ms``. A steady-state training
  loop must show a FLAT compile count — a climbing one is the recompile
  storm the round-5 review's bucketing ask wants ruled out. Each of the
  three is also one ring span (``jit.trace``, ``jit.lower``,
  ``jit.compile``; category ``jit``) with ``fun`` (JAX's function name)
  and the ``parent`` and ``step`` of the span open on the thread that
  did the work; a ``jit.compile`` span says in ``cache`` what the
  persistent cache did inside it: ``hit``, ``written`` (compiled, then
  stored), ``compiled`` (looked up, compiled, not stored: every program
  under JAX's 1 s threshold) or ``off`` (no cache directory placed, so
  nothing to look up). Hits and writes count in
  ``jit.persistent_cache_hits`` / ``_misses``. With
  ``MXNET_TELEMETRY_RETRACE=1`` the hooks also flip jax's
  ``explain_cache_misses`` and keep the most recent cause strings
  (``retrace_causes()``), which ``dump_metrics()`` appends as comments.
* **Garbage collection** — the same call hooks ``gc.callbacks``: every
  collection feeds ``gc.pause.ms`` (label ``generation``); one of
  generation 2, or of ``GC_SPAN_MIN_MS`` or more, is a ``python.gc``
  ring span (category ``host``; ``generation``, ``collected``), and lies
  in the host plane of a running JAX trace as a ``TraceAnnotation``.
* **The package's import** — ``mxnet_tpu/__init__.py`` hands its first
  and last clock reading to ``note_import``; the first time telemetry is
  on, that interval is one ``mxnet_tpu.import`` ring span.
* **Memory watermarks** — ``sample_memory()`` reads
  ``device.memory_stats()`` (the PJRT allocator view: live bytes, peak,
  limit) into ``hbm.live_bytes`` / ``hbm.peak_bytes`` gauges. Backends
  that expose no allocator stats (CPU) fall back to the process RSS /
  VmHWM from /proc so the watermark is never silently zero — the gauge
  ``hbm.source`` (0 = device allocator, 1 = host RSS) says which you got.
* **Step accounting** — ``record_step(seconds)`` feeds the ``step.ms``
  histogram and samples memory once per call; training loops (module
  fit, parallel trainers) call it once per optimization step.

The eager-dispatch split instruments live at their call site
(ndarray/register.py invoke) because they need the pre/post-dispatch
timestamps; this module only houses instrumentation with no natural
in-tree host.
"""
from __future__ import annotations

import collections
import gc
import logging
import os
import threading

import jax

from .. import profiler
from . import metrics, tracing

__all__ = ["install_jax_hooks", "sample_memory", "record_step",
           "retrace_causes", "note_import", "GC_SPAN_MIN_MS"]

_install_lock = threading.Lock()
_installed = False  # guarded-by: _install_lock
_retrace_log = collections.deque(maxlen=32)

# jax.monitoring event -> short metric stem, and the name of its ring span
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
}

# persistent-cache event -> what it says of the compile it lies in; each
# fires on the compiling thread before that compile's span closes
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compiled",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "written",  # fires on a write
}
_cache_seen = threading.local()  # .tag: the open compile's cache outcome


def _on_duration(event, duration_secs, **kwargs):
    if not metrics.enabled():
        return
    stem = _DURATION_EVENTS.get(event)
    if stem is None:
        return
    metrics.counter(stem + "_count").inc()
    metrics.histogram(stem + ".ms").observe(duration_secs * 1e3)


def _on_event(event, **kwargs):
    if not metrics.enabled():
        return
    tag = _CACHE_EVENTS.get(event)
    if tag is None:
        return
    if tag == "compiled" and not jax.config.jax_compilation_cache_dir:
        tag = "off"  # JAX asks for a key even where no cache is placed
    _cache_seen.tag = tag
    if tag == "hit":
        metrics.counter("jit.persistent_cache_hits").inc()
    elif tag == "written":
        metrics.counter("jit.persistent_cache_misses").inc()


def _on_time_span(event, start_time, end_time, fun_name=None, **kwargs):
    """One ring span a trace, lowering or compile. JAX reports it when the
    work ends, on the thread that did it, with ``time.time()`` stamps: the
    span ends now on the ring's clock and keeps JAX's length."""
    if not metrics.enabled():
        return
    name = _DURATION_EVENTS.get(event)
    if name is None:
        return
    dur = (end_time - start_time) * 1e6
    end = profiler._now_us()
    args = {"fun": fun_name}
    parent = tracing.open_span()
    if parent is not None:
        args["parent"] = parent.name
        if "step" in parent.args:
            args["step"] = parent.args["step"]
    if name == "jit.compile":
        args["cache"] = getattr(_cache_seen, "tag", "off")
        _cache_seen.tag = "off"
    profiler.record(name, "jit", end - dur, dur, args=args)


#: a collection this long or longer becomes a ``python.gc`` ring span
#: (every generation-2 collection does, whatever its length)
GC_SPAN_MIN_MS = 1.0

_gc_open = None  # the running collection: (start us, TraceAnnotation or None)
# finished collections not yet in the registry and the ring: a collection
# can start where its thread holds the registry's or the ring's lock, so
# the hook records only where both are free, and leaves the rest to the
# next collection that finds them free
_gc_done = collections.deque(maxlen=1024)


def _on_gc(phase, info):
    if not metrics.enabled():
        return
    global _gc_open
    if phase == "start":
        ann = None
        if jax.profiler.TraceAnnotation.is_enabled():  # a JAX trace runs
            ann = jax.profiler.TraceAnnotation(
                "python.gc", cat="host", generation=info["generation"])
            ann.__enter__()
        _gc_open = (profiler._now_us(), ann)
        return
    if _gc_open is None:  # telemetry came on during the collection
        return
    (t0, ann), _gc_open = _gc_open, None
    if ann is not None:
        ann.__exit__(None, None, None)
    _gc_done.append((t0, profiler._now_us() - t0, info["generation"],
                     info["collected"]))
    if not (metrics._lock.locked() or profiler._lock.locked()):
        _record_gc()


def _record_gc():
    while True:
        try:
            t0, dur, generation, collected = _gc_done.popleft()
        except IndexError:
            return
        metrics.histogram("gc.pause.ms", labels={
            "generation": generation}).observe(dur / 1e3)
        if generation == 2 or dur >= GC_SPAN_MIN_MS * 1e3:
            profiler.record("python.gc", "host", t0, dur, args={
                "generation": generation, "collected": collected})


_import_span = None  # perf_counter (start, end) of the package's import


def note_import(start, end):
    """The package's import ran from ``start`` to ``end``
    (``time.perf_counter``): one ``mxnet_tpu.import`` ring span, now if
    telemetry is on, else the first time it comes on."""
    global _import_span
    _import_span = (start, end)
    _record_import()


def _record_import():
    global _import_span
    if _import_span is None or not metrics.enabled():
        return
    (start, end), _import_span = _import_span, None
    profiler.record("mxnet_tpu.import", "host",
                    (start - profiler._t0) * 1e6, (end - start) * 1e6)


class _RetraceHandler(logging.Handler):
    """Capture jax's TRACING CACHE MISS explanations into a ring buffer."""

    def emit(self, record):
        try:
            msg = record.getMessage()
        except Exception:
            return
        if "CACHE MISS" in msg:
            _retrace_log.append(msg.strip())


def install_jax_hooks():
    """Idempotently register the jax.monitoring listeners and the
    garbage-collection hook (and, when MXNET_TELEMETRY_RETRACE is set, the
    cache-miss explainer), and record the import span if it waits. Called
    automatically from ``metrics.set_enabled(True)`` / config's flag
    applier; safe to call directly."""
    global _installed
    _record_import()
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        gc.callbacks.append(_on_gc)

        from ..config import get_flag

        if get_flag("MXNET_TELEMETRY_RETRACE"):
            import jax

            jax.config.update("jax_explain_cache_misses", True)
            handler = _RetraceHandler()
            handler.setLevel(logging.WARNING)
            logger = logging.getLogger("jax._src.pjit")
            logger.addHandler(handler)
            if logger.level > logging.WARNING or logger.level == 0:
                logger.setLevel(logging.WARNING)
        _installed = True


def retrace_causes():
    """Most recent captured retrace-cause explanations (empty unless
    MXNET_TELEMETRY_RETRACE was set when hooks installed)."""
    return list(_retrace_log)


def _host_memory():
    """(live_bytes, peak_bytes) of this process from /proc — the fallback
    when the backend reports no allocator stats."""
    live = peak = 0
    try:
        page = os.sysconf("SC_PAGE_SIZE")
        with open("/proc/self/statm") as f:
            live = int(f.read().split()[1]) * page  # resident pages
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    peak = int(line.split()[1]) * 1024
                    break
    except Exception:
        pass
    return live, max(peak, live)


def device_peak_bytes():
    """Ungated peak-memory read: PJRT allocator stats on backends that
    expose them, process VmHWM otherwise; None when nothing is readable.
    Shared by the health layer's per-step flight-recorder records (which
    must work without MXNET_TELEMETRY) and available to callers that
    don't want sample_memory's gauge writes/flag gating."""
    try:
        import jax

        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        if stats:
            return int(stats.get("peak_bytes_in_use",
                                 stats.get("bytes_in_use", 0)))
    except Exception:
        pass
    try:
        _live, peak = _host_memory()
        return peak or None
    except Exception:
        return None


def sample_memory(context=None):
    """Record device-memory gauges: ``hbm.live_bytes`` (point-in-time)
    and ``hbm.peak_bytes`` (watermark across samples). Honors the
    MXNET_TELEMETRY_MEMSTATS flag (on by default under telemetry);
    returns the live-bytes sample, or None when disabled."""
    if not metrics.enabled():
        return None
    from ..config import get_flag

    if not get_flag("MXNET_TELEMETRY_MEMSTATS"):
        return None
    stats = None
    try:
        if context is not None:
            dev = context.jax_device()
        else:
            import jax

            dev = jax.devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
    except Exception:
        stats = None
    if stats:
        live = stats.get("bytes_in_use", 0)
        peak = stats.get("peak_bytes_in_use", live)
        if "bytes_limit" in stats:
            metrics.gauge("hbm.limit_bytes").set(stats["bytes_limit"])
        metrics.gauge("hbm.source").set(0)
    else:
        live, peak = _host_memory()
        metrics.gauge("hbm.source").set(1)
    metrics.gauge("hbm.live_bytes").set(live)
    metrics.gauge("hbm.peak_bytes").set_max(peak)
    return live


def record_step(seconds, context=None):
    """Per-optimization-step accounting: step-time histogram + a memory
    sample. Call once per step from the training loop."""
    if not metrics.enabled():
        return
    metrics.counter("step.count").inc()
    metrics.histogram("step.ms").observe(seconds * 1e3)
    sample_memory(context)

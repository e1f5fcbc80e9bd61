"""Built-in instruments: XLA compile accounting + device memory watermarks.

Three groups of ready-made telemetry, all writing into the metrics
registry (metrics.py):

* **Compile events** — ``install_jax_hooks()`` registers
  ``jax.monitoring`` listeners. Every backend compile increments
  ``jit.compile_count`` and feeds ``jit.compile_ms``; every jaxpr trace
  feeds ``jit.trace_count``/``jit.trace_ms``. A steady-state training
  loop must show a FLAT compile count — a climbing one is the recompile
  storm the round-5 review's bucketing ask wants ruled out. With
  ``MXNET_TELEMETRY_RETRACE=1`` the hooks also flip jax's
  ``explain_cache_misses`` and keep the most recent cause strings
  (``retrace_causes()``), which ``dump_metrics()`` appends as comments.
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
import logging
import os
import threading

from . import metrics

__all__ = ["install_jax_hooks", "sample_memory", "record_step",
           "retrace_causes"]

_install_lock = threading.Lock()
_installed = False  # guarded-by: _install_lock
_retrace_log = collections.deque(maxlen=32)

# jax.monitoring event -> short metric stem
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
}


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
    if event == "/jax/compilation_cache/cache_hits":
        metrics.counter("jit.persistent_cache_hits").inc()


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
    """Idempotently register the jax.monitoring listeners (and, when
    MXNET_TELEMETRY_RETRACE is set, the cache-miss explainer). Called
    automatically from ``metrics.set_enabled(True)`` / config's flag
    applier; safe to call directly."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)

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

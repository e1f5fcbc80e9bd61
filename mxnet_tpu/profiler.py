"""Profiler (reference: python/mxnet/profiler.py:27-55,
src/engine/profiler.cc — per-op chrome://tracing JSON).

Two complementary layers here:

1. **Framework events** — when profiling runs, the eager op dispatcher
   and the graph executor record per-op / per-program events with host
   timestamps and write the reference's chrome://tracing JSON format on
   ``dump_profile()`` (load it in chrome://tracing or Perfetto, or feed
   it to ``tools/trace_report.py`` for a top-K op-time table). Mode
   'symbolic' records only whole-program executor runs (the engine-op
   analog); 'imperative' only eager ops; 'all' records both. While
   profiling, eager ops run synchronously (block_until_ready) so
   durations mean compute, not dispatch — the reference's profiler
   measures inside the engine worker the same way. Framework *phase
   spans* (observability.trace_span: trainer step and enqueue, fit-loop
   forward/backward/update, kvstore push/pull) record in ANY mode while
   the session runs, and also whenever telemetry is enabled — phases
   are not ops, so the mode split does not gate them. These events
   carry ``perf_counter`` timestamps: the chrome JSON is the host's
   timeline alone.
2. **XLA device trace** — set_state('run') also starts the JAX/XLA
   profiler in ``<filename>_trace/``. Its ``.xplane.pb`` holds the
   device's operations AND every ``trace_span`` (as a
   ``TraceAnnotation`` in the host plane) on one clock, with the
   ``device_scope`` labels in each operation's ``op_name``:
   ``tools/trace_report.py <dir>`` reads it for device time by phase
   and scope and for the host span over each idle gap (and still reads
   the ``*.trace.json.gz`` beside it for the top-K table).

The initial mode can be set from the environment (``MXNET_PROFILER_MODE``)
so unmodified scripts can be traced. All state transitions take the
module lock, and ``dump_profile()`` writes via temp-file + atomic rename
so a concurrent reader (a dashboard tailing the file, the CI artifact
scraper) never observes truncated JSON.

The event buffer is a bounded ring (``MXNET_PROFILER_RING`` events,
default 200k): a week-long serving process with a session left running
(or the always-on span tail the flight recorder embeds) can never grow
host memory without bound. When the ring is full the OLDEST event is
dropped and counted — :func:`dropped_events`, the
``profiler.events_dropped`` metric, and a ``droppedEventsCount`` field
in the dump all expose the loss, so a truncated trace is visible, never
silent.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "pause", "resume", "events_tail", "record_raw",
           "dropped_events", "configure_ring"]

_VALID_MODES = ("symbolic", "imperative", "all")


def _env_mode():
    mode = os.environ.get("MXNET_PROFILER_MODE", "symbolic")
    return mode if mode in _VALID_MODES else "symbolic"


_state = {"mode": _env_mode(), "filename": "profile.json", "running": False,
          "paused": False}  # guarded-by: _lock
_events = collections.deque()  # bounded ring, manual cap  # guarded-by: _lock
_ring_cap = None  # resolved lazily from MXNET_PROFILER_RING  # guarded-by: _lock
_dropped = 0  # events evicted from the full ring  # guarded-by: _lock
_lock = threading.Lock()
_trace_lock = threading.Lock()  # serializes jax device-trace start/stop
_t0 = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def imperative_active():
    return (_state["running"] and not _state["paused"]
            and _state["mode"] in ("imperative", "all"))


def symbolic_active():
    return (_state["running"] and not _state["paused"]
            and _state["mode"] in ("symbolic", "all"))


def spans_active():
    """Whether a session runs: phase spans (observability.trace_span)
    record in any mode while it does (and while telemetry is enabled)."""
    return _state["running"] and not _state["paused"]


def _cap_locked():
    # caller holds _lock — the _locked suffix contract
    global _ring_cap
    if _ring_cap is None:
        from .config import get_flag

        _ring_cap = max(1024, get_flag("MXNET_PROFILER_RING"))  # graftlint: disable=G004 — under _lock via every caller (_append/configure_ring)
    return _ring_cap


def configure_ring(capacity=None):
    """Runtime override of the event-ring capacity (tests; None restores
    the MXNET_PROFILER_RING flag resolution). Excess oldest events are
    evicted (and counted) immediately."""
    global _ring_cap
    evicted = 0
    with _lock:
        _ring_cap = None if capacity is None else max(1, int(capacity))
        cap = _cap_locked()
        while len(_events) > cap:
            _events.popleft()
            evicted += 1
        _count_dropped_locked(evicted)
    _note_dropped_metric(evicted)


def _count_dropped_locked(n):
    # caller holds _lock — the _locked suffix contract
    global _dropped
    _dropped += n  # graftlint: disable=G004 — under _lock via every caller (_append/configure_ring)


def _note_dropped_metric(n):
    if not n:
        return
    try:
        from .observability import metrics as _metrics

        _metrics.counter(
            "profiler.events_dropped",
            help="profiler ring evictions (trace tail truncated)").inc(n)
    except Exception:  # the ring must keep working during teardown
        pass


def dropped_events():
    """How many events the bounded ring has evicted since the last
    ``dump_profile`` (0 = the current buffer/trace is complete; the
    ``profiler.events_dropped`` metric keeps the cumulative count)."""
    with _lock:
        return _dropped


def _append(ev):
    with _lock:
        dropped = len(_events) >= _cap_locked()
        if dropped:
            _events.popleft()
            _count_dropped_locked(1)
        _events.append(ev)
    if dropped:
        _note_dropped_metric(1)


def _note_pid():
    global _pid
    _pid = os.getpid()


# getpid is a system call (5 us on a sandboxed host): asked once, and
# again in a forked child
_note_pid()
os.register_at_fork(after_in_child=_note_pid)


def record(name, cat, ts_us, dur_us, args=None, tid=None):
    """Append one complete ('ph':'X') event. ``args`` rides into the
    chrome JSON verbatim (request tracing stores trace ids there);
    ``tid`` overrides the recording thread's id (a trace emitted at
    completion replays spans onto the threads where they happened)."""
    ev = {"name": name, "cat": cat, "ph": "X",
          "ts": ts_us, "dur": dur_us,
          "pid": _pid,
          "tid": (threading.get_ident() % (1 << 20)
                  if tid is None else int(tid))}
    if args:
        ev["args"] = dict(args)
    _append(ev)


def record_raw(ev):
    """Append one pre-built chrome-trace event dict (flow events,
    instant events — phases the 'X' shape cannot express)."""
    _append(dict(ev))


def events_tail(n=256):
    """Copy of the most recent ``n`` recorded events (the flight
    recorder embeds this tail in its crash dump). Collected from the
    ring's right end — O(n), never an O(ring-capacity) copy under the
    lock recording threads contend on."""
    import itertools

    with _lock:
        tail = list(itertools.islice(reversed(_events), max(0, int(n))))
    tail.reverse()
    return tail


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(reference: profiler.py:profiler_set_config); mode is 'symbolic',
    'imperative', or 'all'."""
    if mode not in _VALID_MODES:
        raise ValueError("mode must be symbolic/imperative/all, got %r"
                         % (mode,))
    with _lock:
        _state["mode"] = mode
        _state["filename"] = filename


def profiler_set_state(state="stop"):
    """(reference: profiler.py:profiler_set_state); 'run' starts
    recording (+ a JAX device trace), 'stop' ends it."""
    import jax

    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop', got %r"
                         % (state,))
    with _lock:
        if state == "run" and not _state["running"]:
            trace_dir = os.path.splitext(_state["filename"])[0] + "_trace"
            start_trace = True
            _state["running"] = True
            _state["paused"] = False
        elif state == "stop" and _state["running"]:
            start_trace = False
            _state["running"] = False
        else:
            return
    # the jax profiler calls run outside _lock (start_trace can spend
    # tens of ms in the backend and must not serialize against record())
    # but under _trace_lock, which serializes start vs stop so a stop
    # racing a just-started run cannot leak a running device trace
    if start_trace:
        with _trace_lock:
            try:
                jax.profiler.start_trace(trace_dir)
            except BaseException:
                # no device trace, no session: a caller that asked for a
                # profile must hear that it is not getting one
                with _lock:
                    _state["running"] = False
                raise
            with _lock:
                _state["trace_dir"] = trace_dir
                still_running = _state["running"]
        if not still_running:
            # a concurrent stop won the race before our trace_dir was
            # visible to it; the stop is on us
            _stop_device_trace(jax)
    else:
        _stop_device_trace(jax)


def _stop_device_trace(jax):
    """Stop the XLA device trace if one is recorded in _state."""
    with _trace_lock:
        with _lock:
            trace_dir, _state["trace_dir"] = _state.get("trace_dir"), None
        if trace_dir:
            jax.profiler.stop_trace()


def pause():
    """Suspend event recording without ending the session
    (reference: profiler.py pause)."""
    with _lock:
        _state["paused"] = True


def resume():
    """(reference: profiler.py resume)"""
    with _lock:
        _state["paused"] = False


def dump_profile():
    """Stop profiling and write the chrome://tracing JSON
    (reference: profiler.py:dump_profile → DumpProfile,
    src/engine/profiler.h:107). The write is atomic (temp file +
    rename): a concurrent reader sees either the previous dump or the
    complete new one, never a truncated file."""
    global _dropped
    profiler_set_state("stop")
    with _lock:
        events = list(_events)
        _events.clear()
        filename = _state["filename"]
        # the dump consumes the loss: dropped counts what THIS artifact
        # is missing, and a later session's complete dump must not
        # inherit it (the events_dropped metric stays cumulative)
        dropped, _dropped = _dropped, 0
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    if dropped:
        # non-standard but chrome-ignored: makes ring truncation visible
        # in the artifact itself, not just the live process
        payload["droppedEventsCount"] = dropped
    tmp = "%s.tmp.%d.%d" % (filename, os.getpid(), threading.get_ident())
    with open(tmp, "w") as f:
        # json.dumps hits the C encoder; json.dump streams through the
        # pure-Python one — 10-50x slower, which matters at profiler
        # event volumes (hundreds of thousands of events per dump)
        f.write(json.dumps(payload))
    os.replace(tmp, filename)
    return filename


# aliased modern names
set_config = profiler_set_config
set_state = profiler_set_state

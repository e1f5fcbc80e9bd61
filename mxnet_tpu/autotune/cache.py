"""Persistent per-device tuning cache — the "nobody pays the search twice"
half of the autotuner (ISSUE 6; TVM's schedule-search loop keeps the same
artifact, its "tuning log").

One JSON file maps ``(device fingerprint, op, shape-bucket, dtype)`` to the
winning candidate of a measured search (autotune/search.py). Consumers
(:func:`mxnet_tpu.parallel.fused.resolve_blocks`, the executor's
program build, ``serving.InferenceServer``) call :func:`lookup` at trace
time: a hit costs one dict probe, a miss falls back to the hand-picked
config.py defaults — searching only ever happens through the explicit
``tune_*`` entry points or ``MXNET_TUNE=1``.

File protocol:

* Path: ``MXNET_TUNE_CACHE`` env, else
  ``$XDG_CACHE_HOME/mxnet_tpu/tuning.json`` (``~/.cache`` fallback).
* Writes are atomic (temp file + ``os.replace``, the profiler-dump
  protocol) and **merge-on-write**: the writer re-reads the file and
  unions it with its own entries before renaming, so two concurrent
  tuners tuning different ops both land. Last-writer-wins per key.
* The device fingerprint is part of the key, so moving the cache file to
  a different chip makes every entry miss (stale-by-construction rather
  than stale-and-wrong); :func:`scrub_stale` physically drops foreign
  entries.

Counters (:func:`stats`): ``hits`` / ``misses`` / ``measurements`` /
``searches`` — the regression surface for "a second process with a warm
cache performs zero search measurements" (tests/test_autotune.py,
tools/autotune_smoke.py).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

__all__ = ["cache_path", "device_fingerprint", "lookup", "lookup_entry",
           "record", "entries", "reload", "reset", "scrub_stale",
           "stats", "reset_stats", "note_measurements", "note_search"]

_lock = threading.RLock()
_entries = None          # key -> entry dict; None = not loaded  # guarded-by: _lock
_loaded_path = None      # path _entries came from  # guarded-by: _lock
_stats = {"hits": 0, "misses": 0, "measurements": 0, "searches": 0,
          "records": 0}  # guarded-by: _lock
_fp_probe = None         # memoized backend probe  # guarded-by: _lock

_VERSION = 1


def cache_path():
    """Resolved cache file path (``MXNET_TUNE_CACHE`` > XDG default)."""
    env = os.environ.get("MXNET_TUNE_CACHE")
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(base, "mxnet_tpu", "tuning.json")


def device_fingerprint():
    """Stable id of the chip entries were measured on, e.g.
    ``tpu:TPU v5 lite`` / ``cpu:cpu``. ``MXNET_TUNE_FINGERPRINT``
    overrides (tests; or shipping one cache to a known fleet)."""
    global _fp_probe
    env = os.environ.get("MXNET_TUNE_FINGERPRINT")
    if env:
        return env
    with _lock:
        if _fp_probe is not None:
            return _fp_probe
    try:
        import jax

        dev = jax.devices()[0]
        probe = "%s:%s" % (dev.platform, getattr(dev, "device_kind", "?"))
    except Exception:
        probe = "unknown"
    with _lock:
        _fp_probe = probe
    return probe


def _canon(key):
    """Deterministic string form of a shape-bucket key (str / scalars /
    nested tuples / dicts of those)."""
    if isinstance(key, str):
        return key
    if isinstance(key, dict):
        return ",".join("%s=%s" % (k, _canon(key[k])) for k in sorted(key))
    if isinstance(key, (list, tuple)):
        return ",".join(_canon(k) for k in key)
    return str(key)


def _full_key(op, key, dtype, fingerprint=None):
    fp = fingerprint or device_fingerprint()
    return "|".join([fp, str(op), _canon(key), str(dtype or "-")])


def _mode():
    from ..config import get_flag

    return get_flag("MXNET_TUNE")


def _load_file(path):
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(payload, dict) or "entries" not in payload:
        return {}
    ent = payload["entries"]
    if not isinstance(ent, dict):
        return {}
    # drop non-dict entry bodies at the boundary: a hand-edited entry
    # must read as a miss everywhere (lookup, scrub, save), not crash
    return {k: v for k, v in ent.items() if isinstance(v, dict)}


def _ensure_loaded():
    # RLock: callers already inside `with _lock:` re-enter harmlessly
    global _entries, _loaded_path
    with _lock:
        path = cache_path()
        if _entries is None or _loaded_path != path:
            _entries = _load_file(path)
            _loaded_path = path
        return _entries


def lookup(op, key, dtype=None):
    """Tuned value for ``(device, op, key, dtype)`` or None. This is the
    trace-time hot path: one dict probe on a loaded cache. Returns None
    without touching the cache when ``MXNET_TUNE=-1`` (bypass)."""
    if _mode() < 0:
        return None
    entry = lookup_entry(op, key, dtype)
    return entry.get("value") if entry else None


def lookup_entry(op, key, dtype=None):
    """Full cache entry dict (value + provenance) or None."""
    k = _full_key(op, key, dtype)
    with _lock:
        ent = _ensure_loaded()
        entry = ent.get(k)
        # counter writes are idempotent accounting, not program semantics
        if entry is not None:
            _stats["hits"] += 1  # graftlint: disable=G003 — lock-guarded hit accounting, idempotent under retrace
        else:
            _stats["misses"] += 1  # graftlint: disable=G003 — lock-guarded miss accounting, idempotent under retrace
    return entry


def record(op, key, value, dtype=None, ms=None, trials=None, extra=None,
           persist=True):
    """Store a search winner and (by default) persist the cache file.
    Returns the full entry."""
    fp = device_fingerprint()
    entry = {"value": value, "fingerprint": fp, "op": str(op),
             "key": _canon(key), "dtype": str(dtype or "-"),
             "time": time.strftime("%Y-%m-%dT%H:%M:%S")}
    if ms is not None:
        entry["ms"] = round(float(ms), 4)
    if trials is not None:
        entry["trials"] = int(trials)
    if extra:
        entry.update(extra)
    k = _full_key(op, key, dtype, fingerprint=fp)
    with _lock:
        ent = _ensure_loaded()
        ent[k] = entry
        _stats["records"] += 1
    if persist:
        save()
    return entry


def _write_file(path, entries_dict):
    """The one atomic write protocol (makedirs + temp + os.replace) —
    shared by save() and scrub_stale() so it can never drift."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), threading.get_ident())
    with open(tmp, "w") as f:
        json.dump({"version": _VERSION, "entries": entries_dict}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)


@contextlib.contextmanager
def _file_lock(path):
    """Advisory cross-process lock (POSIX flock on a sidecar .lock file)
    around the read-merge-write window, so two processes saving at the
    same instant cannot drop each other's entries. Degrades to a no-op
    where flock is unavailable — the atomic rename still guarantees
    readers never see a torn file."""
    lock_path = path + ".lock"
    try:
        import fcntl

        d = os.path.dirname(lock_path)
        if d:
            os.makedirs(d, exist_ok=True)
        lf = open(lock_path, "w")
    except Exception:
        yield
        return
    try:
        fcntl.flock(lf, fcntl.LOCK_EX)
        yield
    finally:
        try:
            fcntl.flock(lf, fcntl.LOCK_UN)
        finally:
            lf.close()


def save():
    """Atomic merge-on-write: union the on-disk entries with ours (ours
    win per key), temp+rename. The whole read-merge-write runs under the
    lock, so concurrent in-process tuners serialize and lose no entries;
    concurrent PROCESSES are covered by the re-read (their already-
    flushed entries merge in) plus each of their own subsequent saves."""
    global _entries, _loaded_path
    with _lock:
        path = cache_path()
        with _file_lock(path):
            merged = _load_file(path)
            merged.update(_ensure_loaded())
            _write_file(path, merged)
        _entries = merged
        _loaded_path = path
    return path


def entries():
    """Copy of the loaded entry map (tests/reporting)."""
    with _lock:
        return dict(_ensure_loaded())


def reload():
    """Force a re-read of the cache file (e.g. after another process
    tuned)."""
    global _entries
    with _lock:
        _entries = None
        return dict(_ensure_loaded())


def reset():
    """Drop the in-memory cache and fingerprint probe (tests; simulates a
    fresh process — the file on disk is untouched)."""
    global _entries, _loaded_path, _fp_probe
    with _lock:
        _entries = None
        _loaded_path = None
        _fp_probe = None


def scrub_stale(persist=True):
    """Drop entries recorded under a different device fingerprint than the
    current one. Returns the number dropped. (Fingerprint is part of the
    key, so stale entries can never *match* — scrubbing just reclaims
    the file.)

    With ``persist`` the write is a merge-then-scrub under the file
    lock: entries another process saved since we loaded survive (only
    foreign-fingerprint keys are dropped, from the MERGED map) — the
    same lost-update discipline as :func:`save`."""
    global _entries, _loaded_path
    fp = device_fingerprint()

    def _is_stale(k, v):
        return v.get("fingerprint", k.split("|", 1)[0]) != fp

    with _lock:
        ent = _ensure_loaded()
        if not persist:
            stale = [k for k, v in ent.items() if _is_stale(k, v)]
            for k in stale:
                del ent[k]
            return len(stale)
        path = cache_path()
        with _file_lock(path):
            merged = _load_file(path)
            merged.update(ent)
            stale = [k for k, v in merged.items() if _is_stale(k, v)]
            for k in stale:
                del merged[k]
            _write_file(path, merged)
        _entries = merged
        _loaded_path = path
    return len(stale)


# ------------------------------------------------------------- accounting
def note_measurements(n=1):
    """Called by the search driver once per measured candidate — the
    counter the zero-measurement-on-warm-cache regression tests read."""
    with _lock:
        _stats["measurements"] += n
    try:
        from ..observability import metrics

        metrics.counter("autotune.measurements").inc(n)
    except Exception:
        pass


def note_search():
    with _lock:
        _stats["searches"] += 1


def stats():
    """Copy of {hits, misses, measurements, searches, records}."""
    with _lock:
        return dict(_stats)


def reset_stats():
    with _lock:
        for k in _stats:
            _stats[k] = 0

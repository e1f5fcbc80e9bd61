"""Active training-health layer: on-device numerical anomaly detection
fused into the step (no reference counterpart — the reference's answer to
"why did my run go bad" is MXNET_ENGINE_TYPE=NaiveEngine and a debugger).

One call per optimization step — :func:`guard_step` — does all of:

* **One fused non-finite reduction** over every watched tensor (loss,
  gradients, parameters). All per-tensor statistics (non-finite count,
  finite-masked sum of squares, finite-masked sum) are computed in a
  single jitted program and fetched to the host as ONE tiny (n, 3) array
  — never a per-tensor sync (graftlint G001 clean). The sums are masked
  to the finite elements so the grad-norm trajectory stays readable on
  the very step a NaN appears.
* **Health gauges** — global gradient norm, parameter norm, and the
  update-to-param ratio ``lr * ||g|| / ||w||`` (the classic "learning
  rate too hot" early-warning signal), recorded into the metrics
  registry when telemetry is on.
* **A flight-recorder step record** (flight_recorder.py): loss, grad
  norm, lr, HBM watermark, step wall time, cumulative compile count —
  the last-K ring that survives the crash it explains.
* **Policy** (``MXNET_HEALTH=off|warn|raise|skip_step``):

  - ``off`` (default): :func:`active` is False and every call site takes
    its existing zero-cost no-op path (one cached module-global read).
  - ``warn``: log the anomaly, dump the flight recorder (throttled), and
    keep training. Warn mode fetches the fused stats with a ONE-STEP
    LAG: the (n, 3) result is a device future stashed at step N and
    read at step N+1 — by then it has long completed, so the loop's
    async dispatch pipeline never drains (a synchronous per-step fetch
    costs far more in lost overlap than the reduction itself).
    Attribution stays exact —
    the stash carries its own step/tensor metadata, so the dump and
    triage report name the step the NaN occurred, one step after it ran.
    Pending stats are flushed at fit end, on any dump, and at exit.
  - ``raise``: dump, then raise :class:`TrainingHealthError` on the step
    the anomaly occurred — the fail-fast mode for CI and debugging.
    Synchronous (the fetch waits on the step; drain cost accepted).
  - ``skip_step``: additionally tell the caller to DROP this update
    (``verdict.skip``) so parameters stay finite; training continues on
    the next batch (the "loss-scale-style skip" for rare overflow
    blips). Synchronous — the verdict must gate the update it protects.

Call sites: the module ``fit`` loop (module/base_module.py), gluon
``Trainer.step`` and ``compile_step`` (gluon/trainer.py), the autograd
backward tape (autograd.py, loss heads), and ``Executor.health_check``
for direct executor users. ``skip_step`` is applied wherever an update
can actually be withheld (fit loop, Trainer, compile_step writeback);
the backward-path check treats it as ``warn`` and relies on the update
site's own check to do the skipping.

The compile counter here is independent of MXNET_TELEMETRY: when health
is active a ``jax.monitoring`` listener counts backend compiles so the
flight recorder can show compile storms even with telemetry off.
"""
from __future__ import annotations

import logging
import os
import threading

import numpy as np

from ..base import MXNetError

__all__ = ["TrainingHealthError", "Verdict", "policy", "set_policy",
           "active", "check", "guard_step", "flush", "compiles"]

_POLICIES = ("off", "warn", "raise", "skip_step")

_lock = threading.Lock()
_policy = None        # resolved policy string, lazy from env  # guarded-by: _lock
_compiles = 0         # backend compiles since hook install  # guarded-by: _lock
_hooks_installed = False  # guarded-by: _lock
_anomaly_log_count = 0    # throttles anomaly WARNING spam  # guarded-by: _lock
_pending = None       # warn-mode lag-1 stash: (stats future, meta)  # guarded-by: _lock
_stats_fn = None          # jitted fused reduction (built on first use)


class TrainingHealthError(MXNetError):
    """Raised by the ``raise`` policy when a step produces non-finite
    values; carries the verdict for programmatic triage."""

    def __init__(self, verdict):
        self.verdict = verdict
        super().__init__(
            "training health: non-finite values at step %s in %s "
            "(first bad tensor: %s; %s) — flight recorder dump: %s"
            % (verdict.step, verdict.where, verdict.first_bad,
               ", ".join("%s=%d" % (n, c) for n, c in verdict.bad[:4]),
               verdict.dump_path
               or "throttled (covered by the next dump / exit flush)"))


def _read_policy():
    # string-valued like MXNET_PROFILER_MODE: read straight from the
    # environment, NOT through the integer get_flag machinery
    p = os.environ.get("MXNET_HEALTH", "off").strip().lower()
    if p in _POLICIES:
        return p
    if p:
        # the user explicitly asked for protection; silently running
        # unprotected because of a typo is the worst failure mode here
        logging.warning(
            "MXNET_HEALTH=%r is not one of %s — health checking is OFF",
            p, "|".join(_POLICIES))
    return "off"


def policy():
    """Current health policy string (``MXNET_HEALTH``, overridable at
    runtime with :func:`set_policy`)."""
    global _policy
    if _policy is None:
        with _lock:
            if _policy is None:
                _policy = _read_policy()
    return _policy


def set_policy(p):
    """Programmatic policy override (``None`` re-reads the env)."""
    global _policy
    if p is not None and p not in _POLICIES:
        raise ValueError("MXNET_HEALTH policy must be one of %s, got %r"
                         % (_POLICIES, p))
    with _lock:
        _policy = p
    if p is not None and p != "off":
        _ensure_hooks()


def active():
    """True when any checking policy is in effect. Call sites guard on
    this so ``off`` costs one cached read per step."""
    return policy() != "off"


# --------------------------------------------------------- compile counter
def _on_compile_event(event, duration_secs, **kwargs):
    global _compiles
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _compiles += 1


def _ensure_hooks():
    """Install the health-owned jax.monitoring compile listener once (so
    compile storms show in the flight recorder without MXNET_TELEMETRY)."""
    global _hooks_installed
    with _lock:
        if _hooks_installed:
            return
        _hooks_installed = True
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
    except Exception:  # pragma: no cover - jax always present in-tree
        pass


def compiles():
    """Cumulative backend compiles observed since the health hooks were
    installed (0 until the first active check)."""
    return _compiles


# --------------------------------------------------------- fused reduction
def _stats_impl(arrs):
    """Per-array [non-finite count, finite sum(x^2), finite sum(x)] in one
    program; returns an (n, 3) float32 array — the ONE host fetch."""
    import jax.numpy as jnp

    rows = []
    for a in arrs:
        x = a.astype(jnp.float32)
        finite = jnp.isfinite(x)
        xf = jnp.where(finite, x, jnp.float32(0.0))
        # count the BAD elements in integer dtype: float32 accumulation
        # of size-or-finite counts loses exactness past 2^24 elements,
        # which could round 3 NaNs in a 33M-element gradient to bad=0 —
        # summing ~finite is exactly 0 for healthy tensors of any size
        bad = jnp.sum(~finite, dtype=jnp.int32).astype(jnp.float32)
        rows.append(jnp.stack([bad, jnp.sum(xf * xf), jnp.sum(xf)]))
    return jnp.stack(rows)


def _fused_stats(datas):
    global _stats_fn
    if _stats_fn is None:
        import jax

        # one module-level jitted program; jax's signature cache keys on
        # the tuple's shapes/dtypes, so stable training loops trace once
        _stats_fn = jax.jit(_stats_impl)
    return _stats_fn(tuple(datas))


def _raw(a):
    """NDArray or raw jax array -> raw array."""
    return a._data if hasattr(a, "_data") else a


def _is_inexact(data):
    dt = getattr(data, "dtype", None)
    if dt is None:
        return False
    name = getattr(dt, "name", str(dt))
    return name in ("bfloat16", "float16", "float32", "float64",
                    "complex64", "complex128")


class Verdict:
    """Result of one fused health check."""

    __slots__ = ("ok", "skip", "step", "where", "bad", "first_bad", "loss",
                 "grad_norm", "param_norm", "update_ratio", "lr",
                 "dump_path")

    def __init__(self):
        self.ok = True
        self.skip = False
        self.step = None
        self.where = ""
        self.bad = []          # [(name, non-finite count), ...]
        self.first_bad = None  # first bad tensor name, check order
        self.loss = None
        self.grad_norm = None
        self.param_norm = None
        self.update_ratio = None
        self.lr = None
        self.dump_path = None

    def as_record(self):
        return {"step": self.step, "where": self.where, "ok": self.ok,
                "skipped": self.skip, "loss": self.loss,
                "grad_norm": self.grad_norm, "param_norm": self.param_norm,
                "update_ratio": self.update_ratio, "lr": self.lr,
                "bad": list(self.bad), "first_bad": self.first_bad}


def _gather(losses, grads, params):
    """[(kind, name, raw array)] over the inexact-dtype inputs."""
    named = []
    for kind, group in (("loss", losses), ("grad", grads),
                        ("param", params)):
        for name, arr in group:
            data = _raw(arr)
            if data is not None and _is_inexact(data):
                named.append((kind, name, data))
    return named


def _meta_of(named):
    """Array-free metadata [(kind, name, size)]: the lag-1 stash must not
    pin the step's input buffers (they may be donated by the next step)."""
    out = []
    for kind, name, data in named:
        size = 1
        for dim in getattr(data, "shape", ()):
            size *= int(dim)
        out.append((kind, name, size))
    return out


def _evaluate(stats, meta, lr, step, where):
    """Build a Verdict from the fetched (n, 3) stats + metadata."""
    v = Verdict()
    v.step = step
    v.where = where
    v.lr = lr
    grad_ss = param_ss = 0.0
    have_grad = have_param = False
    for (kind, name, size), (bad, ss, total) in zip(meta, stats):
        if bad > 0:
            v.ok = False
            v.bad.append(("%s:%s" % (kind, name), int(bad)))
            if v.first_bad is None:
                v.first_bad = "%s:%s" % (kind, name)
        if kind == "loss" and v.loss is None:
            v.loss = float(total) / max(size, 1)
        elif kind == "grad":
            grad_ss += float(ss)
            have_grad = True
        elif kind == "param":
            param_ss += float(ss)
            have_param = True
    if have_grad:
        v.grad_norm = float(np.sqrt(grad_ss))
    if have_param:
        v.param_norm = float(np.sqrt(param_ss))
    if lr is not None and v.grad_norm is not None and v.param_norm:
        v.update_ratio = float(lr) * v.grad_norm / (v.param_norm + 1e-20)
    return v


def check(losses=(), grads=(), params=(), lr=None, step=None, where=""):
    """Run the fused reduction over the named tensors and build a
    :class:`Verdict` synchronously (no policy applied, no recording).
    Each of ``losses``/``grads``/``params`` is an iterable of
    ``(name, array)`` with NDArray or raw jax arrays. Returns None when
    nothing watchable (no inexact-dtype tensors) was passed."""
    named = _gather(losses, grads, params)
    if not named:
        return None
    # ONE fused device program + ONE tiny host fetch for the whole step
    stats = np.asarray(_fused_stats([d for _k, _n, d in named]))
    return _evaluate(stats, _meta_of(named), lr, step, where)


_site_steps = {}  # call-site -> monotonic step counter  # guarded-by: _lock


def next_step(site):
    """Per-call-site monotonic step counter for wiring points with no
    natural index of their own (one backward == one eager training step),
    so their ring records — and the triage report's 'first bad step' —
    name a real batch number instead of None."""
    with _lock:
        _site_steps[site] = _site_steps.get(site, 0) + 1
        return _site_steps[site]


def skip_allowed(kvstore):
    """May a skip_step verdict actually withhold the update given this
    kvstore? A worker-LOCAL skip in front of a dist_sync push would make
    workers disagree about entering the compiled cross-process
    all-reduce — the healthy workers hang in the collective forever. So
    skipping is allowed for local/device stores and for dist_async
    (pushes are per-worker and the server applies them independently —
    withholding one worker's poisoned push is exactly right), but under
    synchronous distributed stores skip_step degrades to warn."""
    kv_type = getattr(kvstore, "type", "") if kvstore is not None else ""
    return not ("dist" in kv_type and "async" not in kv_type)


def _record_gauges(v):
    from . import metrics

    if not metrics.enabled():
        return
    metrics.counter("health.checks").inc()
    if v.grad_norm is not None:
        metrics.gauge("health.grad_norm").set(v.grad_norm)
    if v.update_ratio is not None:
        metrics.gauge("health.update_ratio").set(v.update_ratio)
    if not v.ok:
        metrics.counter("health.anomalies").inc()
    if v.skip:
        metrics.counter("health.skipped_steps").inc()


def _log_anomaly(v):
    """WARNING for the first few anomalies, then every 100th — a stuck-NaN
    run must not drown the log it is supposed to explain."""
    global _anomaly_log_count
    with _lock:
        _anomaly_log_count += 1
        n = _anomaly_log_count
    if n <= 5 or n % 100 == 0:
        logging.warning(
            "training health [%s]: non-finite values at step %s "
            "(first bad: %s; %s)%s%s",
            v.where, v.step, v.first_bad,
            ", ".join("%s=%d" % (name, c) for name, c in v.bad[:4]),
            " — SKIPPING update" if v.skip else "",
            (" — dump: %s" % v.dump_path) if v.dump_path else "")


def _hbm_watermark():
    """Peak device-memory bytes right now (so the OOM story the flight
    recorder exists for is never silently blank). Independent of
    MXNET_TELEMETRY; one cheap call per guarded step."""
    from .instruments import device_peak_bytes

    return device_peak_bytes()


def _commit(v, wall_s, allow_dump=True):
    """Gauges + flight-recorder record + (throttled) anomaly dump/log for
    an evaluated verdict; never raises (the raise policy raises at its
    call site, after this bookkeeping)."""
    from . import flight_recorder

    _record_gauges(v)
    rec = v.as_record()
    rec["wall_ms"] = round(wall_s * 1e3, 3) if wall_s is not None else None
    rec["compiles"] = compiles()
    rec["hbm_bytes"] = _hbm_watermark()
    flight_recorder.record(rec, anomaly=not v.ok)
    if not v.ok:
        if allow_dump:
            v.dump_path = flight_recorder.dump_on_anomaly(
                "anomaly:%s:step=%s:first_bad=%s"
                % (v.where, v.step, v.first_bad))
        _log_anomaly(v)
    return v


def _finish_pending(pending, allow_dump=True):
    """Fetch + evaluate + commit a lag-1 stash (warn semantics: no raise,
    no skip). A stash whose buffer died with its backend is dropped."""
    stats_dev, meta, lr, step, where, wall_s = pending
    try:
        stats = np.asarray(stats_dev)
    except Exception:
        return None
    return _commit(_evaluate(stats, meta, lr, step, where), wall_s,
                   allow_dump=allow_dump)


def _take_pending():
    global _pending
    with _lock:
        pending, _pending = _pending, None
    return pending


def flush(allow_dump=True):
    """Evaluate the warn-mode lag-1 stash now (fit end, dump time, exit).
    Returns the flushed Verdict or None."""
    pending = _take_pending()
    if pending is None:
        return None
    return _finish_pending(pending, allow_dump=allow_dump)


def guard_step(where, losses=(), grads=(), params=(), lr=None, step=None,
               wall_s=None, can_skip=True, sync=None):
    """The per-step entry point every wired front-end calls.

    Launches the fused reduction, records the flight-recorder step record
    and the health gauges, and applies the policy. Under ``raise`` and
    ``skip_step`` (or ``sync=True``) the result is fetched immediately
    and the returned Verdict describes THIS step (callers that can
    withhold the update drop it when ``verdict.skip``). Under ``warn``
    the fetch lags one step (see module docstring): the returned Verdict
    describes the PREVIOUS guarded step, and this step's stats are
    stashed for the next call / :func:`flush`. Returns None when the
    policy is ``off`` or nothing was watchable.
    """
    if not active():
        return None
    _ensure_hooks()
    from . import flight_recorder

    # any actively-guarded step arms the crash hooks: a later uncaught
    # exception dumps the ring this very call is about to extend
    flight_recorder.install()
    pol = policy()
    if sync is None:
        sync = pol in ("raise", "skip_step")

    named = _gather(losses, grads, params)
    if not named:
        return flush() if not sync else None
    stats_dev = _fused_stats([d for _k, _n, d in named])
    meta = _meta_of(named)

    if not sync:
        global _pending
        with _lock:
            prev, _pending = _pending, (stats_dev, meta, lr, step, where,
                                        wall_s)
        return _finish_pending(prev) if prev is not None else None

    flush()  # a stale warn stash must not outlive a sync verdict
    v = _evaluate(np.asarray(stats_dev), meta, lr, step, where)
    if not v.ok and pol == "skip_step" and can_skip:
        v.skip = True
    _commit(v, wall_s)
    if not v.ok and pol == "raise":
        raise TrainingHealthError(v)
    return v

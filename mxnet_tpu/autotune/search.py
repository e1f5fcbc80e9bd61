"""Measured search driver: analytic pruning, then real timings decide
(ISSUE 6 — the TVM schedule-search shape: cost model prunes,
measurement picks, cache remembers).

The driver is a grid/refinement hybrid over a :class:`~.registry.Tunable`'s
candidate space:

1. the tunable's analytic cost function scores every candidate and drops
   infeasible ones (``inf`` — e.g. VMEM overflow); the cheapest
   survivors fill the measurement budget (``MXNET_TUNE_TRIALS``),
2. each surviving candidate is timed by the caller-supplied ``measure``
   callable (median of k runs, warmup discarded — :func:`median_time`),
3. the remaining budget hill-climbs: one-notch neighbors of the current
   best are measured until the budget runs out or no unmeasured neighbor
   improves.

The hand-picked default is ALWAYS measured first (budget permitting), so
a tuned value can only beat or match it — the tuner never regresses a
config below the incumbent except for measurement noise.

Every measured candidate increments the cache's ``measurements``
counter; a warm cache hit never reaches this module at all (the
zero-measurement acceptance bar).
"""
from __future__ import annotations

import time

from . import cache

__all__ = ["SearchConfig", "SearchResult", "median_time", "search",
           "tune_and_record"]


class SearchConfig:
    """Measurement budget/protocol. ``trials`` = total measured
    candidates (default ``MXNET_TUNE_TRIALS``); ``repeats``/``warmup``
    feed :func:`median_time` when the measurer uses it."""

    def __init__(self, trials=None, repeats=3, warmup=1):
        if trials is None:
            from ..config import get_flag

            trials = get_flag("MXNET_TUNE_TRIALS")
        self.trials = max(1, int(trials))
        self.repeats = max(1, int(repeats))
        self.warmup = max(0, int(warmup))


class SearchResult:
    __slots__ = ("best", "best_s", "measured", "pruned", "log")

    def __init__(self, best, best_s, measured, pruned, log):
        self.best = best          # winning candidate dict
        self.best_s = best_s      # its measured seconds
        self.measured = measured  # number of candidates actually timed
        self.pruned = pruned      # dropped by the cost model
        self.log = log            # [(candidate, seconds)] in measure order

    def as_dict(self):
        return {"best": self.best, "best_ms": round(self.best_s * 1e3, 4),
                "measured": self.measured, "pruned": self.pruned}


def median_time(fn, repeats=3, warmup=1):
    """Median wall seconds of ``fn()`` over ``repeats`` runs after
    ``warmup`` discarded runs (the first pays the compile)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _frozen(candidate):
    def h(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, h(v)) for k, v in candidate.items()))


def _neighbors(candidate, space):
    """One-notch mutations of each param along its candidate axis."""
    out = []
    for param, values in space.items():
        values = list(values)
        try:
            i = values.index(candidate[param])
        except (KeyError, ValueError):
            continue
        for j in (i - 1, i + 1):
            if 0 <= j < len(values):
                mut = dict(candidate)
                mut[param] = values[j]
                out.append(mut)
    return out


def search(tunable, measure, ctx=None, cfg=None):
    """Run the pruned, measured search. ``measure(candidate) -> seconds``
    (the measurer owns its warmup/median protocol; :func:`median_time`
    is the standard helper). Returns a :class:`SearchResult`."""
    ctx = ctx or {}
    cfg = cfg or SearchConfig()
    cache.note_search()
    space = tunable.resolve_space(ctx)
    candidates = tunable.candidates(ctx)

    pruned = 0
    if tunable.cost is not None:
        scored = []
        for c in candidates:
            s = tunable.cost(c, ctx)
            if s == float("inf"):
                pruned += 1
            else:
                scored.append((s, c))
        scored.sort(key=lambda sc: sc[0])
        candidates = [c for _s, c in scored]
    if not candidates:
        raise ValueError("tunable %r: every candidate pruned (space %r)"
                         % (tunable.name, space))
    # incumbent first: the tuned value may only beat or match it
    ordered = []
    default = tunable.default_value(ctx)
    if default is not None:
        ordered.append(dict(default))
    ordered.extend(candidates)

    seen, log = set(), []

    def _measure(c):
        key = _frozen(c)
        if key in seen:
            return None
        seen.add(key)
        s = float(measure(c))
        cache.note_measurements(1)
        log.append((dict(c), s))
        return s

    budget = cfg.trials
    # wave 1: incumbent + cost-ranked grid (leave ~1/3 for refinement)
    wave = max(1, (2 * budget) // 3) if len(ordered) > budget else budget
    for c in ordered:
        if len(log) >= wave:
            break
        _measure(c)

    def _best():
        return min(log, key=lambda cs: cs[1])

    # wave 2: hill-climb one-notch neighbors of the running best
    while len(log) < budget:
        best_c, best_s = _best()
        nxt = [n for n in _neighbors(best_c, space)
               if _frozen(n) not in seen]
        if not nxt:
            # best's neighborhood exhausted: spend remaining budget on
            # the next cost-ranked unmeasured candidates
            nxt = [c for c in ordered if _frozen(c) not in seen][:1]
            if not nxt:
                break
        for n in nxt:
            if len(log) >= budget:
                break
            _measure(n)

    best_c, best_s = _best()
    return SearchResult(best_c, best_s, len(log), pruned, log)


def tune_and_record(op, key, measure, ctx=None, dtype=None, cfg=None):
    """search() + cache.record(): the one-call tuning entry point used by
    the concrete tuners. Returns the winning value dict."""
    from . import registry

    tunable = registry.get(op)
    result = search(tunable, measure, ctx=ctx, cfg=cfg)
    cache.record(op, key, result.best, dtype=dtype,
                 ms=result.best_s * 1e3, trials=result.measured)
    return result

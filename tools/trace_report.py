#!/usr/bin/env python
"""Trace analysis: top-K ops/phases by time from a profiler dump.

The per-HLO time budget the round-5 review's roofline ask demands, as a
tool:
feed it any chrome://tracing JSON — the framework profiler's
``dump_profile()`` output, or the ``*.trace.json.gz`` the JAX/XLA
profiler (XPlane) writes under ``<filename>_trace/`` — and it prints the
top-K event names by total time with per-row percent and
cumulative-percent columns, so "where did my step time go" is one
command:

    python tools/trace_report.py profile.json
    python tools/trace_report.py profile_trace/           # XPlane dir
    python tools/trace_report.py profile_trace/ --device  # by phase/scope
    python tools/trace_report.py --cell lm_train_t2048_b2 --steps 4

``--device`` reads the ``.xplane.pb`` the JAX profiler writes (the one
input that carries the program's scopes and the operations' stats):
device time by phase (forward / backward / update / unscoped, from the
``device_scope`` labels in each operation's ``op_name``) and by scope
(graph node, or ``l*/attn`` folded over layers), the costliest
operations with their share of the roofline where the trace carries
FLOPs and bytes, and the longest device idle gaps, each named by the
innermost ``trace_span`` that covers it. ``--cell`` builds a cell of
``BENCHMARK.json`` through ``perfbench.drivers``, traces a few steps of
it under ``mx.profiler.set_state('run')`` and prints that report.
    python tools/trace_report.py profile.json --cat operator -k 20
    python tools/trace_report.py --compare before.json after.json

``--compare`` prints a per-name regression diff (total-ms delta, sorted
by |delta|) between two traces — the artifact a perf PR should paste to
prove its claim.  With ``--perf`` it instead diffs the two sources'
roofline-attribution sections (MFU + waterfall-segment delta columns;
accepts flight-recorder dumps or ``BENCH_LEDGER.jsonl[:N]`` rows).
``--roofline DUMP`` / ``--waterfall DUMP`` print a dump's per-op
roofline table (ranked fusion candidates) and per-step wall-time
waterfall (tools/perf_report.py renders; docs/perf_observability.md).

Accepted inputs: a ``.json`` trace, a ``.json.gz`` / ``.gz`` trace, or a
directory that contains one (searched recursively, newest wins — the
layout ``jax.profiler`` writes: ``plugins/profile/<run>/*.trace.json.gz``).

Library use: :func:`load_events`, :func:`aggregate`, :func:`report_rows`
are importable (tests use them).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys


def load_events(path):
    """Complete ('X') events from a chrome trace file or XPlane trace
    dir; returns a list of {name, cat, ts, dur, pid, tid} dicts."""
    path = _resolve(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8", errors="replace") as f:
        payload = json.load(f)
    events = payload.get("traceEvents", payload) if isinstance(
        payload, dict) else payload
    out = []
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        dur = ev.get("dur")
        if dur is None:
            continue
        out.append(ev)
    return out


def _resolve(path):
    """Map a directory to the newest trace file inside it."""
    if not os.path.isdir(path):
        return path
    candidates = []
    for pattern in ("**/*.trace.json.gz", "**/*.trace.json", "**/*.json"):
        candidates = glob.glob(os.path.join(path, pattern), recursive=True)
        if candidates:
            break
    if not candidates:
        raise FileNotFoundError("no trace file under %r" % path)
    return max(candidates, key=os.path.getmtime)


def _self_times(events):
    """id(event) -> exclusive (self) duration in us.

    Per (pid, tid) timeline sweep: each event's duration minus the time
    spent in the events nested directly inside it. Self times are
    non-overlapping, so they sum to actual wall time — unlike inclusive
    durations, where a phase span and every op it contains would count
    the same wall time twice."""
    groups = {}
    for ev in events:
        groups.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    selfs = {}
    for evs in groups.values():
        # parents first at equal start (longer duration = outer span)
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []  # [(id(ev), end_ts)]
        for ev in evs:
            ts, dur = float(ev["ts"]), float(ev["dur"])
            while stack and ts >= stack[-1][1]:
                stack.pop()
            selfs[id(ev)] = dur
            if stack:
                selfs[stack[-1][0]] -= dur
            stack.append((id(ev), ts + dur))
    return selfs


def aggregate(events, cat=None):
    """Sum durations per (name, cat) ->
    {(name, cat): {count, total_us, self_us}}.

    Keyed by category as well as name: a framework phase span and an op
    can share a name (Module.forward's 'forward' span vs the executor's
    'forward' program event) and merging them would double-count the
    same wall time under one mislabeled row. Self times are computed on
    the FULL event set before any category filter, so a filtered view
    still subtracts children of other categories."""
    selfs = _self_times(events)
    agg = {}
    for ev in events:
        if cat is not None and ev.get("cat") != cat:
            continue
        key = (ev.get("name", "?"), ev.get("cat", ""))
        slot = agg.get(key)
        if slot is None:
            slot = agg[key] = {"count": 0, "total_us": 0.0, "self_us": 0.0}
        slot["count"] += 1
        slot["total_us"] += float(ev["dur"])
        slot["self_us"] += max(selfs.get(id(ev), 0.0), 0.0)
    return agg


def report_rows(agg, k=15):
    """Ranked rows [{rank, name, cat, count, total_ms, self_ms, avg_ms,
    pct, cum_pct}] for the top-k (name, cat) pairs by total time.

    pct/cum_pct are shares of summed SELF time (= wall time actually
    attributable to each row): with nested spans in the trace, inclusive
    totals overlap and percentages of their sum would deflate parents
    and overstate coverage."""
    total_self = sum(v["self_us"] for v in agg.values()) or 1.0
    ranked = sorted(agg.items(), key=lambda kv: -kv[1]["total_us"])
    rows, cum = [], 0.0
    for i, ((name, ecat), v) in enumerate(ranked[:k]):
        cum += v["self_us"]
        rows.append({
            "rank": i + 1, "name": name, "cat": ecat,
            "count": v["count"],
            "total_ms": round(v["total_us"] / 1e3, 3),
            "self_ms": round(v["self_us"] / 1e3, 3),
            "avg_ms": round(v["total_us"] / v["count"] / 1e3, 4),
            "pct": round(100.0 * v["self_us"] / total_self, 1),
            "cum_pct": round(100.0 * cum / total_self, 1),
        })
    return rows


def format_table(rows, title="top ops by time"):
    if not rows:
        return "(no events)"
    width = max([len(r["name"]) for r in rows] + [4])
    lines = ["# %s (pct = share of self time; total includes nested)"
             % title,
             "%-4s %-*s %-10s %8s %12s %12s %10s %7s %7s"
             % ("rank", width, "name", "cat", "count", "total_ms",
                "self_ms", "avg_ms", "%", "cum%")]
    for r in rows:
        lines.append("%-4d %-*s %-10s %8d %12.3f %12.3f %10.4f %7.1f %7.1f"
                     % (r["rank"], width, r["name"], r["cat"][:10],
                        r["count"], r["total_ms"], r["self_ms"],
                        r["avg_ms"], r["pct"], r["cum_pct"]))
    return "\n".join(lines)


def report(path, k=15, cat=None):
    """One-call convenience: path -> ranked rows."""
    return report_rows(aggregate(load_events(path), cat=cat), k=k)


def compare(path_a, path_b, k=15, cat=None):
    """Per-(name, cat) total-time regression diff rows between two
    traces, sorted by |delta| (b minus a: positive = b is slower)."""
    a = aggregate(load_events(path_a), cat=cat)
    b = aggregate(load_events(path_b), cat=cat)
    rows = []
    for key in set(a) | set(b):
        ta = a.get(key, {}).get("total_us", 0.0)
        tb = b.get(key, {}).get("total_us", 0.0)
        rows.append({
            "name": key[0], "cat": key[1],
            "a_ms": round(ta / 1e3, 3), "b_ms": round(tb / 1e3, 3),
            "delta_ms": round((tb - ta) / 1e3, 3),
            "ratio": round(tb / ta, 3) if ta else None,
            "a_count": a.get(key, {}).get("count", 0),
            "b_count": b.get(key, {}).get("count", 0),
        })
    rows.sort(key=lambda r: -abs(r["delta_ms"]))
    return rows[:k]


def format_compare(rows, path_a, path_b):
    if not rows:
        return "(no events)"
    width = max([len(r["name"]) for r in rows] + [4])
    lines = ["# regression diff: %s -> %s (positive delta = slower)"
             % (path_a, path_b),
             "%-*s %-10s %12s %12s %12s %8s %9s"
             % (width, "name", "cat", "a_ms", "b_ms", "delta_ms", "ratio",
                "counts")]
    for r in rows:
        lines.append("%-*s %-10s %12.3f %12.3f %+12.3f %8s %5d/%-5d"
                     % (width, r["name"], r["cat"][:10], r["a_ms"],
                        r["b_ms"], r["delta_ms"],
                        "-" if r["ratio"] is None else "%.3f" % r["ratio"],
                        r["a_count"], r["b_count"]))
    return "\n".join(lines)


def graph_pass_rows(payload):
    """Per-pass provenance rows from a flight-recorder dump's
    ``graph_pass`` provider section (observability/flight_recorder.py):
    one row per pass per recently-built program, so a health dump
    answers "did this program run under the bf16 rewrite, and what did
    the pass layer fold/prune?"."""
    section = (payload.get("providers", {}) or {}).get("graph_pass")
    if not section:
        return []
    rows = []
    for prog in section.get("recent", []):
        tag = prog.get("graph", prog.get("program", "?"))
        if "passes" not in prog:  # external program note (generation)
            rows.append({"program": tag, "pass": "amp",
                         "rewrites": 1 if prog.get("amp") else 0,
                         "nodes_before": None, "nodes_after": None,
                         "kv_dtype": prog.get("kv_dtype")})
            continue
        for rep in prog["passes"]:
            row = {
                "program": tag, "pass": rep["pass"],
                "rewrites": rep["rewrites"],
                "nodes_before": rep["nodes_before"],
                "nodes_after": rep["nodes_after"],
                "amp": prog.get("amp", False),
                "folded_constants": prog.get("folded_constants", 0)}
            if rep["pass"] == "quantize":
                # int8 coverage + calibration-table fingerprint: the
                # triage row a numerics regression needs (ISSUE 11)
                row["quantize"] = rep.get("detail",
                                          prog.get("quantize")) or {}
            rows.append(row)
    return rows


def format_graph_pass(rows, path):
    if not rows:
        return "(no graph_pass provider section in %s)" % path
    lines = ["# graph_pass provenance — %s" % path,
             "%-18s %-10s %9s %13s %12s %6s" % (
                 "program", "pass", "rewrites", "nodes_before",
                 "nodes_after", "amp")]
    for r in rows:
        lines.append("%-18s %-10s %9s %13s %12s %6s" % (
            str(r["program"])[:18], r["pass"], r["rewrites"],
            "-" if r["nodes_before"] is None else r["nodes_before"],
            "-" if r["nodes_after"] is None else r["nodes_after"],
            "Y" if r.get("amp") else "-"))
        if r.get("kv_dtype"):
            lines.append("  kv pages: %s" % r["kv_dtype"])
        q = r.get("quantize")
        if q:
            lines.append(
                "  int8 coverage: %s/%s ops quantized, table %s" % (
                    q.get("ops_quantized", 0), q.get("ops_eligible", 0),
                    q.get("table", "-")))
            for name, why in sorted(q.get("skipped", {}).items()):
                lines.append("    fp32 %-24s %s" % (name, why))
    return "\n".join(lines)


# ------------------------------------------------------ request tracing
def _percentile(sorted_vals, q):
    """Nearest-rank percentile of an ASCENDING-sorted list (q in 0-100)
    — the registry's shared estimator (metrics.percentile), so this
    report and the time-series plane agree on what a p99 is."""
    if not sorted_vals:
        return None
    from mxnet_tpu.observability.metrics import percentile

    return percentile(sorted_vals, q)


def request_timelines(events):
    """Reconstruct per-request timelines from a chrome trace's request
    events (cat ``request``, emitted by observability/request_trace.py:
    phase spans named ``req.<kind>.<phase>`` carrying ``args.trace_id``;
    kvstore server-side spans stitch in by the same id).

    Returns [{trace_id, kind, start_ts, total_ms, phases (merged ms by
    phase), spans (ordered), ttft_ms, itl_ms (list), queue_ms}] sorted
    slowest-first."""
    groups = {}
    for ev in events:
        if ev.get("cat") != "request" or ev.get("ph", "X") != "X":
            continue
        tid = (ev.get("args") or {}).get("trace_id")
        if not tid:
            continue
        groups.setdefault(tid, []).append(ev)
    out = []
    for trace_id, evs in groups.items():
        evs.sort(key=lambda e: float(e["ts"]))
        # totals/phases come from the ENGINE's partitioning req.* spans
        # ONLY: stitched spans (kvstore.server.*) (a) fully overlap the
        # worker phase that contains them — adding them in would break
        # the sum(phases) == total partition invariant — and (b) may
        # come from ANOTHER PROCESS whose perf_counter epoch is
        # unrelated, so their timestamps must never stretch this
        # request's bounds. They correlate by trace_id, not by clock,
        # and are reported in a separate `stitched` list.
        req_evs = [e for e in evs
                   if e.get("name", "").startswith("req.")]
        stitched = [
            {"span": e.get("name", "?"),
             "dur_ms": round(float(e["dur"]) / 1e3, 4),
             "pid": e.get("pid")}
            for e in evs if not e.get("name", "").startswith("req.")]
        if not req_evs:
            # a server-side-only dump: phases from the stitched spans
            # themselves (one process, one epoch — bounds are sound)
            t0 = min(float(e["ts"]) for e in evs)
            t1 = max(float(e["ts"]) + float(e["dur"]) for e in evs)
            out.append({
                "trace_id": trace_id, "kind": "(stitched)",
                "start_ts": t0,
                "total_ms": round((t1 - t0) / 1e3, 4),
                "phases": {}, "spans": [], "stitched": stitched,
                "queue_ms": 0.0, "ttft_ms": None, "itl_ms": [],
            })
            continue
        kind = None
        phases, spans, itl = {}, [], []
        t0 = min(float(e["ts"]) for e in req_evs)
        t1 = max(float(e["ts"]) + float(e["dur"]) for e in req_evs)
        ttft = None
        prefix_hit = None  # control-plane engines annotate every span
        for ev in req_evs:
            _, k, phase = ev["name"].split(".", 2)
            kind = kind or k
            if prefix_hit is None:
                ph = (ev.get("args") or {}).get("prefix_hit")
                if ph is not None:
                    prefix_hit = bool(ph)
            dur_ms = float(ev["dur"]) / 1e3
            phases[phase] = phases.get(phase, 0.0) + dur_ms
            spans.append({"phase": phase,
                          "offset_ms": round((float(ev["ts"]) - t0) / 1e3,
                                             4),
                          "dur_ms": round(dur_ms, 4),
                          "tid": ev.get("tid")})
            if phase == "prefill":
                # TTFT = submit -> end of the prefill span
                ttft = (float(ev["ts"]) + float(ev["dur"]) - t0) / 1e3
            elif phase == "decode":
                itl.append(dur_ms)
        out.append({
            "trace_id": trace_id,
            "kind": kind,
            "start_ts": t0,
            "total_ms": round((t1 - t0) / 1e3, 4),
            "phases": {p: round(v, 4) for p, v in phases.items()},
            "spans": spans,
            "stitched": stitched,
            "queue_ms": round(phases.get("queue", 0.0), 4),
            "ttft_ms": None if ttft is None else round(ttft, 4),
            "itl_ms": [round(v, 4) for v in itl],
            "prefix_hit": prefix_hit,
        })
    out.sort(key=lambda r: -r["total_ms"])
    return out


def request_summary(timelines):
    """Per-kind percentile rows: request count plus p50/p90/p99/max of
    end-to-end latency, queue wait, TTFT and inter-token latency."""
    by_kind = {}
    for r in timelines:
        by_kind.setdefault(r["kind"], []).append(r)
    rows = []
    for kind in sorted(by_kind):
        reqs = by_kind[kind]
        annotated = [r for r in reqs if r.get("prefix_hit") is not None]
        hits = [r for r in annotated if r["prefix_hit"]]
        row = {"kind": kind, "count": len(reqs),
               "slowest": reqs[0]["trace_id"],
               # prefix-cache column (serving control plane): None when
               # the engine ran without the cache
               "prefix_hits": len(hits) if annotated else None,
               "prefix_annotated": len(annotated),
               "prefix_hit_rate": (round(len(hits) / len(annotated), 4)
                                   if annotated else None)}
        for label, vals in (
                ("total", [r["total_ms"] for r in reqs]),
                ("queue", [r["queue_ms"] for r in reqs]),
                ("ttft", [r["ttft_ms"] for r in reqs
                          if r["ttft_ms"] is not None]),
                # TTFT split by prefix-cache hit/miss — the cache's
                # effect measured in the existing tooling
                ("ttft_hit", [r["ttft_ms"] for r in hits
                              if r["ttft_ms"] is not None]),
                ("ttft_miss", [r["ttft_ms"] for r in annotated
                               if not r["prefix_hit"]
                               and r["ttft_ms"] is not None]),
                ("itl", [v for r in reqs for v in r["itl_ms"]])):
            vals = sorted(vals)
            for q in (50, 90, 99):
                row["%s_p%d_ms" % (label, q)] = (
                    None if not vals
                    else round(_percentile(vals, q), 4))
            row["%s_max_ms" % label] = (None if not vals
                                        else round(vals[-1], 4))
        rows.append(row)
    return rows


def format_requests(timelines, path, k_spans=40):
    """The --requests rendering: percentile table + the slowest
    request's full span timeline."""
    if not timelines:
        return "(no request events in %s — was tracing sampled and a " \
               "profiler session running?)" % path
    rows = request_summary(timelines)
    lines = ["# request latency attribution — %s (%d requests)"
             % (path, len(timelines)),
             "%-11s %6s %6s %10s %10s %10s %10s %10s %10s %10s"
             % ("kind", "count", "hits", "total_p50", "total_p99",
                "queue_p99", "ttft_p50", "ttft_p99", "itl_p50",
                "itl_p99")]
    fmt = lambda v: "-" if v is None else "%.2f" % v  # noqa: E731
    for r in rows:
        lines.append("%-11s %6d %6s %10s %10s %10s %10s %10s %10s %10s"
                     % (r["kind"], r["count"],
                        "-" if r["prefix_hits"] is None
                        else "%d" % r["prefix_hits"],
                        fmt(r["total_p50_ms"]),
                        fmt(r["total_p99_ms"]), fmt(r["queue_p99_ms"]),
                        fmt(r["ttft_p50_ms"]), fmt(r["ttft_p99_ms"]),
                        fmt(r["itl_p50_ms"]), fmt(r["itl_p99_ms"])))
    if any(r["prefix_hits"] is not None for r in rows):
        lines.append("")
        lines.append("# TTFT by prefix-cache hit/miss (serving control "
                     "plane)")
        lines.append("%-11s %6s %6s %10s %10s %10s %10s"
                     % ("kind", "arm", "count", "ttft_p50", "ttft_p90",
                        "ttft_p99", "ttft_max"))
        for r in rows:
            if r["prefix_hits"] is None:
                continue
            for arm, n in (("hit", r["prefix_hits"]),
                           ("miss",
                            r["prefix_annotated"] - r["prefix_hits"])):
                lines.append(
                    "%-11s %6s %6d %10s %10s %10s %10s"
                    % (r["kind"], arm, n,
                       fmt(r["ttft_%s_p50_ms" % arm]),
                       fmt(r["ttft_%s_p90_ms" % arm]),
                       fmt(r["ttft_%s_p99_ms" % arm]),
                       fmt(r["ttft_%s_max_ms" % arm])))
    slow = timelines[0]
    lines.append("")
    lines.append("# slowest request: %s (%s, %.3f ms total)"
                 % (slow["trace_id"], slow["kind"], slow["total_ms"]))
    lines.append("%-12s %12s %12s %10s" % ("phase", "offset_ms",
                                           "dur_ms", "tid"))
    for s in slow["spans"][:k_spans]:
        lines.append("%-12s %12.4f %12.4f %10s"
                     % (s["phase"], s["offset_ms"], s["dur_ms"],
                        s.get("tid", "-")))
    if len(slow["spans"]) > k_spans:
        lines.append("... (%d more spans)" % (len(slow["spans"]) - k_spans))
    lines.append("")
    lines.append("# phase totals of the slowest request (sum = total):")
    for p, v in slow["phases"].items():
        lines.append("  %-12s %10.4f ms" % (p, v))
    if slow.get("stitched"):
        lines.append("# stitched spans (correlated by trace_id; overlap "
                     "the phases above, possibly other processes):")
        for s in slow["stitched"]:
            lines.append("  %-24s %10.4f ms  pid %s"
                         % (s["span"], s["dur_ms"], s.get("pid", "-")))
    return "\n".join(lines)


def compare_requests(path_a, path_b):
    """--compare for the request sections: per-kind percentile deltas
    (b minus a; positive = b is slower)."""
    rows_a = {r["kind"]: r for r in request_summary(
        request_timelines(load_events(path_a)))}
    rows_b = {r["kind"]: r for r in request_summary(
        request_timelines(load_events(path_b)))}
    out = []
    for kind in sorted(set(rows_a) | set(rows_b)):
        a, b = rows_a.get(kind), rows_b.get(kind)
        row = {"kind": kind,
               "a_count": a["count"] if a else 0,
               "b_count": b["count"] if b else 0}
        for metric in ("total_p50_ms", "total_p99_ms", "queue_p99_ms",
                       "ttft_p99_ms", "itl_p99_ms"):
            va = a.get(metric) if a else None
            vb = b.get(metric) if b else None
            row["a_" + metric] = va
            row["b_" + metric] = vb
            row["delta_" + metric] = (None if va is None or vb is None
                                      else round(vb - va, 4))
        out.append(row)
    return out


def format_compare_requests(rows, path_a, path_b):
    if not rows:
        return "(no request events in either trace)"
    lines = ["# request regression diff: %s -> %s (positive = slower)"
             % (path_a, path_b),
             "%-11s %9s %12s %12s %12s %12s %12s"
             % ("kind", "counts", "d_total_p50", "d_total_p99",
                "d_queue_p99", "d_ttft_p99", "d_itl_p99")]
    fmt = lambda v: "-" if v is None else "%+.2f" % v  # noqa: E731
    for r in rows:
        lines.append("%-11s %4d/%-4d %12s %12s %12s %12s %12s"
                     % (r["kind"], r["a_count"], r["b_count"],
                        fmt(r["delta_total_p50_ms"]),
                        fmt(r["delta_total_p99_ms"]),
                        fmt(r["delta_queue_p99_ms"]),
                        fmt(r["delta_ttft_p99_ms"]),
                        fmt(r["delta_itl_p99_ms"])))
    return "\n".join(lines)


def input_pipeline_rows(payload):
    """Per-stage wait/occupancy rows from a flight-recorder dump's
    ``io`` provider section (runtime/pipeline.py): one pipeline view
    per live StreamingIter, so a dump answers "was this run input-bound
    or compute-bound?" directly."""
    section = (payload.get("providers", {}) or {}).get("io")
    if not section:
        return []
    views = (section.get("pipelines") if isinstance(section, dict)
             and "pipelines" in section else [section])
    rows = []
    for i, view in enumerate(views):
        if not isinstance(view, dict) or "stages" not in view:
            rows.append({"pipeline": i, "error": repr(view)})
            continue
        for stage, vals in view["stages"].items():
            row = {"pipeline": i, "stage": stage}
            row.update(vals)
            rows.append(row)
        rows.append({"pipeline": i, "stage": "(verdict)",
                     "verdict": view.get("verdict"),
                     "host_stall_pct": view.get("host_stall_pct"),
                     "batches": view.get("batches"),
                     "queue_depth": view.get("queue_depth"),
                     "decode_workers": view.get("decode_workers"),
                     "prefetch_depth": view.get("prefetch_depth")})
    return rows


def format_input_pipeline(rows, path):
    if not rows:
        return "(no io provider section in %s)" % path
    lines = ["# input pipeline — %s" % path,
             "%-9s %-12s %s" % ("pipeline", "stage", "detail")]
    for r in rows:
        if r.get("stage") == "(verdict)":
            lines.append(
                "%-9s %-12s %s (host stall %.1f%%, %s batches, queue "
                "depth %s, %s workers, prefetch %s)" % (
                    r["pipeline"], "verdict", r.get("verdict"),
                    r.get("host_stall_pct") or 0.0, r.get("batches"),
                    r.get("queue_depth"), r.get("decode_workers"),
                    r.get("prefetch_depth")))
            continue
        detail = ", ".join("%s=%s" % (k, v) for k, v in sorted(r.items())
                           if k not in ("pipeline", "stage"))
        lines.append("%-9s %-12s %s" % (r.get("pipeline"),
                                        r.get("stage"), detail))
    return "\n".join(lines)


# ------------------------------------------------- device trace (.xplane.pb)
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
# transforms JAX wraps around a scope's name in op_name:
# jit(step)/transpose(jvp(forward))/bn0/dot_general
_WRAPPED = re.compile(r"\b(jvp|transpose|checkpoint|remat|rematted_"
                      r"computation|vmap|custom_jvp|custom_vjp|p?jit)"
                      r"\(([^()]*)\)")
PHASES = ("forward", "backward", "update", "unscoped")


def split_op_name(op_name):
    """(phase, scope) of an HLO ``op_name`` path. ``transpose(...)`` is
    the backward pass (a recomputed forward operation under a
    checkpoint sits inside it and counts there), ``jvp(...)`` the
    forward, a leading ``update`` scope the optimizer; the scope is the
    path less the jitted function's name, the transform wrappers,
    ``checkpoint`` / ``rematted_computation`` segments, a conditional's
    ``cond`` / ``branch_<i>_fun`` (the routed layer runs in one of two
    layouts: both count under the scopes inside them), a leading
    ``forward`` and the primitive's own name."""
    path = (op_name or "").split(";")[0]
    if "transpose(" in path:
        phase = "backward"
    elif "jvp(" in path:
        phase = "forward"
    else:
        phase = None
    jitted = re.match(r"p?jit\(", path) is not None
    while True:
        path, n = _WRAPPED.subn(r"\2", path)
        if not n:
            break
    segs = [seg for seg in path.split("/") if seg][jitted:-1]
    # a recomputed layer's operations sit under jax.checkpoint's own scope
    segs = [seg for seg in segs
            if seg not in ("checkpoint", "rematted_computation", "cond")
            and not re.fullmatch(r"branch_\d+_fun", seg)]
    if segs[:1] == ["forward"]:
        segs = segs[1:]
    if phase is None:
        phase = "update" if segs[:1] == ["update"] else "unscoped"
    if phase == "unscoped":
        return phase, ""
    return phase, "/".join(segs) or "(no scope)"


def program_scope(scope):
    """The part of a scope path the program itself named: a graph node
    (``bn0``), or ``l17/attn`` as ``l*/attn`` (one row for the same scope
    of every layer) and ``l3/moe/experts`` as ``l*/moe/experts`` (the
    parts an attention, routed, state-space or short-convolution layer
    names); what jax.numpy adds below it (an einsum's spec,
    ``log_softmax``, a kernel's name) is left to the per-operation rows."""
    layer = re.match(r"l\d+/(attn/(?:proj|rope|flash|diff|out)(?=/|$)"
                     r"|moe/[^/]+|ssm/[^/]+|conv/[^/]+|[^/]+)", scope)
    return "l*/" + layer.group(1) if layer else scope.split("/")[0]


def find_xplane(path):
    """The newest ``.xplane.pb`` under a directory (or the file itself);
    None where there is none."""
    if os.path.isfile(path):
        return path if path.endswith(".xplane.pb") else None
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _trace_reduce():
    """perfbench.trace_reduce (its interval arithmetic is the one copy)."""
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    from perfbench import trace_reduce

    return trace_reduce


def _pb_varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _pb_fields(buf):
    """(field number, value) pairs of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _pb_varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _pb_varint(buf, i)
        else:
            if wire == 2:
                size, i = _pb_varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError("wire type %d in an .xplane.pb" % wire)
            value = buf[i:i + size]
            i += size
        yield field, value


# the stats the TPU profiler keeps per *kind* of event (XEventMetadata),
# which jax.profiler.ProfileData does not hand out with the events
METADATA_STATS = ("tf_op", "flops", "bytes_accessed", "hlo_category")


def metadata_stats(path):
    """{plane name: {event name: {stat: value}}} for METADATA_STATS, read
    from the file's wire format (tsl/profiler/protobuf/xplane.proto:
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5;
    XEventMetadata.name=2, .stats=5; XStat.metadata_id=1, .uint64=3,
    .int64=4, .str=5; XStatMetadata.id=1, .name=2)."""
    import struct

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _pb_fields(space):
        if field != 1:
            continue
        name, stat_names, metas = "", {}, []
        for field, value in _pb_fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 5:
                entry = dict(_pb_fields(dict(_pb_fields(value))[2]))
                if bytes(entry.get(2, b"")).decode() in METADATA_STATS:
                    stat_names[entry.get(1, 0)] = bytes(entry[2]).decode()
            elif field == 4:
                metas.append(dict(_pb_fields(value))[2])
        if not name.startswith("/device:"):
            continue
        events = out[name] = {}
        for meta in metas:
            ev_name, stats = "", {}
            for field, value in _pb_fields(meta):
                if field == 2:
                    ev_name = bytes(value).decode(errors="replace")
                elif field == 5:
                    stat = dict(_pb_fields(value))
                    key = stat_names.get(stat.get(1))
                    if key is None:
                        continue
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode(errors="replace")
                    elif 2 in stat:
                        stats[key] = struct.unpack("<d", stat[2])[0]
                    else:
                        stats[key] = stat.get(4, stat.get(3, 0))
            if stats:
                events[ev_name] = stats
    return out


def load_xplane(path):
    """``{"devices": {plane name: [event, ...]}, "host": [event, ...]}``
    of an ``.xplane.pb``, read with ``jax.profiler.ProfileData``. A
    device event is a chrome-style dict (``name``, ``ts``/``dur`` in us,
    ``pid``, ``tid``) with the operation's stats under ``args``; a host
    event likewise, for every annotation of the host plane (the Python
    tracer's ``$file:line`` frames left out)."""
    import jax

    ops_line = _trace_reduce().OPS_LINE
    path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    by_kind = metadata_stats(path)
    devices, host = {}, []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        if not is_device and not plane.name.startswith("/host:"):
            continue
        kinds = by_kind.get(plane.name, {})
        for line in plane.lines:
            if is_device and line.name != ops_line:
                continue
            out = devices.setdefault(plane.name, []) if is_device else host
            for e in line.events:
                if not is_device and e.name.startswith("$"):
                    continue
                out.append({"name": e.name, "ph": "X",
                            "ts": e.start_ns / 1e3,
                            "dur": e.duration_ns / 1e3,
                            "pid": plane.name, "tid": line.name,
                            "args": dict(kinds.get(e.name, ()),
                                         **dict(e.stats))})
    return {"devices": devices, "host": host}


def event_op_name(ev):
    """The ``op_name`` path of a device event: on this libtpu the
    ``tf_op`` stat of the event's kind, written ``<op_name>:<op type>``."""
    return (ev["args"].get("tf_op") or "").rsplit(":", 1)[0]


def _innermost(host, lo, hi):
    """Name of the innermost host event that covers [lo, hi] us."""
    best = None
    for ev in host:
        if ev["ts"] <= lo and ev["ts"] + ev["dur"] >= hi and (
                best is None or ev["dur"] < best["dur"]):
            best = ev
    return best["name"] if best else None


def device_report(trace, peaks=None, k=10):
    """The report of one device plane (the first): busy time, its split
    by phase and by scope, the costliest operations, what is unscoped by
    kind of operation, the copies by owner, and the longest idle gaps."""
    reduce = _trace_reduce()
    if not trace["devices"]:
        raise SystemExit("trace_report: the trace holds no device plane "
                         "with an %r line (a CPU trace has none)"
                         % reduce.OPS_LINE)
    plane = sorted(trace["devices"])[0]
    events = sorted(trace["devices"][plane], key=lambda e: e["ts"])
    lo = events[0]["ts"]
    hi = max(e["ts"] + e["dur"] for e in events)
    busy, gaps = reduce.busy_union([(e["name"], e["ts"], e["dur"])
                                    for e in events], lo, hi)
    # an operation that encloses others (a while loop and its body) keeps
    # only its own time, so that the rows sum to the busy time
    selfs = _self_times(events)
    where = [split_op_name(event_op_name(ev)) for ev in events]
    # a copy the compiler put in carries no scope: its owner is the next
    # scoped operation on the device, the one it moves data for
    owner, owners = ("unscoped", ""), []
    for at in reversed(where):
        owner = at if at[0] != "unscoped" else owner
        owners.append(owner)
    owners.reverse()
    phases = dict.fromkeys(PHASES, 0.0)
    scopes, ops, unscoped, copies = {}, {}, {}, {}
    for ev, (phase, scope), owner in zip(events, where, owners):
        own = max(selfs[id(ev)], 0.0)
        phases[phase] += own
        name = reduce.short_name(ev["name"])
        kind = re.sub(r"[.\d]+$", "", name.split(" ")[0])
        if phase == "unscoped":
            unscoped[kind] = unscoped.get(kind, 0.0) + own
        else:
            key = (phase, program_scope(scope))
            scopes[key] = scopes.get(key, 0.0) + own
        if kind.startswith("copy"):
            key = (owner[0], program_scope(owner[1]),
                   "own scope" if phase != "unscoped" else "next scoped")
            copies[key] = copies.get(key, 0.0) + own
        row = ops.setdefault(name, {"us": 0.0, "count": 0, "flops": 0,
                                    "bytes": 0, "phase": phase,
                                    "scope": scope})
        row["us"] += own
        row["count"] += 1
        row["flops"] += ev["args"].get("flops") or 0
        row["bytes"] += ev["args"].get("bytes_accessed") or 0
    top = sorted(ops.items(), key=lambda kv: -kv[1]["us"])[:k]
    for _, row in top:
        row["roofline_pct"] = row["binds"] = None
        if peaks and (row["flops"] or row["bytes"]) and row["us"]:
            t_flops = row["flops"] / peaks["bf16_flops_per_s"]
            t_bytes = row["bytes"] / peaks["hbm_bytes_per_s"]
            row["roofline_pct"] = 100.0 * max(t_flops, t_bytes) / (
                row["us"] / 1e6)
            row["binds"] = "flops" if t_flops >= t_bytes else "bytes"
    spans = [e for e in trace["host"] if "cat" in e["args"]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:5]:
        inner = _innermost(spans, a, b)
        named.append({"us": b - a, "at_us": a - lo,
                      "span": inner or "no program span (%s)" % (
                          _innermost(trace["host"], a, b)
                          or "no annotation")})

    def ranked(table):
        return sorted(table.items(), key=lambda kv: -kv[1])[:k]

    return {"plane": plane, "window_us": hi - lo, "busy_us": busy,
            "phases_us": phases, "scopes_us": ranked(scopes), "ops": top,
            "unscoped_us": ranked(unscoped), "copies_us": ranked(copies),
            "gaps": named,
            "program_spans": sorted({e["name"] for e in spans})}


def format_device_report(rep, path):
    busy = rep["busy_us"] or 1.0
    lines = ["# device trace - %s (%s)" % (path, rep["plane"]),
             "window %.3f ms, busy %.3f ms (idle %.4f%%)" % (
                 rep["window_us"] / 1e3, busy / 1e3,
                 100.0 * (1 - busy / rep["window_us"])),
             "", "# device time by phase",
             "%-10s %12s %8s" % ("phase", "ms", "% busy")]
    for phase in PHASES:
        us = rep["phases_us"][phase]
        lines.append("%-10s %12.3f %8.2f" % (phase, us / 1e3,
                                             100.0 * us / busy))
    total = sum(rep["phases_us"].values())
    lines.append("%-10s %12.3f %8.2f" % ("sum", total / 1e3,
                                         100.0 * total / busy))
    lines += ["", "# costliest scopes", "%-10s %-36s %12s %8s" % (
        "phase", "scope", "ms", "% busy")]
    for (phase, scope), us in rep["scopes_us"]:
        lines.append("%-10s %-36s %12.3f %8.2f" % (
            phase, scope[:36], us / 1e3, 100.0 * us / busy))
    lines += ["", "# unscoped operations by kind",
              "%-36s %12s %8s" % ("kind", "ms", "% busy")]
    for kind, us in rep["unscoped_us"]:
        lines.append("%-36s %12.3f %8.2f" % (kind[:36], us / 1e3,
                                             100.0 * us / busy))
    lines += ["", "# copies by owner (their own scope, or the next scoped "
              "operation's)", "%-10s %-36s %-12s %10s %8s" % (
                  "phase", "scope", "from", "ms", "% busy")]
    for (phase, scope, how), us in rep["copies_us"]:
        lines.append("%-10s %-36s %-12s %10.3f %8.2f" % (
            phase, scope[:36], how, us / 1e3, 100.0 * us / busy))
    lines += ["", "# costliest operations (roofline against "
              "perfbench/peaks.json; flops and bytes are XLA's estimate "
              "of the", "# operation, operands reused on chip counted "
              "again: a share over 100% says so)",
              "%-44s %6s %10s %-9s %-22s %12s %12s %9s %6s" % (
                  "operation", "count", "ms", "phase", "scope", "flops",
                  "bytes", "roofline%", "binds")]
    for name, row in rep["ops"]:
        has = row["flops"] or row["bytes"]
        lines.append("%-44s %6d %10.3f %-9s %-22s %12s %12s %9s %6s" % (
            name[:44], row["count"], row["us"] / 1e3, row["phase"],
            row["scope"][:22],
            "%.3e" % row["flops"] if has else "not in trace",
            "%.3e" % row["bytes"] if has else "not in trace",
            "-" if row["roofline_pct"] is None
            else "%.1f" % row["roofline_pct"], row["binds"] or "-"))
    lines += ["", "# longest device idle gaps, by the innermost program "
              "span over each", "%12s %14s  %s" % ("us", "at ms", "span")]
    for gap in rep["gaps"]:
        lines.append("%12.1f %14.3f  %s" % (gap["us"], gap["at_us"] / 1e3,
                                            gap["span"]))
    lines += ["", "program spans in the host plane: %s" % (
        ", ".join(rep["program_spans"]) or "none")]
    return "\n".join(lines)


def load_peaks(device_kind=None):
    """The row of perfbench/peaks.json for a device kind (the only row
    where none is given: a trace file does not name its chip)."""
    with open(os.path.join(_ROOT, "perfbench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind is None and len(table) == 1:
        return next(iter(table.values()))
    return table.get(device_kind)


def trace_cell(name, steps, out_dir):
    """Build a cell of BENCHMARK.json through its perfbench driver, run
    3 warm steps and ``steps`` traced ones of the harness's own loop
    under ``mx.profiler.set_state('run')``; returns (trace directory,
    the chip's peaks)."""
    import importlib

    _trace_reduce()  # the checkout's root on sys.path
    import jax

    import mxnet_tpu as mx
    from perfbench import run as harness

    _, entry, workload, config = harness.load_cell(name, False)
    mx.config.enable_compile_cache()
    mx.observability.set_enabled(True)
    driver = importlib.import_module("perfbench.drivers." + config["driver"])
    cell = driver.build(config, workload["sizes"], 0,
                        jax.devices()[:entry["chips"]])
    harness.drive(cell, 0, steps=3)
    mx.profiler.set_config(filename=os.path.join(out_dir, "profile.json"))
    mx.profiler.set_state("run")
    try:
        harness.drive(cell, 3, steps=steps, annotate=True)
    finally:
        mx.profiler.set_state("stop")
    return (os.path.join(out_dir, "profile_trace"),
            load_peaks(jax.devices()[0].device_kind))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="top-K op/phase time report from a chrome/XPlane trace")
    ap.add_argument("trace", nargs="?",
                    help="trace file (.json/.json.gz) or XPlane trace dir")
    ap.add_argument("-k", "--top-k", type=int, default=15)
    ap.add_argument("--cat", default=None,
                    help="only events of this category (e.g. operator, "
                         "executor, module, kvstore)")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="diff two traces instead of reporting one")
    ap.add_argument("--requests", action="store_true",
                    help="per-request latency attribution from the "
                         "trace's request events (request_trace.py): "
                         "TTFT/ITL/queue-wait percentile table + the "
                         "slowest request's full span timeline; with "
                         "--compare, per-kind percentile deltas")
    ap.add_argument("--roofline", metavar="DUMP",
                    help="print the perf provider section of a "
                         "flight-recorder dump as a roofline table: "
                         "per-program achieved-vs-roofline MFU, per-op "
                         "intensity rows and ranked fusion candidates "
                         "(tools/perf_report.py renders)")
    ap.add_argument("--waterfall", metavar="DUMP",
                    help="print the per-step wall-time waterfall "
                         "(data-wait/host/device/kvstore, summing to the "
                         "step wall) from a flight-recorder dump's perf "
                         "section")
    ap.add_argument("--perf", action="store_true",
                    help="with --compare: diff the two sources' perf "
                         "sections instead (MFU + waterfall-segment "
                         "delta columns; accepts dumps or "
                         "BENCH_LEDGER.jsonl[:N] rows)")
    ap.add_argument("--dist", action="store_true",
                    help="with --compare: diff the two sources' dist "
                         "sections instead (per-rank waterfall-segment "
                         "deltas + straggler-ranking drift; accepts "
                         "statusz captures, flight dumps or "
                         "tools/dist_report.py --save outputs)")
    ap.add_argument("--graph-passes", metavar="DUMP",
                    help="print the graph_pass provider section of a "
                         "flight-recorder dump (per-program pass summary: "
                         "nodes folded/pruned, precision rewrites)")
    ap.add_argument("--input-pipeline", metavar="DUMP",
                    help="print the io provider section of a "
                         "flight-recorder dump (per-stage wait/occupancy "
                         "of the streaming input pipeline + the "
                         "input-bound vs compute-bound verdict)")
    ap.add_argument("--device", action="store_true",
                    help="read the .xplane.pb under the trace path: device "
                         "time by phase and scope, costliest operations "
                         "against the roofline, idle gaps by program span")
    ap.add_argument("--cell", metavar="NAME",
                    help="build this cell of BENCHMARK.json through "
                         "perfbench.drivers, trace --steps steps of it "
                         "under mx.profiler and print the --device report")
    ap.add_argument("--steps", type=int, default=4,
                    help="with --cell: steps to trace (after 3 warm ones)")
    ap.add_argument("--out", default="trace_report_out",
                    help="with --cell: where the trace is written")
    ap.add_argument("--json", action="store_true",
                    help="emit rows as JSON instead of a table")
    args = ap.parse_args(argv)

    if args.cell or args.device:
        peaks = load_peaks()
        if args.cell:
            args.trace, peaks = trace_cell(args.cell, args.steps, args.out)
        elif not args.trace or not find_xplane(args.trace):
            ap.error("--device needs a trace path with an .xplane.pb")
        rep = device_report(load_xplane(args.trace), peaks, k=args.top_k)
        print(json.dumps(rep, indent=1) if args.json
              else format_device_report(rep, args.trace))
        return 0

    if args.roofline or args.waterfall:
        try:
            import perf_report
        except ImportError:
            from tools import perf_report

        spec = args.roofline or args.waterfall
        section = perf_report.load_perf_section(spec)
        if args.json:
            print(json.dumps(section, indent=1))
            return 0
        if args.roofline:
            print(perf_report.format_roofline(section, spec))
        if args.waterfall:
            print(perf_report.format_waterfall(section, spec))
        return 0
    if args.compare and args.dist:
        try:
            import dist_report
        except ImportError:
            from tools import dist_report

        cmp = dist_report.compare_dist(*args.compare)
        print(json.dumps(cmp, indent=1) if args.json
              else dist_report.format_compare_dist(cmp, *args.compare))
        return 0
    if args.compare and args.perf:
        try:
            import perf_report
        except ImportError:
            from tools import perf_report

        cmp = perf_report.compare_perf(*args.compare)
        print(json.dumps(cmp, indent=1) if args.json
              else perf_report.format_compare_perf(cmp))
        return 0
    if args.input_pipeline:
        with open(args.input_pipeline) as f:
            payload = json.load(f)
        rows = input_pipeline_rows(payload)
        print(json.dumps(rows, indent=1) if args.json
              else format_input_pipeline(rows, args.input_pipeline))
        return 0
    if args.graph_passes:
        with open(args.graph_passes) as f:
            payload = json.load(f)
        rows = graph_pass_rows(payload)
        print(json.dumps(rows, indent=1) if args.json
              else format_graph_pass(rows, args.graph_passes))
        return 0
    if args.compare:
        if args.requests:
            rows = compare_requests(*args.compare)
            print(json.dumps(rows, indent=1) if args.json
                  else format_compare_requests(rows, *args.compare))
            return 0
        rows = compare(args.compare[0], args.compare[1], k=args.top_k,
                       cat=args.cat)
        print(json.dumps(rows, indent=1) if args.json
              else format_compare(rows, *args.compare))
        return 0
    if not args.trace:
        ap.error("trace path required (or use --compare A B)")
    if args.requests:
        timelines = request_timelines(load_events(args.trace))
        print(json.dumps(timelines, indent=1) if args.json
              else format_requests(timelines, args.trace))
        return 0
    rows = report(args.trace, k=args.top_k, cat=args.cat)
    title = "top %d by total time — %s" % (args.top_k, args.trace)
    if args.cat:
        title += " [cat=%s]" % args.cat
    print(json.dumps(rows, indent=1) if args.json
          else format_table(rows, title))
    return 0


if __name__ == "__main__":
    sys.exit(main())

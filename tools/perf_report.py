#!/usr/bin/env python
"""Roofline attribution + perf-ledger reports (ISSUE 13).

Three report surfaces over the observability.perf layer:

* **Roofline** — per-program achieved-vs-roofline table (analytic FLOPs
  / HBM bytes at the published peaks vs the fenced device time) plus
  the per-op roofline table and the ranked fusion candidates: the op
  sequences whose achieved arithmetic intensity sits furthest under the
  ridge point — the work list for ROADMAP item 3's fusion-region pass.
* **Waterfall** — the fit loop's per-step wall-time partition
  (data-wait / host dispatch / device compute / kvstore), which sums to
  the step wall exactly by construction.
* **Ledger** — an append-only ``BENCH_LEDGER.jsonl`` trajectory
  (rows of ``observability.perf.append_ledger``; nothing in the tree
  writes them since PR 30, ROADMAP Design): last-N table, per-bench
  deltas against the previous comparable row, and the regression
  verdict (``--gate`` exits nonzero on a CPU-stable regression).

Inputs: a flight-recorder dump (``providers.perf``), a ``/statusz``
capture, or a ledger row (``BENCH_LEDGER.jsonl`` optionally suffixed
``:N`` for row N, negative from the end):

    python tools/perf_report.py health_dumps/health_dump_1_001.json
    python tools/perf_report.py --roofline dump.json
    python tools/perf_report.py --waterfall dump.json
    python tools/perf_report.py --ledger [BENCH_LEDGER.jsonl] -n 5
    python tools/perf_report.py --ledger --gate          # CI gate

``trace_report.py --compare A B --perf`` reuses :func:`compare_perf`
for MFU + waterfall-segment delta columns between two dumps or ledger
rows.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def _ledger():
    from mxnet_tpu.observability import perf

    return perf


# ------------------------------------------------------------ loading
def load_perf_section(spec):
    """A perf section from any of the accepted sources.

    ``spec``: a flight-recorder dump / statusz JSON (the ``perf``
    provider section is extracted), a raw perf-summary JSON, or a
    ``.jsonl`` ledger path (optional ``:N`` row index, default the last
    row).  Returns a dict with (subsets of) ``programs``,
    ``waterfalls``/``waterfall``, ``benches``."""
    path, idx = spec, None
    if not os.path.exists(path) and ":" in spec:
        head, _, tail = spec.rpartition(":")
        try:
            idx = int(tail)
            path = head
        except ValueError:
            pass
    if not os.path.exists(path):
        raise FileNotFoundError("no such perf source: %r" % spec)
    if path.endswith(".jsonl"):
        rows = _ledger().read_ledger(path)
        if not rows:
            raise ValueError("ledger %s is empty" % path)
        row = rows[idx if idx is not None else -1]
        return {"source": "ledger:%s" % row.get("ts"),
                "programs": row.get("programs", []),
                "waterfall": row.get("waterfall"),
                "waterfalls": [row["waterfall"]] if row.get("waterfall")
                              else [],
                "benches": row.get("benches", {})}
    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict) and "providers" in payload:
        section = (payload.get("providers") or {}).get("perf")
        if not section:
            # a /statusz capture also carries a top-level brief
            section = payload.get("perf") or {}
        return section or {}
    if isinstance(payload, dict) and "perf" in payload \
            and "programs" not in payload:
        return payload["perf"] or {}
    return payload if isinstance(payload, dict) else {}


# ----------------------------------------------------------- roofline
def roofline_rows(section):
    """Ranked per-program rows (+ nested op tables) from a perf section."""
    rows = []
    for prog in section.get("programs", []):
        rows.append(dict(prog))
    rows.sort(key=lambda p: -(p.get("roofline_ms") or 0))
    return rows


def format_roofline(section, path, k_ops=12):
    rows = roofline_rows(section)
    if not rows:
        return "(no perf program attribution in %s — was MXNET_PERF on " \
               "and a fit running?)" % path
    lines = ["# roofline attribution — %s" % path,
             "%-28s %-6s %12s %12s %12s %10s %8s %8s %9s" % (
                 "program", "mode", "gflops", "hbm_mb", "roofline_ms",
                 "device_ms", "mfu%", "hbm%", "resid")]
    fmt = lambda v, p="%.2f": "-" if v is None else p % v  # noqa: E731
    for p in rows:
        lines.append("%-28s %-6s %12.3f %12.2f %12.4f %10s %8s %8s %9s" % (
            str(p.get("graph", "?"))[:28], p.get("mode", "?"),
            (p.get("flops") or 0) / 1e9,
            (p.get("hbm_bytes") or 0) / 2**20,
            p.get("roofline_ms") or 0.0,
            fmt(p.get("device_ms_ema"), "%.3f"),
            fmt(p.get("mfu_pct")), fmt(p.get("hbm_util_pct")),
            fmt(p.get("residual"), "%.1f")))
    top = rows[0]
    ops = top.get("ops_top") or []
    if ops:
        lines.append("")
        lines.append("# per-op roofline — %s (%s; top %d by roofline "
                     "time; ridge %.1f FLOPs/byte)"
                     % (top.get("graph"), top.get("basis", "forward walk"),
                        min(k_ops, len(ops)),
                        top.get("ridge_intensity") or 0.0))
        lines.append("%-26s %-16s %12s %12s %10s %10s" % (
            "op", "type", "gflops", "kb", "intensity", "bound"))
        for r in ops[:k_ops]:
            lines.append("%-26s %-16s %12.4f %12.1f %10.2f %10s" % (
                str(r["name"])[:26], str(r["op"])[:16], r["flops"] / 1e9,
                r["bytes"] / 1024.0, r.get("intensity", 0.0), r["bound"]))
    cands = top.get("fusion_candidates") or []
    if cands:
        lines.append("")
        lines.append("# fusion candidates — bandwidth-bound runs, ranked "
                     "by HBM bytes a fused kernel would save:")
        for i, c in enumerate(cands[:8]):
            lines.append("  %d. [%s] saves %.1f KB/run (%s)"
                         % (i + 1, " -> ".join(c["ops"]),
                            c["saved_bytes"] / 1024.0,
                            " -> ".join(c["op_types"])))
    return "\n".join(lines)


# ------------------------------------------------------------- fusion
def load_graph_pass_section(spec):
    """The ``graph_pass`` provider section (fuse-pass region/rejection
    reports) from a flight-recorder dump, or {} when the source carries
    none (ledger rows, raw perf summaries)."""
    path = spec.rpartition(":")[0] if (not os.path.exists(spec)
                                       and ":" in spec) else spec
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return {}
    if isinstance(payload, dict) and "providers" in payload:
        return (payload.get("providers") or {}).get("graph_pass") or {}
    if isinstance(payload, dict):
        return payload.get("graph_pass") or {}
    return {}


def fusion_adoption(section, gp_section=None):
    """Per-program fusion adoption: regions the fuse pass carved
    (name, members, analytic bytes saved) plus the REMAINING roofline
    candidates annotated with why they are still unfused — the pass's
    own rejection reasons when a graph_pass provider section is
    available.  The report shows headroom, not a re-listing of regions
    the pass already consumed (those no longer appear as candidates at
    all — perf.fusion_candidates excludes fused rows)."""
    rejected = {}
    for rep in (gp_section or {}).get("recent", ()):
        fuse = rep.get("fuse") or {}
        rejected.update(fuse.get("rejected") or {})
    out = []
    for prog in section.get("programs", []):
        regions = prog.get("fused_regions") or []
        remaining = []
        for c in prog.get("fusion_candidates") or []:
            reason = None
            for op_name in c.get("ops", ()):
                if op_name in rejected:
                    reason = rejected[op_name]
                    break
            remaining.append({
                "ops": list(c.get("ops", ())),
                "saved_bytes": c.get("saved_bytes", 0),
                "status": ("unfused: %s" % reason if reason
                           else "unfused (outside region grammar or pass "
                                "off)")})
        out.append({"graph": prog.get("graph"), "mode": prog.get("mode"),
                    "fused_regions": regions,
                    "fused_saved_bytes": prog.get("fused_saved_bytes", 0),
                    "remaining": remaining})
    return out


def format_fusion(section, path, gp_section=None):
    rows = fusion_adoption(section, gp_section)
    if not rows:
        return "(no perf program attribution in %s — was MXNET_PERF on " \
               "and a fit running?)" % path
    lines = ["# fusion adoption — %s (fused regions vs remaining "
             "candidates)" % path]
    for prog in rows:
        lines.append("%s/%s: %d fused region(s), %.1f KB interior "
                     "traffic saved/run"
                     % (prog["graph"], prog["mode"],
                        len(prog["fused_regions"]),
                        prog["fused_saved_bytes"] / 1024.0))
        for r in prog["fused_regions"]:
            lines.append("  FUSED    [%s] saves %.1f KB"
                         % (" -> ".join(r.get("members", ())),
                            r.get("saved_bytes", 0) / 1024.0))
        for c in prog["remaining"]:
            lines.append("  headroom [%s] %.1f KB — %s"
                         % (" -> ".join(c["ops"]),
                            c["saved_bytes"] / 1024.0, c["status"]))
        if not prog["fused_regions"] and not prog["remaining"]:
            lines.append("  (nothing bandwidth-bound to fuse)")
    return "\n".join(lines)


# ---------------------------------------------------------- waterfall
def waterfall_rows(section):
    rows = section.get("waterfalls")
    if not rows:
        last = section.get("waterfall")
        rows = [last] if last else []
    return [r for r in rows if r]


def format_waterfall(section, path):
    rows = waterfall_rows(section)
    if not rows:
        return "(no step waterfalls in %s — was MXNET_PERF on and a fit " \
               "running?)" % path
    lines = ["# step-time waterfall — %s (segments sum to wall exactly)"
             % path,
             "%6s %10s %10s %10s %10s %10s %8s %8s" % (
                 "step", "wall_ms", "data_ms", "host_ms", "device_ms",
                 "kv_ms", "mfu%", "hbm%")]
    fmt = lambda v: "-" if v is None else "%.4f" % v  # noqa: E731
    for r in rows:
        lines.append("%6s %10.3f %10.3f %10.3f %10.3f %10.3f %8s %8s" % (
            r.get("step", "-"), r["wall_s"] * 1e3,
            r["data_wait_s"] * 1e3, r["host_s"] * 1e3,
            r["device_s"] * 1e3, r["kvstore_s"] * 1e3,
            fmt(r.get("mfu_pct")), fmt(r.get("hbm_util_pct"))))
    tot = {k: sum(r[k] for r in rows)
           for k in ("wall_s", "data_wait_s", "host_s", "device_s",
                     "kvstore_s")}
    if tot["wall_s"] > 0:
        lines.append("# share of wall: data %.1f%%  host %.1f%%  device "
                     "%.1f%%  kvstore %.1f%%"
                     % tuple(100.0 * tot[k] / tot["wall_s"]
                             for k in ("data_wait_s", "host_s", "device_s",
                                       "kvstore_s")))
    return "\n".join(lines)


# ------------------------------------------------------------ compare
_SEGMENTS = ("wall_s", "data_wait_s", "host_s", "device_s", "kvstore_s")


def compare_perf(spec_a, spec_b):
    """MFU + waterfall-segment deltas between two perf sections (dumps,
    statusz captures or ledger rows) — the one-axis diff trace_report
    ``--compare A B --perf`` prints (b minus a; positive = b slower /
    higher)."""
    a, b = load_perf_section(spec_a), load_perf_section(spec_b)

    def last_fall(s):
        rows = waterfall_rows(s)
        return rows[-1] if rows else None

    fa, fb = last_fall(a), last_fall(b)
    out = {"a": spec_a, "b": spec_b, "waterfall": [], "programs": []}
    for seg in _SEGMENTS:
        va = fa.get(seg) if fa else None
        vb = fb.get(seg) if fb else None
        out["waterfall"].append({
            "segment": seg, "a_ms": None if va is None else va * 1e3,
            "b_ms": None if vb is None else vb * 1e3,
            "delta_ms": (None if va is None or vb is None
                         else (vb - va) * 1e3)})
    for label, key in (("mfu_pct", "mfu_pct"),
                       ("hbm_util_pct", "hbm_util_pct")):
        va = fa.get(key) if fa else None
        vb = fb.get(key) if fb else None
        out[label] = {"a": va, "b": vb,
                      "delta": (None if va is None or vb is None
                                else vb - va)}
    pa = {(p.get("graph"), p.get("mode")): p for p in a.get("programs", [])}
    pb = {(p.get("graph"), p.get("mode")): p for p in b.get("programs", [])}
    for key in sorted(set(pa) | set(pb), key=str):
        ra, rb = pa.get(key), pb.get(key)
        row = {"graph": key[0], "mode": key[1]}
        for field in ("mfu_pct", "residual", "device_ms_ema", "flops"):
            va = ra.get(field) if ra else None
            vb = rb.get(field) if rb else None
            row["a_" + field] = va
            row["b_" + field] = vb
            row["delta_" + field] = (None if va is None or vb is None
                                     else vb - va)
        out["programs"].append(row)
    return out


def format_compare_perf(cmp):
    lines = ["# perf diff: %s -> %s (positive = b higher)"
             % (cmp["a"], cmp["b"])]
    fmt = lambda v, p="%.3f": "-" if v is None else p % v  # noqa: E731
    lines.append("%-14s %12s %12s %12s" % ("segment", "a_ms", "b_ms",
                                           "delta_ms"))
    for r in cmp["waterfall"]:
        lines.append("%-14s %12s %12s %12s" % (
            r["segment"], fmt(r["a_ms"]), fmt(r["b_ms"]),
            fmt(r["delta_ms"], "%+.3f")))
    for key in ("mfu_pct", "hbm_util_pct"):
        r = cmp[key]
        lines.append("%-14s %12s %12s %12s" % (
            key, fmt(r["a"]), fmt(r["b"]), fmt(r["delta"], "%+.3f")))
    if cmp["programs"]:
        lines.append("")
        lines.append("%-28s %-6s %10s %10s %12s %12s" % (
            "program", "mode", "a_mfu%", "b_mfu%", "d_resid",
            "d_device_ms"))
        for r in cmp["programs"]:
            lines.append("%-28s %-6s %10s %10s %12s %12s" % (
                str(r["graph"])[:28], r["mode"], fmt(r["a_mfu_pct"]),
                fmt(r["b_mfu_pct"]), fmt(r["delta_residual"], "%+.2f"),
                fmt(r["delta_device_ms_ema"], "%+.4f")))
            if r["delta_flops"] not in (None, 0):
                lines.append("  !! analytic flops drift: %s -> %s"
                             % (r["a_flops"], r["b_flops"]))
    return "\n".join(lines)


# ------------------------------------------------------------- ledger
def format_ledger(rows, verdict, n=5):
    if not rows:
        return "(empty ledger)"
    lines = ["# perf ledger — %d rows, showing last %d"
             % (len(rows), min(n, len(rows)))]
    for row in rows[-n:]:
        fp = row.get("fingerprint", {})
        lines.append("%s  device=%s quick=%s  %d benches, %d programs"
                     % (row.get("ts"), fp.get("device"), row.get("quick"),
                        len(row.get("benches", {})),
                        len(row.get("programs", []))))
        for name, b in sorted(row.get("benches", {}).items()):
            if "error" in b:
                lines.append("    %-26s ERROR %s" % (name,
                                                     str(b["error"])[:60]))
                continue
            mfu = ("  mfu %.2f%%" % b["mfu_pct"]
                   if b.get("mfu_pct") is not None else "")
            lines.append("    %-26s %s %s%s" % (name, b.get("value"),
                                                b.get("unit", ""), mfu))
    lines.append("")
    lines.append("# verdict: %s" % verdict["verdict"].upper())
    for r in verdict.get("regressions", []):
        lines.append("  REGRESSION: %s" % r)
    for w in verdict.get("warnings", []):
        lines.append("  warning: %s" % w)
    if verdict.get("note"):
        lines.append("  (%s)" % verdict["note"])
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="roofline attribution + perf-ledger reports")
    ap.add_argument("source", nargs="?",
                    help="flight-recorder dump / statusz JSON / "
                         "ledger.jsonl[:N]")
    ap.add_argument("--roofline", action="store_true",
                    help="per-program + per-op roofline table and fusion "
                         "candidates only")
    ap.add_argument("--waterfall", action="store_true",
                    help="per-step waterfall table only")
    ap.add_argument("--fusion", action="store_true",
                    help="fusion adoption: fused regions vs remaining "
                         "candidates with the pass's rejection reasons")
    ap.add_argument("--ledger", nargs="?", const="BENCH_LEDGER.jsonl",
                    metavar="PATH",
                    help="ledger trajectory report + regression verdict "
                         "(default ./BENCH_LEDGER.jsonl)")
    ap.add_argument("--gate", action="store_true",
                    help="with --ledger: exit 1 on a regression verdict "
                         "(CI)")
    ap.add_argument("-n", type=int, default=5,
                    help="ledger rows to show (default 5)")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="MFU + waterfall-segment deltas between two "
                         "dumps/ledger rows")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.compare:
        cmp = compare_perf(*args.compare)
        print(json.dumps(cmp, indent=1) if args.json
              else format_compare_perf(cmp))
        return 0
    if args.ledger is not None:
        perf = _ledger()
        rows = perf.read_ledger(args.ledger)
        verdict = perf.ledger_verdict(rows)
        if args.json:
            print(json.dumps({"rows": rows[-args.n:], "verdict": verdict},
                             indent=1))
        else:
            print(format_ledger(rows, verdict, n=args.n))
        if args.gate and verdict["verdict"] != "ok":
            print("perf_report --ledger --gate: REGRESSION", file=sys.stderr)
            return 1
        return 0
    if not args.source:
        ap.error("a dump/statusz/ledger source is required (or --ledger / "
                 "--compare)")
    section = load_perf_section(args.source)
    if args.json:
        if args.fusion:
            print(json.dumps(fusion_adoption(
                section, load_graph_pass_section(args.source)), indent=1))
        else:
            print(json.dumps(section, indent=1))
        return 0
    if args.fusion:
        print(format_fusion(section, args.source,
                            load_graph_pass_section(args.source)))
        return 0
    parts = []
    if args.roofline or not args.waterfall:
        parts.append(format_roofline(section, args.source))
    if args.waterfall or not args.roofline:
        parts.append(format_waterfall(section, args.source))
    # the adoption section joins the default (no-flag) report only when
    # the source actually carries program attribution — --roofline and
    # --waterfall keep printing exactly the one table they promise
    if not args.roofline and not args.waterfall \
            and section.get("programs"):
        parts.append(format_fusion(section, args.source,
                                   load_graph_pass_section(args.source)))
    print("\n\n".join(parts))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""HLO layout audit of the fused ResNet train step (VERDICT r4 item 3).

The round-3/4 profile attributed ~3.6 ms/step to layout copies and
~1.5 ms to maxpool select-and-scatter. This tool compiles the fused
``ShardedTrainer`` train step, dumps the optimized HLO, and reports every
transpose/copy/select-and-scatter with operand shapes and an estimated
byte volume — so layout work is attributable to specific graph sites
rather than a lump in the profile. Run on the TPU backend for the real
numbers (XLA:CPU chooses different layouts); the CPU run still catches
algorithmic transposes (NCHW<->NHWC shuffles we inserted ourselves).

Usage:
    python tools/hlo_layout_audit.py [--layers 50] [--batch 32] [--cpu]
    python tools/hlo_layout_audit.py --out audit.json       # save report
    python tools/hlo_layout_audit.py --compare old.json     # diff vs a
        fresh audit run (same flags)
    python tools/hlo_layout_audit.py --compare old.json new.json

``--compare`` prints a per-op regression diff (count and byte deltas,
positive = B is worse) in the same shape as ``trace_report.py
--compare`` — the artifact a layout-tuning PR pastes to prove its claim.
Library use: :func:`run_audit`, :func:`compare_reports`.
"""
import argparse
import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_OPS = ("transpose", "copy", "select-and-scatter", "bitcast-convert")


def _bytes_of(shape_str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 0
    dtype, dims = m.groups()
    width = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "f64": 8, "pred": 1, "s8": 1, "u8": 1}.get(dtype, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * width


def audit(hlo_text):
    """Count layout-moving ops in optimized HLO."""
    rows = {op: [] for op in _OPS}
    for line in hlo_text.splitlines():
        line = line.strip()
        for op in rows:
            if (" %s(" % op) in line:
                rows[op].append((line.split(" = ")[0].strip()[:60],
                                 _bytes_of(line)))
    return rows


def run_audit(layers=50, batch=32, layout="NHWC", dtype="bfloat16",
              cpu=False, dump=None, size=224):
    """Compile the fused ResNet train step and return the layout-op
    report dict (the CLI's JSON)."""
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    symbol = get_resnet(num_classes=1000, num_layers=layers,
                        image_shape=(3, size, size), layout=layout)
    trainer = ShardedTrainer(symbol, mesh, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9},
                             dtype=np.dtype(dtype))
    shapes = {"data": ((batch, 3, size, size)
                       if layout == "NCHW"
                       else (batch, size, size, 3)),
              "softmax_label": (batch,)}
    state = trainer.init(shapes)
    rng = np.random.RandomState(0)
    batch_d = trainer.shard_batch({
        "data": rng.uniform(0, 1, shapes["data"]).astype(np.float32),
        "softmax_label": rng.randint(0, 1000,
                                     batch).astype(np.float32)})

    lowered = trainer.lower_step(state, batch_d)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    if dump:
        with open(dump, "w") as f:
            f.write(hlo)

    rows = audit(hlo)
    report = {"platform": jax.devices()[0].platform,
              "layers": layers, "batch": batch, "layout": layout,
              "dtype": dtype, "size": size}
    for op, items in rows.items():
        report[op] = {"count": len(items),
                      "bytes_total": int(sum(b for _n, b in items)),
                      "top": sorted(items, key=lambda r: -r[1])[:5]}
    return report


def compare_reports(old, new):
    """Per-op regression rows between two audit reports (new minus old:
    positive delta = new moves more layout bytes). Accepts report dicts
    or paths."""
    def _load(r):
        if isinstance(r, str):
            with open(r) as f:
                return json.load(f)
        return r

    old, new = _load(old), _load(new)
    rows = []
    for op in _OPS:
        a = old.get(op, {}) or {}
        b = new.get(op, {}) or {}
        rows.append({
            "op": op,
            "a_count": a.get("count", 0), "b_count": b.get("count", 0),
            "delta_count": b.get("count", 0) - a.get("count", 0),
            "a_mb": round(a.get("bytes_total", 0) / 2**20, 2),
            "b_mb": round(b.get("bytes_total", 0) / 2**20, 2),
            "delta_mb": round((b.get("bytes_total", 0)
                               - a.get("bytes_total", 0)) / 2**20, 2),
        })
    rows.sort(key=lambda r: -abs(r["delta_mb"]))
    return rows


def format_compare(rows, label_a, label_b):
    lines = ["# layout regression diff: %s -> %s (positive = B moves "
             "more layout bytes)" % (label_a, label_b),
             "%-20s %8s %8s %8s %10s %10s %10s"
             % ("op", "a_count", "b_count", "d_count", "a_mb", "b_mb",
                "delta_mb")]
    for r in rows:
        lines.append("%-20s %8d %8d %+8d %10.2f %10.2f %+10.2f"
                     % (r["op"], r["a_count"], r["b_count"],
                        r["delta_count"], r["a_mb"], r["b_mb"],
                        r["delta_mb"]))
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--size", type=int, default=224,
                    help="square image size (CPU smoke runs shrink it)")
    ap.add_argument("--layout", default="NHWC", choices=("NHWC", "NCHW"),
                    help="NHWC is the ResNet cells' layout")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--dump", default=None,
                    help="also write the full optimized HLO here")
    ap.add_argument("--out", default=None,
                    help="also write the report JSON here")
    ap.add_argument("--compare", nargs="+", metavar="JSON",
                    help="regression diff: one path diffs OLD vs a fresh "
                         "audit run (honoring the flags above); two "
                         "paths diff OLD NEW without compiling")
    ap.add_argument("--json", action="store_true",
                    help="emit --compare rows as JSON instead of a table")
    args = ap.parse_args()

    if args.compare and len(args.compare) > 2:
        ap.error("--compare takes one (OLD vs fresh run) or two "
                 "(OLD NEW) paths")

    if args.compare and len(args.compare) == 2:
        rows = compare_reports(args.compare[0], args.compare[1])
        print(json.dumps(rows, indent=1) if args.json
              else format_compare(rows, *args.compare))
        return

    report = run_audit(layers=args.layers, batch=args.batch,
                       layout=args.layout, dtype=args.dtype,
                       cpu=args.cpu, dump=args.dump, size=args.size)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.compare:
        rows = compare_reports(args.compare[0], report)
        print(json.dumps(rows, indent=1) if args.json
              else format_compare(rows, args.compare[0], "fresh run"))
    else:
        print(json.dumps(report))


if __name__ == "__main__":
    main()

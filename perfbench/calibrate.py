#!/usr/bin/env python3
"""perfbench/calibrate.py — the readings a cell's limits are set from, on
the chip at the cell's own size, several seeds in one process:

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 [--control] [--faults]

For each seed: the program's first three steps against the plain reference
(the lower reading); with ``--control`` the reference in fp8 put in the
program's place (the upper reading); with ``--faults`` the reference with
half of the batch left out. One JSON line per seed. Not part of a run.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lr", type=float, help="try another learning rate")
    ap.add_argument("--witness", action="store_true",
                    help="the program itself in float32 at highest matmul "
                         "precision: a second witness beside the reference")
    ap.add_argument("--dump", help="append every reading, leaf by leaf, "
                                   "to this .jsonl file")
    args = ap.parse_args(argv)

    from perfbench import check, run

    _, entry, workload, config = run.load_cell(args.workload, args.rehearse)
    if args.lr is not None:
        config["optimizer"]["learning_rate"] = args.lr
    if args.witness:
        config["compute_dtype"] = "float32"
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=%d" % entry["chips"])
    import jax

    import mxnet_tpu as mx

    mx.config.enable_compile_cache()
    devices = jax.devices()[:entry["chips"]]
    if not args.rehearse and devices[0].platform != "tpu":
        raise SystemExit("calibrate: not a TPU")
    driver = importlib.import_module("perfbench.drivers." + config["driver"])
    if args.witness:
        jax.config.update("jax_default_matmul_precision", "highest")

    def dump(seed, kind, readings):
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"seed": seed, "kind": kind,
                                    **readings}) + "\n")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = driver.build(config, workload["sizes"], seed, devices)
        _, _, first = run.drive(cell, 0, steps=3)
        got = cell.readings([r[2] for r in first])
        cell.release()
        gc.collect()
        t1 = time.perf_counter()
        want = cell.reference()
        t2 = time.perf_counter()
        dump(seed, "witness" if args.witness else "program", got)
        dump(seed, "reference", want)
        row = {"seed": seed, "program": check.gaps(got, want),
               "loss": [got["loss"], want["loss"]],
               "program_s": t1 - t0, "reference_s": t2 - t1}
        if args.control:
            control = cell.reference(quant=True)
            dump(seed, "control", control)
            row["control"] = check.gaps(control, want)
            row["control_s"] = time.perf_counter() - t2
        if args.faults:
            half = cell.reference(share=0.5)
            dump(seed, "half_batch", half)
            row["half_batch"] = check.gaps(half, want)
            if entry["chips"] > 1:
                alone = cell.reference(share=1.0 / entry["chips"])
                dump(seed, "no_exchange", alone)
                row["no_exchange"] = check.gaps(alone, want)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""perfbench/run.py — one cell of BENCHMARK.json, one process, one result.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model and state on the device from ``--seed``, drives its
first three steps through the window's own loop (that compiles or reads the
cache, and is what the reference follows), measures for ``--seconds`` with
one step in flight, then frees the program and runs the plain reference.
The last line of standard output is the result. Without a TPU, or on a
``device_kind`` that ``peaks.json`` does not list, it prints no result and
exits non-zero. ``--rehearse`` runs the workload's tiny ``rehearsal`` sizes
on the CPU and always prints ``"correct": false``: never a result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, rehearse):
    """(BENCHMARK.json entry, workload file, configuration) of a cell; the
    rehearsal's tiny sizes laid over both where asked."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit("perfbench: no cell %r in BENCHMARK.json" % name)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = load_json(ROOT, cfg_entry["file"])
    workload = load_json(HERE, "workloads", name + ".json")
    if rehearse:
        config.update(workload["rehearsal"]["config"])
        workload["sizes"].update(workload["rehearsal"]["sizes"])
    return bench, entry, workload, config


def metrics_for(bench, kind, cell, reported=None):
    """The cell's metrics of one kind: those that list it, and those with
    no list (per-layer: whose ``moves`` the cell reports)."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m["moves"] in reported:
            out.append(m)
    return out


def layer_reader(name):
    """layer_metrics/<name>.py, or the file of the name before its first
    dot (``dispatch_ms.py`` reads ``dispatch_ms.images`` and ``.tokens``)."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "layer_metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "perfbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit("perfbench: no reader for per-layer metric %r" % name)


def drive(cell, start, seconds=None, steps=None, annotate=False):
    """The loop a training script runs, one step in flight: dispatch step
    i+1, then block on step i's result, stamp it and keep its loss. Stops
    dispatching after ``seconds`` (or ``steps``), drains, and returns
    (t_begin, t_end, [(dispatch_seconds, completed_at, loss), ...])."""
    import contextlib

    import jax

    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    rows, prev, i = [], None, start
    t_begin = time.perf_counter()

    def finish(prev):
        handle, took = prev
        with span("perfbench.wait"):
            loss = cell.complete(handle)
        rows.append((took, time.perf_counter(), loss))

    while (i - start < steps if steps is not None
           else time.perf_counter() - t_begin < seconds):
        t0 = time.perf_counter()
        with span("perfbench.dispatch"):
            handle = cell.dispatch(i)
        took = time.perf_counter() - t0
        if prev is not None:
            finish(prev)
        prev, i = (handle, took), i + 1
    if prev is not None:
        finish(prev)
    return t_begin, time.perf_counter(), rows


def traced_steps(cell, start, steps, reduce_trace):
    """A few more steps of the same loop under the JAX profiler."""
    import jax

    with tempfile.TemporaryDirectory(prefix="perfbench_trace_") as tmp:
        jax.profiler.start_trace(tmp)
        try:
            drive(cell, start, steps=steps, annotate=True)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise SystemExit("perfbench: expected one .xplane.pb, found %s"
                             % found)
        return reduce_trace(found[0])


def run_cell(args, rehearse=False, sabotage=None):
    """Everything after the arguments; returns the result as a dict.
    ``sabotage(cell)`` lets the tests break the timed path underneath."""
    bench, entry, workload, config = load_cell(args.workload, rehearse)
    chips = entry["chips"]
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d" % chips)

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.observability import metrics as obs
    from perfbench import check, trace_reduce

    mx.config.enable_compile_cache()
    mx.observability.set_enabled(True)  # jit.compile_count
    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise SystemExit("perfbench: jax found platform %r, not a TPU; "
                         "nothing was measured (--rehearse runs the tiny "
                         "sizes on the CPU)" % dev.platform)
    if len(devices) < chips:
        raise SystemExit("perfbench: cell %s needs %d chips, jax found %d"
                         % (args.workload, chips, len(devices)))
    peaks = load_json(HERE, "peaks.json").get(dev.device_kind)
    if peaks is None and not rehearse:
        raise SystemExit("perfbench: device_kind %r has no row in "
                         "perfbench/peaks.json" % dev.device_kind)
    used = devices[:chips]

    driver = importlib.import_module("perfbench.drivers." + config["driver"])
    cell = driver.build(config, workload["sizes"], args.seed, used)
    if sabotage is not None:
        sabotage(cell)
    # the first three steps: the window's own call and feed, and what the
    # reference follows; the first of them compiles or reads the cache
    _, _, first = drive(cell, 0, steps=3)
    compiles = obs.get_value("jit.compile_count", 0)
    setup_s = time.perf_counter() - T_START

    t_begin, t_end, rows = drive(cell, 3, seconds=args.seconds)
    window_compiles = obs.get_value("jit.compile_count", 0) - compiles
    seconds = t_end - t_begin
    stamps = [t_begin] + [r[1] for r in rows]
    window = {
        "seconds": seconds, "steps": len(rows), "chips": chips,
        "unit": cell.unit, "units_per_step": cell.units_per_step,
        "flops_per_step": cell.flops_per_step,
        "dispatch_s": [r[0] for r in rows],
        "intervals_s": [b - a for a, b in zip(stamps[1:], stamps[2:])],
        "sizes": workload["sizes"]}
    failed = sum(1 for r in rows if not math.isfinite(r[2]))

    trace = None
    if args.trace:
        trace = traced_steps(cell, 3 + len(rows), workload["trace_steps"],
                             lambda path: trace_reduce.reduce_file(
                                 path, chips, workload["trace_steps"],
                                 rehearse))
    got = cell.readings([r[2] for r in first])
    # the TPU runtime's peak counts live arrays and leaves out the running
    # executable's temporaries (PERF.md, PR 25): add XLA's own count of them
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = (max(s.get("peak_bytes_in_use", 0) for s in stats)
                   + cell.step_temp_bytes())
    cell.release()
    gc.collect()
    t_ref = time.perf_counter()
    want = cell.reference()
    reference_s = time.perf_counter() - t_ref
    correct, compared = check.compare(got, want, workload["limits"])
    compared["window_compiles"] = {"value": window_compiles, "limit": 0}
    compared["nonfinite_steps"] = {"value": failed, "limit": 0}
    correct = correct and window_compiles == 0 and failed == 0 and bool(rows)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    rate = cell.unit + "_per_s"
    if args.trace:
        values = {}
        for m in metrics_for(bench, "per_layer", args.workload,
                             {rate, "step_ms_p95", "setup_s"}):
            value = layer_reader(m["name"])(window, trace, config, peaks)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = trace["busy_s"], trace[
            "window_s"]
    else:
        import numpy as np

        e2e = {rate: len(rows) * cell.units_per_step / seconds,
               "step_ms_p95": 1e3 * float(np.percentile(
                   window["intervals_s"], 95)) if len(rows) > 2 else None,
               "setup_s": setup_s}
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in metrics_for(bench, "end_to_end", args.workload)
                  if e2e[m["name"]] is not None}
    result = {"correct": correct, "attempted": len(rows) + 3,
              "failed": failed, "metrics": values, "device": device}
    if trace is not None:
        result["breakdown"] = trace["breakdown"]
    result["reference_s"] = reference_s
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never a result")
    args = ap.parse_args(argv)
    result = run_cell(args, rehearse=args.rehearse)
    if args.rehearse:
        result = dict(result, correct=False, rehearsal=True,
                      compared=result.pop("compared"))
    for name, row in result["compared"].items():
        print("perfbench: %s = %r (limit %r)%s" % (
            name, row["value"], row["limit"],
            " at " + row["at"] if row.get("at") else ""), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

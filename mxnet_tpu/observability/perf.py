"""Roofline attribution: which part of a step is slow, and which
regions are compute- vs bandwidth-bound (ISSUE 13).  Four pieces:

* **Analytic cost accounting per compiled program** — walk the bound
  graph once per (program, shape signature) and compute FLOPs + HBM
  bytes per node (conv / FC / matmul / attention / elemwise rules) and
  per program, against the chip's published peaks — the autotuner's
  basis (``autotune.cost_model.CEILINGS`` + ``roofline_seconds``, read
  from ``context.DEVICE_PEAKS``) and what ``mfu_pct`` in
  PERF_LEDGER.jsonl means.  Cached on
  the ``_GraphProgram`` alongside its ``tuning_key``.
* **Achieved-vs-roofline attribution** — the executor's fenced
  host/device split (the PR 2 discipline) feeds measured device time
  per program run into the analytic model: per-program and per-step
  ``perf.mfu_pct`` / ``perf.hbm_util_pct`` gauges, a per-op roofline
  table, and ranked *fusion candidates* — consecutive bandwidth-bound
  op runs whose intermediate tensors a fused kernel would keep out of
  HBM (ROADMAP item 3's fusion-region pass wants exactly this list).
* **Step-time waterfall** — the fit loop partitions each step's wall
  time into data-wait (input pipeline), device compute (fenced waits),
  kvstore/collective time, and host dispatch (the residual, BY
  CONSTRUCTION: ``host = wall - data - device - kv``, so the segments
  always sum to the step wall exactly).  Per-step records ride a small
  ring surfaced by the flight-recorder ``perf`` provider, ``/statusz``,
  ``get_stats()`` and ``tools/perf_report.py``.
* **Perf ledger** — append-only ``BENCH_LEDGER.jsonl`` rows (one per
  benchmark run: env/device fingerprint, per-bench throughput + MFU,
  predicted-vs-measured residual per program) with a regression
  verdict computed over the CPU-stable quantities.  Nothing in the
  tree writes such rows since PR 30 (ROADMAP Design): the driver's
  PERF_LEDGER.jsonl is the record.

Everything here is host-side arithmetic: the only device interaction is
the ``block_until_ready`` fence the executor already performs for the
profiler, now shared.  Cost walks run once per (program, shape) —
steady-state steps do dict probes only.  ``MXNET_PERF=0`` turns the
whole layer off.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

from . import metrics

__all__ = ["active", "node_cost", "flash_attention_cost", "program_cost",
           "fusion_candidates", "note_program_run", "program_table",
           "step_begin", "step_end", "step_abandon", "step_active",
           "scope_suspended",
           "note_data_wait", "note_kv", "mark_collective", "waterfalls",
           "last_waterfall",
           "summary", "summary_brief", "reset",
           "append_ledger", "read_ledger", "ledger_verdict",
           "TRAIN_FLOPS_MULT", "TRAIN_BYTES_MULT", "ELEMWISE_FLOPS",
           "MOVEMENT_OPS"]

# ----------------------------------------------------------------- flags
_active_cached = None


def active():
    """The MXNET_PERF flag (default 1), cached — config.set_flag keeps
    the cache coherent via its applier (the MXNET_TELEMETRY pattern)."""
    global _active_cached
    if _active_cached is None:
        from ..config import get_flag

        _active_cached = bool(get_flag("MXNET_PERF"))
    return _active_cached


def _apply_perf_flag(value):
    """config.set_flag('MXNET_PERF', ...) applier."""
    global _active_cached
    _active_cached = None if value is None else bool(value)


def _ring_capacity():
    from ..config import get_flag

    return max(8, get_flag("MXNET_PERF_RING"))


_cm = None


def _ceilings():
    # lazy (observability must not import the autotune package at
    # module load — cycle risk through mxnet_tpu.__init__) and bound
    # once: a per-call import costs ~1 µs of import machinery on the
    # per-step path
    global _cm
    if _cm is None:
        from ..autotune import cost_model

        _cm = cost_model
    return _cm


# ------------------------------------------------- analytic per-node rules
#: fused train program (fwd+bwd+grads) multipliers over the forward
#: walk: the backward re-runs ~2 matmuls per layer (dgrad + wgrad), so
#: FLOPs triple; activations are re-read and gradients written, so
#: traffic is modeled with the same integer multiplier (coarse on
#: purpose — ``residual`` reports measured / predicted)
TRAIN_FLOPS_MULT = 3
TRAIN_BYTES_MULT = 3

#: per-OUTPUT-element FLOP weights for elemwise-shaped compute ops;
#: anything absent (and not in MOVEMENT_OPS) counts 1 FLOP per output
#: element.  Documented constants — the hand-count tests restate them.
ELEMWISE_FLOPS = {
    "Activation": 1, "LeakyReLU": 2, "relu": 1, "sigmoid": 4, "tanh": 4,
    "softmax": 5, "log_softmax": 5, "SoftmaxOutput": 5,
    "SoftmaxActivation": 5, "softmax_cross_entropy": 5,
    "BatchNorm": 4, "LayerNorm": 8, "InstanceNorm": 8, "L2Normalization": 4,
    "LRN": 8, "Dropout": 2,
    # Pooling is NOT here: node_cost has a dedicated branch charging one
    # FLOP per INPUT element (every input element is touched once)
}

#: pure data-movement ops: zero FLOPs, traffic only
MOVEMENT_OPS = frozenset((
    "Reshape", "reshape", "Flatten", "flatten", "Cast", "cast",
    "transpose", "slice", "slice_axis", "SliceChannel", "split",
    "expand_dims", "squeeze", "Concat", "concat", "stack", "tile",
    "repeat", "Pad", "pad", "BlockGrad", "identity", "_copy", "zeros_like",
    "ones_like", "broadcast_axis", "broadcast_to", "Embedding", "take",
    "gather_nd", "_zeros", "_ones", "_full", "Dropout_inference",
))


def _prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def node_cost(op, attrs, in_shapes, out_shapes, dtype_bytes=4):
    """(flops, hbm_bytes) of one graph node given its input/output
    shapes.  Rules (all exact integer arithmetic):

    * Convolution  — ``2 * K * out_elems`` with ``K = C_in/groups *
      prod(kernel)`` (+ ``out_elems`` for bias).
    * FullyConnected — ``2 * in_dim * out_elems`` (+ bias).
    * dot / batch_dot — ``2 * contract_dim * out_elems``.
    * elemwise — ``ELEMWISE_FLOPS[op] * out_elems`` (default 1);
      movement ops 0; Pooling counts per input element.
    * ``_FusedRegion`` — base-op FLOPs plus each epilogue step's
      elemwise FLOPs, but EXTERIOR bytes only: once a region IS fused
      its interior tensors stay in VMEM, so the pre-fusion double-count
      (the fusion saving) stops being charged — the MFU denominator and
      roofline table tell the truth post-fusion
      (``2 * steps * out_bytes`` saved exactly, pinned by
      tests/test_fusion.py).
    * bytes — every input read once + every output written once at
      ``dtype_bytes`` each (pre-fusion accounting: a producer's output
      and its consumer's read both count, which is exactly the traffic
      a fusion would save — see :func:`fusion_candidates`).
    """
    in_shapes = [s for s in in_shapes if s is not None]
    out_shapes = [s for s in out_shapes if s is not None]
    in_elems = sum(_prod(s) for s in in_shapes)
    out_elems = sum(_prod(s) for s in out_shapes)
    nbytes = (in_elems + out_elems) * int(dtype_bytes)

    if op == "_FusedRegion":
        import json as _json

        from ..ops.registry import get_op as _get_op

        base_op = attrs.get("base_op", "FullyConnected")
        try:
            base = _get_op(base_op)
            battrs = dict(base.parse_attrs(
                _json.loads(attrs.get("base_attrs", "{}")))._d)
            steps = _json.loads(attrs.get("epilogue", "[]"))
        except Exception:
            return ELEMWISE_FLOPS.get(op, 1) * out_elems, nbytes
        n_base = int(attrs.get("n_base", 2))
        base_flops, _ = node_cost(base_op, battrs, in_shapes[:n_base],
                                  out_shapes, dtype_bytes=dtype_bytes)
        flops = base_flops
        for step in steps:
            sop = step.get("op")
            if sop in MOVEMENT_OPS:
                continue
            flops += ELEMWISE_FLOPS.get(sop, 1) * out_elems
        # exterior traffic only: base inputs + epilogue extras read
        # once, the final output written once — the interior producer/
        # consumer round trips are gone, which is the saving the fuse
        # pass claimed (graph_pass/fuse.py region scoring)
        return flops, nbytes

    if op in MOVEMENT_OPS:
        return 0, nbytes
    if op == "Convolution" and in_shapes and out_shapes:
        kernel = tuple(attrs.get("kernel", ()))
        groups = int(attrs.get("num_group", 1) or 1)
        layout = attrs.get("layout") or ""
        d = in_shapes[0]
        c_in = d[-1] if layout.endswith("C") else d[1]
        k = (int(c_in) // groups) * _prod(kernel)
        o = _prod(out_shapes[0])
        flops = 2 * k * o
        if not attrs.get("no_bias"):
            flops += o
        return flops, nbytes
    if op == "Deconvolution" and in_shapes:
        kernel = tuple(attrs.get("kernel", ()))
        groups = int(attrs.get("num_group", 1) or 1)
        nf = int(attrs.get("num_filter", 1) or 1)
        k = (nf // groups) * _prod(kernel)
        flops = 2 * k * _prod(in_shapes[0])
        if not attrs.get("no_bias", True):
            flops += out_elems
        return flops, nbytes
    if op == "FullyConnected" and in_shapes and out_shapes:
        d = in_shapes[0]
        flatten = attrs.get("flatten", True)
        in_dim = _prod(d[1:]) if flatten else int(d[-1])
        o = _prod(out_shapes[0])
        flops = 2 * in_dim * o
        if not attrs.get("no_bias"):
            flops += o
        return flops, nbytes
    if op in ("dot", "batch_dot") and in_shapes and out_shapes:
        d = in_shapes[0]
        ta = bool(attrs.get("transpose_a"))
        if op == "dot":
            contract = int(d[0]) if ta else int(d[-1])
        else:
            contract = int(d[-2]) if ta else int(d[-1])
        return 2 * contract * _prod(out_shapes[0]), nbytes
    if op == "Pooling":
        return in_elems, nbytes
    return ELEMWISE_FLOPS.get(op, 1) * out_elems, nbytes


def flash_attention_cost(B, H, T, D, causal=True, dtype_bytes=2,
                         backward=False):
    """(flops, hbm_bytes) of one flash-attention call — the rule for the
    attention regions that live below the symbol layer (Pallas kernels
    in parallel/flash_attention.py).  FLOPs: ``4*B*H*T*T*D`` (qk^T + pv,
    2 FLOPs per MAC each), halved under causal masking (dead-block
    skip); the fused backward's five matmuls a tile are 2.5x that.
    Bytes: the streaming traffic — q, k, v read + o written once
    (``4*B*H*T*D``), doubled for the backward's second pass over the
    tiles."""
    flops = 4 * B * H * T * T * D
    if causal:
        flops //= 2
    nbytes = 4 * B * H * T * D * int(dtype_bytes)
    if backward:
        flops = int(flops * 2.5)
        nbytes *= 2
    return flops, nbytes


def program_cost(symbol, topo, var_shapes, dtype_bytes=4, train=False,
                 graph="program"):
    """Walk a bound graph once: per-node FLOPs/bytes rows + program
    totals + roofline seconds at the published peaks.

    ``var_shapes`` maps every variable (args + aux) to its bound shape;
    internal shapes come from partial shape inference.  ``train=True``
    applies the fused fwd+bwd multipliers to the program totals (the
    per-op table stays forward-basis, noted in ``basis``).  Returns a
    JSON-safe dict, or None when shape inference fails (the caller then
    skips attribution rather than crashing the step)."""
    cm = _ceilings()
    internals = symbol.get_internals()
    entries = internals._outputs
    try:
        _, out_shapes, _ = internals.infer_shape_partial(**var_shapes)
    except Exception:
        return None
    shape_of = {}
    for (node, idx), shp in zip(entries, out_shapes):
        if shp is not None and not node.is_variable:
            shape_of[(id(node), idx)] = tuple(shp)

    def entry_shape(e):
        n, i = e
        if n.is_variable:
            return var_shapes.get(n.name)
        return shape_of.get((id(n), i))

    ridge = cm.ridge_intensity()
    rows = []
    fused_regions = []
    fused_saved = 0
    total_flops = total_bytes = 0
    for node in topo:
        if node.is_variable:
            continue
        n_main = node.num_main_inputs()
        in_shapes = [entry_shape(e) for e in node.inputs[:n_main]]
        nout = node.opdef().get_num_outputs(node.parsed_attrs())
        node_outs = [shape_of.get((id(node), i)) for i in range(nout)]
        attrs = dict(node.parsed_attrs()._d)
        flops, nbytes = node_cost(node.op, attrs, in_shapes, node_outs,
                                  dtype_bytes=dtype_bytes)
        total_flops += flops
        total_bytes += nbytes
        out_elems = sum(_prod(s) for s in node_outs if s is not None)
        row = {
            "name": node.name, "op": node.op,
            "flops": flops, "bytes": nbytes,
            "out_bytes": out_elems * int(dtype_bytes),
            "intensity": (flops / nbytes) if nbytes else 0.0,
            "bound": ("compute" if nbytes and flops / nbytes >= ridge
                      else "bandwidth"),
            "roofline_s": cm.roofline_seconds(flops, nbytes),
        }
        if node.op == "_FusedRegion":
            # interior accounting: every epilogue step's input was a
            # producer-write + consumer-read pair pre-fusion — exactly
            # 2 * out_bytes per step (region interiors share the output
            # shape); the saving the pre-fusion tables double-counted
            # and the fused program no longer pays
            try:
                import json as _json

                n_steps = len(_json.loads(attrs.get("epilogue", "[]")))
                members = _json.loads(
                    node.user_attrs.get("__fused_members__", "[]"))
            except Exception:
                n_steps, members = 0, []
            saved = 2 * n_steps * row["out_bytes"]
            row["fused"] = True
            row["members"] = members
            row["interior_saved_bytes"] = saved
            fused_saved += saved
            fused_regions.append({"name": node.name, "members": members,
                                  "saved_bytes": saved})
        rows.append(row)
    if train:
        total_flops *= TRAIN_FLOPS_MULT
        total_bytes *= TRAIN_BYTES_MULT
    return {
        "graph": graph,
        "mode": "train" if train else "infer",
        "basis": ("forward walk x%d flops / x%d bytes (fused fwd+bwd)"
                  % (TRAIN_FLOPS_MULT, TRAIN_BYTES_MULT)) if train
                 else "forward walk",
        "dtype_bytes": int(dtype_bytes),
        "flops": total_flops,
        "hbm_bytes": total_bytes,
        "roofline_s": cm.roofline_seconds(total_flops, total_bytes),
        "ridge_intensity": ridge,
        "ops": rows,
        "fusion_candidates": fusion_candidates(rows),
        "fused_regions": fused_regions,
        "fused_saved_bytes": fused_saved,
    }


def fusion_candidates(rows, k=8):
    """Rank fusion-region candidates: maximal runs of >=2 consecutive
    bandwidth-bound ops in topo order.  The saving of fusing a run is
    the intermediate traffic it eliminates — each interior op's output
    is written to and re-read from HBM today (``2 * out_bytes``), and
    would stay in registers/VMEM fused.  Ranked by saved bytes
    descending: the top entries are where a fusion-region pass (ROADMAP
    item 3) buys the most.  ``_FusedRegion`` rows never join a run —
    the fuse pass already consumed them, so the list shows only the
    REMAINING headroom (tools/perf_report.py renders it as the adoption
    column)."""
    out = []
    run = []
    for row in rows + [None]:
        if row is not None and row["bound"] == "bandwidth" \
                and not row.get("fused") \
                and (row["flops"] or row["bytes"]):
            run.append(row)
            continue
        if len(run) >= 2:
            saved = 2 * sum(r["out_bytes"] for r in run[:-1])
            out.append({
                "ops": [r["name"] for r in run],
                "op_types": [r["op"] for r in run],
                "bytes": sum(r["bytes"] for r in run),
                "flops": sum(r["flops"] for r in run),
                "saved_bytes": saved,
            })
        run = []
    out.sort(key=lambda c: -c["saved_bytes"])
    return out[:k]


# ------------------------------------------- measured program attribution
_lock = threading.Lock()
_programs = {}     # key -> entry dict  # guarded-by: _lock
_provider_armed = False  # guarded-by: _lock


def _arm_provider():
    """Register the flight-recorder 'perf' provider on first activity
    (a dump from a process that never measured anything stays clean).
    Lock-free armed probe on the per-step path; the lock arbitrates the
    one real arming race."""
    global _provider_armed
    if _provider_armed:
        return
    with _lock:
        if _provider_armed:
            return
        _provider_armed = True
    from . import flight_recorder

    flight_recorder.register_provider("perf", summary)


def note_program_run(cost, device_s, host_s, replicas=1):
    """Fold one measured program run (fenced host/device split from the
    executor) into the attribution registry and the active step scope.
    The FIRST run per program entry is treated as warmup (its host side
    contains trace+compile) and excluded from the measured stats AND
    the published gauges; every run's device wait still lands in the
    step waterfall.  ``replicas`` annotates a group-level note covering
    N data-parallel replicas of the same program — the cost stays
    per-replica so MFU remains relative to ONE chip's ceiling (N
    replicas on N chips at the same per-chip utilization read the
    same)."""
    if cost is None:
        return
    _arm_provider()
    cm = _ceilings()
    key = (cost["graph"], cost["mode"])
    mfu = hbm = None
    if device_s > 0:
        mfu = 100.0 * (cost["flops"] / device_s) / cm.PEAK_FLOPS_PER_S
        hbm = (100.0 * (cost["hbm_bytes"] / device_s)
               / cm.PEAK_HBM_BYTES_PER_S)
    warmup = False
    with _lock:
        entry = _programs.get(key)
        if entry is None:
            # per-op roofline table rides the entry (top rows by
            # analytic roofline seconds) so a flight-recorder dump or
            # /statusz carries the fusion-candidate ranking without a
            # re-walk (tools/perf_report.py, trace_report --roofline)
            ops = sorted(cost["ops"], key=lambda r: -r["roofline_s"])[:64]
            entry = _programs[key] = {
                "graph": cost["graph"], "mode": cost["mode"],
                "flops": cost["flops"], "hbm_bytes": cost["hbm_bytes"],
                "roofline_ms": cost["roofline_s"] * 1e3,
                "ridge_intensity": cost["ridge_intensity"],
                "basis": cost["basis"],
                "ops_top": [dict(r) for r in ops],
                "fusion_candidates": [dict(c)
                                      for c in cost["fusion_candidates"]],
                "fused_regions": [dict(r)
                                  for r in cost.get("fused_regions", ())],
                "fused_saved_bytes": cost.get("fused_saved_bytes", 0),
                "runs": 0, "warmup_runs": 0, "replicas": int(replicas),
                "device_ms_last": None, "device_ms_best": None,
                "device_ms_ema": None, "host_ms_ema": None,
                "mfu_pct": None, "hbm_util_pct": None, "residual": None,
            }
        if entry["runs"] == 0 and entry["warmup_runs"] == 0:
            entry["warmup_runs"] = 1
            warmup = True
        else:
            entry["runs"] += 1
            d_ms, h_ms = device_s * 1e3, host_s * 1e3
            entry["device_ms_last"] = d_ms
            entry["device_ms_best"] = (d_ms if entry["device_ms_best"] is None
                                       else min(entry["device_ms_best"], d_ms))
            for field, v in (("device_ms_ema", d_ms), ("host_ms_ema", h_ms)):
                prev = entry[field]
                entry[field] = v if prev is None else 0.8 * prev + 0.2 * v
            if mfu is not None:
                entry["mfu_pct"] = mfu
                entry["hbm_util_pct"] = hbm
            if entry["roofline_ms"] > 0:
                # measured / predicted (>1 = slower than roofline,
                # i.e. the MFU gap)
                entry["residual"] = (entry["device_ms_ema"]
                                     / entry["roofline_ms"])
    if mfu is not None and not warmup and metrics.enabled():
        # warmup runs are excluded from the gauges too: the first run's
        # device wait is trace+compile-distorted, exactly the number the
        # registry's warmup exclusion suppresses
        metrics.gauge("perf.mfu_pct", labels={"scope": "program"},
                      help="achieved FLOP/s as % of the published bf16 "
                           "peak (context.DEVICE_PEAKS)").set(mfu)
        metrics.gauge("perf.hbm_util_pct", labels={"scope": "program"},
                      help="achieved HBM traffic as % of the published "
                           "bandwidth").set(hbm)
    scope = getattr(_tls, "step", None)
    if scope is not None:
        scope["device_s"] += device_s
        scope["flops"] += cost["flops"]
        scope["hbm_bytes"] += cost["hbm_bytes"]
        scope["programs"] += 1


def program_table():
    """Snapshot of the per-program attribution entries (JSON-safe)."""
    with _lock:
        return [dict(v) for v in _programs.values()]


# ------------------------------------------------------ step waterfall
_tls = threading.local()
_waterfalls = None  # deque of step records  # guarded-by: _lock


def step_active():
    """True while this thread is inside a fit-step waterfall scope (the
    executor's fenced-measurement gate)."""
    return getattr(_tls, "step", None) is not None


def step_begin():
    """Open a step scope on this thread (fit loop).  No-op under
    MXNET_PERF=0."""
    if not active():
        return
    _tls.step = {"t0": time.perf_counter(), "data_wait_s": 0.0,
                 "device_s": 0.0, "kvstore_s": 0.0,
                 "flops": 0, "hbm_bytes": 0, "programs": 0}


def step_abandon():
    """Discard the open scope without recording (epoch end, resume
    fast-forward)."""
    _tls.step = None


class _ScopeSuspended:
    """Context manager: temporarily hide the step scope from this
    thread.  The multi-replica dispatch loop uses it so per-executor
    fenced measurement cannot serialize replicas that should overlap —
    the group fences ONCE after dispatching all of them
    (executor_group.DataParallelExecutorGroup.forward)."""

    __slots__ = ("_saved",)

    def __enter__(self):
        self._saved = getattr(_tls, "step", None)
        _tls.step = None
        return self

    def __exit__(self, *exc):
        _tls.step = self._saved
        return False


def scope_suspended():
    return _ScopeSuspended()


def note_data_wait(seconds):
    """Input-pipeline wait attributed to the current step (called by the
    fit loop's lookahead iterator around ``next()``)."""
    scope = getattr(_tls, "step", None)
    if scope is not None:
        scope["data_wait_s"] += seconds


def note_kv(seconds):
    """kvstore/collective time attributed to the current step (called by
    KVStore.push/pull around the whole operation)."""
    scope = getattr(_tls, "step", None)
    if scope is not None:
        scope["kvstore_s"] += seconds


def mark_collective():
    """Tag the current step's kvstore segment as in-device collectives
    (the mesh backend): the ``kvstore_s`` wall is compiled-program
    dispatch, not host RPC round-trips — waterfall rows carry
    ``collective: true`` so dist_report / the fleet timeline render the
    segment as device-side exchange (docs/perf_observability.md)."""
    scope = getattr(_tls, "step", None)
    if scope is not None:
        scope["collective"] = True


def step_end(step=None):
    """Close the scope and record one waterfall row.  The partition is
    exact BY CONSTRUCTION: ``host_s = wall_s - (data_wait_s + device_s +
    kvstore_s)``, so the four segments always sum to the measured step
    wall.  Returns the record (None when no scope was open)."""
    global _waterfalls
    scope = getattr(_tls, "step", None)
    if scope is None:
        return None
    _tls.step = None
    wall = time.perf_counter() - scope["t0"]
    data, device, kv = (scope["data_wait_s"], scope["device_s"],
                        scope["kvstore_s"])
    host = wall - (data + device + kv)
    cm = _ceilings()
    rec = {
        "step": step,
        "rank": _dist_rank(),
        "wall_s": wall,
        "data_wait_s": data,
        "device_s": device,
        "kvstore_s": kv,
        "host_s": host,
        "flops": scope["flops"],
        "hbm_bytes": scope["hbm_bytes"],
        "programs": scope["programs"],
        # step MFU charges the WHOLE step wall (the honest training
        # number: data stalls and host dispatch count against you)
        "mfu_pct": (100.0 * (scope["flops"] / wall)
                    / cm.PEAK_FLOPS_PER_S) if wall > 0 else None,
        "hbm_util_pct": (100.0 * (scope["hbm_bytes"] / wall)
                         / cm.PEAK_HBM_BYTES_PER_S) if wall > 0 else None,
    }
    if scope.get("collective"):
        rec["collective"] = True
    _arm_provider()
    with _lock:
        if _waterfalls is None:
            _waterfalls = collections.deque(maxlen=_ring_capacity())
        _waterfalls.append(rec)
    if metrics.enabled() and rec["mfu_pct"] is not None:
        metrics.gauge("perf.mfu_pct", labels={"scope": "step"},
                      help="achieved FLOP/s as % of the published bf16 "
                           "peak (context.DEVICE_PEAKS)"
                      ).set(rec["mfu_pct"])
        metrics.gauge("perf.hbm_util_pct", labels={"scope": "step"},
                      help="achieved HBM traffic as % of the published "
                           "bandwidth").set(rec["hbm_util_pct"])
    return rec


def waterfalls(n=None):
    """Chronological copy of the per-step waterfall ring (last ``n``)."""
    with _lock:
        rows = list(_waterfalls) if _waterfalls is not None else []
    return rows if n is None else rows[-n:]


def last_waterfall():
    with _lock:
        return (dict(_waterfalls[-1])
                if _waterfalls else None)


# ----------------------------------------------------------- summaries
def _dist_rank():
    # lazy: dist_trace imports perf at module level, so this must not
    # be a top-level import; sys.modules hit + cached int, ~µs per step
    from . import dist_trace
    return dist_trace.current_rank()


def _waterfall_brief(rec):
    if rec is None:
        return None
    brief = {k: rec[k] for k in ("step", "wall_s", "data_wait_s",
                                 "device_s", "kvstore_s", "host_s",
                                 "mfu_pct", "hbm_util_pct")}
    if rec.get("rank") is not None:
        brief["rank"] = rec["rank"]
    return brief


def summary():
    """The full perf section (flight-recorder provider, /statusz,
    tools/perf_report.py): program table + recent waterfalls + ceilings.
    Returns None when nothing was ever measured (keeps unrelated dumps
    clean)."""
    programs = program_table()
    falls = waterfalls(16)
    if not programs and not falls:
        return None
    cm = _ceilings()
    return {
        "enabled": active(),
        "ceilings": dict(cm.CEILINGS),
        "programs": programs,
        "waterfalls": falls,
        "waterfall": _waterfall_brief(falls[-1] if falls else None),
    }


def summary_brief():
    """The compact perf section engine ``get_stats()`` snapshots carry
    (stats_schema): current step MFU/HBM utilization + the last
    waterfall + how many programs have attribution."""
    last = last_waterfall()
    progs = program_table()
    mfu = last["mfu_pct"] if last else None
    hbm = last["hbm_util_pct"] if last else None
    if mfu is None and progs:
        measured = [p for p in progs if p["mfu_pct"] is not None]
        if measured:
            mfu = measured[-1]["mfu_pct"]
            hbm = measured[-1]["hbm_util_pct"]
    return {
        "enabled": active(),
        "mfu_pct": mfu,
        "hbm_util_pct": hbm,
        "programs": len(progs),
        "waterfall": _waterfall_brief(last),
    }


def reset():
    """Drop all measured state (tests, bench isolation)."""
    global _waterfalls
    with _lock:
        _programs.clear()
        _waterfalls = None
    _tls.step = None


# ------------------------------------------------------------- ledger
def append_ledger(row, path):
    """Append one JSON row to the append-only perf ledger (one line per
    bench run).  A single ``write`` of one line on an O_APPEND handle is
    atomic at these sizes; concurrent writers interleave whole lines."""
    line = json.dumps(row, sort_keys=True)
    with open(path, "a") as f:
        f.write(line + "\n")
    return path


def read_ledger(path, last=None):
    """Parse the ledger; corrupt lines are skipped (an interrupted
    writer must not poison the whole trajectory)."""
    rows = []
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue
    return rows if last is None else rows[-last:]


def _comparable(a, b):
    """Two ledger rows are gate-comparable when their stable context
    matches: same quick flag and same device kind."""
    fa, fb = a.get("fingerprint", {}), b.get("fingerprint", {})
    return (a.get("quick") == b.get("quick")
            and fa.get("device") == fb.get("device"))


def ledger_verdict(rows, throughput_drop_pct=20.0):
    """Regression verdict over the last two comparable ledger rows.

    Hard regressions (CPU-stable — CI gates on these):

    * a bench that produced a value before now records an error;
    * a program's ANALYTIC flops or hbm_bytes changed for the same
      (graph, mode) — the cost model itself drifted;
    * a previously-present transformer MFU field disappeared.

    Throughput/MFU drops beyond ``throughput_drop_pct`` are WARNINGS
    (wall-clock is not CPU-stable; on-chip they are the real signal).
    """
    out = {"verdict": "ok", "regressions": [], "warnings": [],
           "compared": None}
    if len(rows) < 2:
        out["note"] = "fewer than 2 ledger rows — nothing to compare"
        return out
    cur = rows[-1]
    prev = None
    for row in reversed(rows[:-1]):
        if _comparable(row, cur):
            prev = row
            break
    if prev is None:
        out["note"] = "no comparable prior row (device/quick differ)"
        return out
    out["compared"] = [prev.get("ts"), cur.get("ts")]
    pb, cb = prev.get("benches", {}), cur.get("benches", {})
    for name in sorted(set(pb) & set(cb)):
        was, now = pb[name], cb[name]
        if "value" in was and "error" in now:
            out["regressions"].append(
                "bench %s newly failing: %s" % (name, now["error"]))
            continue
        if "value" not in was or "value" not in now:
            continue
        if was.get("mfu_pct") is not None and now.get("mfu_pct") is None:
            out["regressions"].append(
                "bench %s lost its MFU field" % name)
        try:
            ratio = float(now["value"]) / float(was["value"])
        except (TypeError, ValueError, ZeroDivisionError):
            continue
        if ratio < 1.0 - throughput_drop_pct / 100.0:
            out["warnings"].append(
                "bench %s throughput %.3g -> %.3g (%.1f%% drop)"
                % (name, was["value"], now["value"], 100 * (1 - ratio)))
    pp = {(p["graph"], p["mode"]): p for p in prev.get("programs", [])}
    for p in cur.get("programs", []):
        old = pp.get((p["graph"], p["mode"]))
        if old is None:
            continue
        for field in ("flops", "hbm_bytes"):
            if old.get(field) != p.get(field):
                out["regressions"].append(
                    "program %s/%s analytic %s drift: %s -> %s"
                    % (p["graph"], p["mode"], field, old.get(field),
                       p.get(field)))
    if out["regressions"]:
        out["verdict"] = "regression"
    return out

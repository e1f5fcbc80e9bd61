#!/usr/bin/env python
"""Fast CPU smoke of collectives-backed sharded training (tier-1 CI
guard, ISSUE 20) — the mesh kvstore end-to-end over a REAL fake
cluster.

The parent spawns ``MXNET_MESH_PROCS`` (default 2) worker processes via
``tools/launch.py`` (jax.distributed + gloo, one virtual CPU device
each).  Every worker runs ``Module.fit`` with ``kvstore="mesh"`` on its
OWN data shard — the gradient exchange is bucketed in-program
collectives with ZeRO-1 optimizer sharding — and asserts the whole
contract from inside the job:

1. **Zero kvstore RPCs on the step path** — the ``kvstore.rpc`` counter
   (every PSClient round-trip lands there) stays at 0: there is no
   parameter server to talk to.
2. **Cross-rank parameter fingerprints identical each step** — a
   batch-end ``process_allgather`` of the full parameter vector must be
   BIT-exact across ranks every step (each rank sees different data;
   only the summed exchange keeps them in lockstep).  A second short
   fit on identical shards runs with the divergence sentinel armed at
   ``raise`` — the per-step fingerprints ride the mesh store's own
   allgather transport (no server) and must stay silent.  (The
   sentinel leg uses identical data because local grad norms/losses
   legitimately differ across shards — dist_trace docstring.)
3. **Resume bit-exact under ZeRO-sharded optimizer state** — every rank
   SIGTERMs itself mid-epoch-1 (symmetric, so collectives stay
   aligned), the preemption guard checkpoints (sharded momenta
   allgathered into the blob), and ``fit(resume=)`` must land on
   parameters BIT-identical to an uninterrupted run.
4. **Observability without a server** — ``dist_trace.current_rank()``
   equals the jax process index, and the waterfall rows are stamped
   ``collective`` (the kvstore segment is in-device exchange, not RPC).
5. **Clean teardown** — workers exit 0 with no leaked ``mxnet-``
   threads.

The multi-host CI leg: it drives the Module/kvstore training path
users actually run.

Usage: ``python tools/mesh_smoke.py [summary.json]`` (parent mode);
``--worker <outdir>`` is the internal child entry point.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPOCHS = 2
BATCH = 8
SAMPLES = 32
PREEMPT_AT = 5          # global batch index to SIGTERM at (epoch 1)


# --------------------------------------------------------------- worker
def _require(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _mlp():
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _rank_iter(rank):
    """Deterministic per-rank data shard: parity across ranks must come
    from the collective exchange, not from identical inputs.  The
    sentinel leg passes rank=None for an identical stream everywhere
    (local grad norms are only comparable across ranks then)."""
    import numpy as np

    import mxnet_tpu as mx

    rng = np.random.RandomState(100 + (rank or 0))
    X = rng.rand(SAMPLES, 6).astype(np.float32)
    y = (rng.rand(SAMPLES) * 4).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=BATCH, shuffle=False,
                             label_name="softmax_label")


def _fit(rank, num_epoch=EPOCHS, resume=None, batch_end_callback=None):
    import numpy as np

    import mxnet_tpu as mx

    np.random.seed(11)
    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(_rank_iter(rank), num_epoch=num_epoch, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1), ("momentum", 0.9)),
            initializer=mx.init.Uniform(0.3), kvstore="mesh",
            batch_end_callback=batch_end_callback, resume=resume)
    args, _ = mod.get_params()
    out = {k: v.asnumpy().copy() for k, v in args.items()}
    if mod._kvstore is not None:
        mod._kvstore.close()        # disarm the sentinel between legs
    return out


def _flat_params(params):
    import numpy as np

    return np.concatenate([np.asarray(
        params[k].asnumpy() if hasattr(params[k], "asnumpy")
        else params[k]).ravel()
        for k in sorted(params)]).astype(np.float32)


def worker_main(outdir):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1").strip()
    import signal
    import threading

    import numpy as np

    # wire the fake cluster BEFORE any jax computation runs (building
    # even one NDArray counts) — jax.distributed refuses to init after
    from mxnet_tpu.kvstore import _ensure_distributed

    _ensure_distributed()

    from jax.experimental import multihost_utils

    import mxnet_tpu as mx  # noqa: F401 - registers ops/io for _fit
    from mxnet_tpu.observability import dist_trace, metrics, perf
    from mxnet_tpu.resilience import PreemptedError

    rank = int(os.environ["MXTPU_WORKER_ID"])
    nprocs = int(os.environ["MXTPU_NUM_WORKERS"])

    # ---- leg 1+2+4: fit with per-step cross-rank fingerprints --------
    fingerprint_steps = [0]

    def check_fingerprints(param):
        mod = param.locals["self"]
        args, _ = mod.get_params()
        flat = _flat_params(args)
        allp = np.asarray(multihost_utils.process_allgather(flat))
        for r in range(nprocs):
            _require(
                np.array_equal(allp[r], allp[0]),
                "step %d: rank %d params diverged from rank 0 "
                "(max delta %g)" % (fingerprint_steps[0], r,
                                    float(np.abs(allp[r] - allp[0]).max())))
        fingerprint_steps[0] += 1

    base_rpc = metrics.get_value("kvstore.rpc") or 0
    params = _fit(rank, batch_end_callback=check_fingerprints)
    steps = fingerprint_steps[0]
    _require(steps == EPOCHS * SAMPLES // BATCH,
             "expected %d fingerprinted steps, got %d"
             % (EPOCHS * SAMPLES // BATCH, steps))
    rpc = (metrics.get_value("kvstore.rpc") or 0) - base_rpc
    _require(rpc == 0,
             "mesh step path must issue ZERO kvstore RPCs, counted %d"
             % rpc)
    _require(dist_trace.current_rank() == rank,
             "dist_trace rank %r != process index %d"
             % (dist_trace.current_rank(), rank))
    rows = perf.waterfalls()
    _require(rows and all(r.get("collective") for r in rows),
             "waterfall rows must be stamped collective: %r"
             % (rows[:2],))
    _require(all(r.get("rank") == rank for r in rows),
             "waterfall rows must carry this rank: %r" % (rows[:2],))

    # ---- leg 2b: divergence sentinel over the allgather transport ----
    # identical data on every rank, policy=raise: the per-step health
    # fingerprints meet on each rank's own tracker and must stay silent
    # (a false positive — or a real divergence — kills this fit)
    from mxnet_tpu.observability import health

    os.environ["MXNET_DIST_SENTINEL"] = "raise"
    health.set_policy("warn")
    try:
        sentinel_params = _fit(None, num_epoch=1)
    finally:
        os.environ["MXNET_DIST_SENTINEL"] = "off"
        health.set_policy("off")
    _require(np.isfinite(_flat_params(sentinel_params)).all(),
             "sentinel-leg fit produced non-finite params")

    # ---- leg 3: resume bit-exact under ZeRO-sharded states -----------
    straight = _fit(rank, num_epoch=EPOCHS + 1)
    ckpt_dir = os.path.join(outdir, "ckpt_rank%d" % rank)
    count = [0]

    def preempt(param):
        count[0] += 1
        if count[0] == PREEMPT_AT:      # same batch on EVERY rank
            os.kill(os.getpid(), signal.SIGTERM)

    try:
        _fit(rank, num_epoch=EPOCHS + 1, resume=ckpt_dir,
             batch_end_callback=preempt)
        raise AssertionError("preemption never fired")
    except PreemptedError:
        pass
    resumed = _fit(rank, num_epoch=EPOCHS + 1, resume=ckpt_dir)
    for k in straight:
        _require(np.array_equal(straight[k], resumed[k]),
                 "resume-with-sharded-states params differ at %r" % k)

    # ---- leg 5: teardown ---------------------------------------------
    leftovers = [t.name for t in threading.enumerate()
                 if t.name.startswith("mxnet-") and not t.daemon]
    _require(not leftovers, "worker %d leaked threads: %r"
             % (rank, leftovers))

    section = {
        "rank": rank, "steps": steps, "kvstore_rpcs": rpc,
        "param_norm": float(np.linalg.norm(_flat_params(params))),
        "resume_bit_exact": True,
        "collective_rows": len(rows),
    }
    tmp = os.path.join(outdir, "rank%d.json.tmp" % rank)
    with open(tmp, "w") as f:
        json.dump(section, f)
    os.replace(tmp, os.path.join(outdir, "rank%d.json" % rank))
    print("WORKER_OK rank=%d steps=%d" % (rank, steps))


# --------------------------------------------------------------- parent
def main(out_path=None):
    import tempfile

    try:
        from launch import launch_local
    except ImportError:
        from tools.launch import launch_local

    nprocs = int(os.environ.get("MXNET_MESH_PROCS", "2") or 2)
    outdir = tempfile.mkdtemp(prefix="mesh_smoke_")
    procs = launch_local(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker", outdir],
        env_extra={"MXNET_TELEMETRY": "1"})
    outs = []
    ok = True
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=600)
        outs.append(out.decode())
        if p.returncode != 0 or "WORKER_OK" not in outs[-1]:
            ok = False
    if not ok:
        for r, text in enumerate(outs):
            sys.stdout.write("---- worker %d (rc=%s) ----\n%s\n"
                             % (r, procs[r].returncode, text))
        raise AssertionError("mesh smoke worker(s) failed")

    sections = []
    for r in range(nprocs):
        with open(os.path.join(outdir, "rank%d.json" % r)) as f:
            sections.append(json.load(f))
    norms = {s["param_norm"] for s in sections}
    _require(len(norms) == 1,
             "final param norms differ across ranks: %r" % (norms,))
    summary = {
        "workers": nprocs,
        "steps": sections[0]["steps"],
        "kvstore_rpcs": sum(s["kvstore_rpcs"] for s in sections),
        "resume_bit_exact": all(s["resume_bit_exact"] for s in sections),
        "collective_rows": sum(s["collective_rows"] for s in sections),
        "ok": True,
    }
    line = json.dumps(summary, sort_keys=True)
    print(line)
    print("MESH_SMOKE_OK workers=%d steps=%d rpcs=%d"
          % (nprocs, summary["steps"], summary["kvstore_rpcs"]))
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        worker_main(sys.argv[2])
    else:
        main(sys.argv[1] if len(sys.argv) > 1 else None)

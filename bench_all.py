#!/usr/bin/env python
"""All-config benchmark sidecar: one JSON artifact covering every
BASELINE.json config plus the flash-attention claim.

Configs (BASELINE.json "configs" + VERDICT r3 item 3):
  1. MNIST LeNet training (Module API)          — samples/sec
  2. ResNet-50 train bs32 (headline, bench.py protocol) — img/sec
  3. Gluon HybridBlock ResNet-18 train step     — img/sec
  4. LSTM PTB training step (2x200, bs32, T=35) — samples/sec
  5. SSD-300 training step (VGG-reduced)        — img/sec
  +  ResNet-50 inference bs32 (benchmark_score protocol, P100 713.17)
  +  flash vs dense attention fwd at T=4096     — speedup ratio
  +  flash vs dense attention TRAIN (fwd+bwd, Pallas recompute backward
     vs dense autodiff) at T in {1024..8192}    — speedup + residual MB
  +  transformer-LM train step at T=2048 and T=4096 — tokens/sec, MFU
  +  serving engine vs naive per-request loop under Poisson arrivals
     (resnet50 inference)                       — throughput ratio + p50/p99

Writes BENCH_ALL.json (repo root by default) and prints it. Each entry is
measured independently and failures are recorded, not fatal, so one slow
compile cannot sink the artifact. Set BENCH_QUICK=1 for a fast smoke pass.

Standalone gates/modes: --lint-clean (graftlint vs baseline),
--health-overhead (warn-mode <=2%/step), --resilience-overhead
(faults-disabled injection points + deadline checks <1%/request;
docs/resilience.md), --obs-overhead (request tracing <1%/request,
on and sampled-out; docs/observability.md), --ts-overhead (time-series
sampler + fleet scrape duty cycle <1% of interval; docs/observability.md),
--perf-overhead (roofline
attribution + step waterfall <1%/step on stable quantities;
docs/perf_observability.md), --autotune (tuned-vs-default on the
autotuner's knob families + the warm-cache <1%/step gate;
docs/autotune.md), --dist-train (PS push/pull vs fused collective vs
bucketed-overlap step walls on a fake cluster + ZeRO-1 sharding
witnesses; docs/distributed.md), --ingest-ledger (drain ledger
residuals + tune-cache measurements into the learned cost model's
sample store, report the ranking gate; docs/autotune.md).

Every full run also appends one row to BENCH_LEDGER.jsonl (fingerprint,
per-bench throughput + MFU, per-program predicted-vs-measured
residuals) — the perf trajectory tools/perf_report.py --ledger diffs.
"""
import atexit
import functools
import itertools
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

QUICK = os.environ.get("BENCH_QUICK", "") == "1"

# published reference numbers (BASELINE.md)
P100_RESNET50_TRAIN = 181.53   # docs/faq/perf.md:180-187
P100_RESNET50_INFER = 713.17   # docs/faq/perf.md:138


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def _bench_ctx(device_id=0):
    """Where the configs run: ``mx.tpu()``, which raises without a chip —
    a full run never measures the host unnoticed. Only the rehearsal mode
    chosen with BENCH_QUICK=1 (recorded as ``"quick": true``) uses
    ``mx.gpu()``, the harness alias that resolves to the accelerator if
    there is one and to a CPU device otherwise."""
    import mxnet_tpu as mx

    return mx.gpu(device_id) if QUICK else mx.tpu(device_id)


def bench_resnet50_train():
    import jax

    import bench

    iters = 20 if QUICK else 200
    devices = [_bench_ctx(i).jax_device()
               for i in range(jax.local_device_count())]
    return {"value": round(bench._bench_one(
        devices, 32, "NHWC", np.dtype("bfloat16"), iters), 2),
        "unit": "images/sec", "protocol": "bs32 bf16 NHWC fused train step",
        "vs_baseline_p100": None}


def bench_resnet50_infer():
    """benchmark_score protocol: repeated executor forward, async queue
    drained once at the end (reference: benchmark_score.py)."""
    import mxnet_tpu as mx

    size = 64 if QUICK else 224
    batches = 5 if QUICK else 50
    sym = mx.models.get_resnet(num_classes=1000, num_layers=50,
                               image_shape=(3, size, size), layout="NHWC")
    shape = (32, size, size, 3) if size != 64 else (32, size, size, 3)
    ctx = _bench_ctx()
    ex = sym.simple_bind(ctx, data=shape, grad_req="null")
    rng = np.random.RandomState(0)
    for k, v in ex.arg_dict.items():
        if k != "data":
            v[:] = (rng.randn(*v.shape) * 0.01).astype(np.float32)
    ex.arg_dict["data"][:] = rng.rand(*shape).astype(np.float32)
    ex.forward()
    ex.outputs[0].asnumpy()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(batches):
        ex.forward()
    ex.outputs[0].asnumpy()
    dt = time.perf_counter() - t0
    ips = 32 * batches / dt
    return {"value": round(ips, 2), "unit": "images/sec",
            "protocol": "bs32 fp32 executor forward x%d" % batches,
            "vs_baseline_p100": round(ips / P100_RESNET50_INFER, 3)}


def bench_lenet_mnist():
    """Module.fit protocol on synthetic MNIST-shaped data."""
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    c1 = mx.sym.Activation(mx.sym.Convolution(
        data, kernel=(5, 5), num_filter=20), act_type="tanh")
    p1 = mx.sym.Pooling(c1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = mx.sym.Activation(mx.sym.Convolution(
        p1, kernel=(5, 5), num_filter=50), act_type="tanh")
    p2 = mx.sym.Pooling(c2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    f1 = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.Flatten(p2), num_hidden=500), act_type="tanh")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(f1, num_hidden=10),
                               name="softmax")

    bs = 128
    steps = 10 if QUICK else 100
    mod = mx.mod.Module(net, context=_bench_ctx())
    mod.bind(data_shapes=[("data", (bs, 1, 28, 28))],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(bs, 1, 28, 28).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 10, bs).astype(np.float32))])
    for _ in range(3):  # compile + warm
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    dt = time.perf_counter() - t0
    return {"value": round(bs * steps / dt, 1), "unit": "samples/sec",
            "protocol": "Module fwd+bwd+update, bs128"}


def bench_gluon_resnet():
    """Gluon path: Trainer.compile_step — the whole train step (fwd+bwd+
    optimizer) as ONE XLA program, the TPU-native Gluon training surface.
    An eager-tape sub-measurement is reported alongside for honesty about
    the imperative path's per-dispatch cost."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    size = 32 if QUICK else 224
    bs = 4 if QUICK else 32
    steps = 3 if QUICK else 30
    # reference-style device placement: mx.gpu() is the accelerator (the
    # TPU chip on this build); without it everything computes on host
    ctx = _bench_ctx()
    net = resnet18_v1()
    net.initialize(ctx=ctx)
    net.hybridize()
    x = mx.nd.array(np.random.rand(bs, 3, size, size).astype(np.float32),
                    ctx=ctx)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    y = mx.nd.array(np.random.randint(0, 1000, bs).astype(np.float32),
                    ctx=ctx)
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05}, kvstore="local")

    step = trainer.compile_step(net, loss_fn)
    step(x, y).asnumpy()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    assert step.compile_count == 1, "compile_step recompiled mid-bench"

    # eager-tape comparison point (few steps — it pays per-node dispatch)
    eager_steps = 1 if QUICK else 3
    def eager_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(bs)
        return loss

    eager_step().asnumpy()  # warm
    t0e = time.perf_counter()
    for _ in range(eager_steps):
        loss_e = eager_step()
    loss_e.asnumpy()
    eager_rate = bs * eager_steps / (time.perf_counter() - t0e)

    return {"value": round(bs * steps / dt, 1), "unit": "images/sec",
            "protocol": ("hybridized resnet18_v1 bs%d %dx%d, "
                         "Trainer.compile_step: fwd+bwd+update as ONE "
                         "XLA program" % (bs, size, size)),
            "eager_tape_img_per_sec": round(eager_rate, 1),
            "note": ("the eager tape pays one dispatch per recorded "
                     "node; compile_step is the TPU-native step surface")}


def bench_lstm_ptb():
    """PTB-style LSTM LM step: 2 layers x 200 hidden, bs32, T=35
    (example/rnn/lstm_bucketing.py protocol, BASELINE config #4)."""
    import mxnet_tpu as mx

    bs, seq_len, hidden, layers, vocab = 32, 35, 200, 2, 10000
    if QUICK:
        bs, seq_len, vocab = 8, 10, 500
    steps = 5 if QUICK else 60

    stack = mx.rnn.FusedRNNCell(hidden, num_layers=layers, mode="lstm")
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=hidden,
                             name="embed")
    outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
    lab = mx.sym.Reshape(label, shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, lab, name="softmax")

    mod = mx.mod.Module(net, context=_bench_ctx())
    mod.bind(data_shapes=[("data", (bs, seq_len))],
             label_shapes=[("softmax_label", (bs, seq_len))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randint(0, vocab, (bs, seq_len))
                          .astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, vocab, (bs, seq_len))
                           .astype(np.float32))])
    for _ in range(2):
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    dt = time.perf_counter() - t0
    return {"value": round(bs * steps / dt, 1), "unit": "samples/sec",
            "protocol": "LSTM 2x200 T=%d bs%d fused-RNN train step"
                        % (seq_len, bs)}


def bench_ssd300():
    """SSD-300 training step over the MultiBox pipeline (config #5)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.ssd import get_ssd

    size, bs = (64, 4) if QUICK else (300, 8)
    steps = 3 if QUICK else 20

    if QUICK:
        def features(data):
            x = data
            outs = []
            for i, nf in enumerate((16, 32)):
                x = mx.sym.Convolution(x, kernel=(3, 3), stride=(2, 2),
                                       pad=(1, 1), num_filter=nf,
                                       name="f%d" % i)
                x = mx.sym.Activation(x, act_type="relu")
                outs.append(x)
            return outs
        net = get_ssd(num_classes=20, mode="train", features=features,
                      sizes=[[0.3], [0.6]], ratios=[[1], [1]])
    else:
        net = get_ssd(num_classes=20, mode="train")

    ex = net.simple_bind(_bench_ctx(),
                         data=(bs, 3, size, size), label=(bs, 3, 5),
                         grad_req="write")
    rng = np.random.RandomState(0)
    for k, v in ex.arg_dict.items():
        if k not in ("data", "label"):
            v[:] = (rng.randn(*v.shape) * 0.01).astype(np.float32)
    ex.arg_dict["data"][:] = rng.rand(bs, 3, size, size).astype(np.float32)
    lab = -np.ones((bs, 3, 5), np.float32)
    lab[:, 0] = [0, 0.3, 0.3, 0.7, 0.7]
    ex.arg_dict["label"][:] = lab
    ex.forward(is_train=True)
    ex.backward()
    ex.outputs[0].asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        ex.forward(is_train=True)
        ex.backward()
    ex.outputs[0].asnumpy()
    dt = time.perf_counter() - t0
    return {"value": round(bs * steps / dt, 2), "unit": "images/sec",
            "protocol": "SSD-%d VGG-reduced fwd+bwd bs%d" % (size, bs)}


def bench_flash_attention():
    """Flash (Pallas) vs dense XLA attention at T=4096 — the README claim."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.flash_attention import flash_attention

    b, h, t, d = 1, 8, (512 if QUICK else 4096), 64
    q = jnp.asarray(np.random.randn(b, h, t, d), jnp.bfloat16)
    k = jnp.asarray(np.random.randn(b, h, t, d), jnp.bfloat16)
    v = jnp.asarray(np.random.randn(b, h, t, d), jnp.bfloat16)

    def dense(q, k, v):
        # causal-masked, like the flash kernel — an unmasked dense
        # baseline would be an apples-to-oranges comparison
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
            / np.sqrt(d)
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), v)

    def timeit(attn, n=100):
        # N dependent iterations inside ONE program, fenced once: a host
        # loop would time dispatches, not the kernel
        @jax.jit
        def run(q, k, v):
            def body(carry, _):
                return attn(carry, k, v), None
            out, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(out.astype(jnp.float32))

        float(run(q, k, v))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(run(q, k, v))
            best = min(best, time.perf_counter() - t0)
        return best / n

    td = timeit(dense)
    tf = timeit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    return {"value": round(td / tf, 2), "unit": "x speedup vs dense XLA",
            "protocol": "causal attention b1 h8 T=%d d64 bf16" % t,
            "dense_ms": round(td * 1e3, 2), "flash_ms": round(tf * 1e3, 2)}


def bench_flash_attention_train():
    """Training-mode microbench: fwd+bwd through the flash kernel (tiled
    recompute Pallas backward, residuals O(T) per head) vs XLA autodiff
    of the dense formula (T x T score matrix materialized in the
    backward), causal, across sequence lengths. Also records the actual
    vjp residual footprint of each path — the memory claim, measured."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.flash_attention import (flash_attention,
                                                    _dense_with_lse)

    b, h, d = 1, 8, 64
    seq_lens = (512,) if QUICK else (1024, 2048, 4096, 8192)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def dense_loss(q, k, v):
        out, _ = _dense_with_lse(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    def residual_bytes(loss, q, k, v):
        # the real vjp residual set, via abstract evaluation — nothing
        # executes, so measuring the dense path at T=8192 (10+ GB of
        # residuals) cannot itself OOM the chip
        vjp_fn = jax.eval_shape(
            lambda q, k, v: jax.vjp(loss, q, k, v)[1], q, k, v)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(vjp_fn)
                   if hasattr(x, "dtype"))

    def timeit(loss, q, k, v, n):
        grad = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def run(q, k, v):
            # chain iterations through dq (keeps every fwd+bwd live and
            # dependent — same one-program protocol as the fwd bench);
            # the 1e-30 factor keeps dk/dv from being dead code
            def body(carry, _):
                dq, dk, dv = grad(carry, k, v)
                return dq + 1e-30 * (dk + dv), None
            out, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(out.astype(jnp.float32))

        float(run(q, k, v))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(run(q, k, v))
            best = min(best, time.perf_counter() - t0)
        return best / n

    rng = np.random.RandomState(0)
    per_t = {}
    best = None
    for t in seq_lens:
        q = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        entry = {
            "flash_residual_mb": round(
                residual_bytes(flash_loss, q, k, v) / 2**20, 1),
            "dense_residual_mb": round(
                residual_bytes(dense_loss, q, k, v) / 2**20, 1),
        }
        per_t["T%d" % t] = entry
        n = max(8, (204800 if not QUICK else 4096) // t)
        try:
            # flash first: if the DENSE side OOMs at long T (its T x T
            # backward is exactly what this kernel exists to avoid),
            # keep the flash timing and record the failure per-T
            # instead of sinking the whole entry
            tf = timeit(flash_loss, q, k, v, n)
            entry["flash_ms"] = round(tf * 1e3, 2)
            td = timeit(dense_loss, q, k, v, n)
            entry["dense_ms"] = round(td * 1e3, 2)
            entry["speedup"] = round(td / tf, 2)
            best = (t, entry["speedup"])
        except Exception as err:
            entry["error"] = repr(err)
    if best is None:
        raise RuntimeError("no T completed: %r" % per_t)
    return {"value": best[1],
            "unit": "x fwd+bwd speedup vs dense autodiff (T=%d)" % best[0],
            "protocol": "causal attention grad(q,k,v) b1 h8 d64 bf16",
            "per_T": per_t}


def bench_transformer_lm(B=None, T=None):
    """Beyond-reference config: causal-LM transformer train step (flash
    attention fwd AND bwd as Pallas kernels, whole step one XLA program)
    — the long-context story's single-chip anchor."""
    import jax

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.transformer import TransformerParallel

    if B is None:
        B, T = (2, 256) if QUICK else (8, 2048)
    d_model, n_layers = (64, 2) if QUICK else (512, 8)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tp = TransformerParallel(mesh, vocab=32768, d_model=d_model,
                             n_heads=8, n_layers=n_layers,
                             d_ff=4 * d_model, n_experts=1,
                             dtype=np.dtype("bfloat16"))
    params = tp.init(0)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 32768, (B, T)).astype(np.int32)
    tok, tgt = tp.shard_batch(tok, np.roll(tok, -1, axis=1))
    step = tp.step_fn(lr=0.01)
    params, loss = step(params, tok, tgt)
    float(loss)  # compile + warm, D2H fence
    steps = 3 if QUICK else 30
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, tok, tgt)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    n_par = sum(v.size for v in jax.tree_util.tree_leaves(params))
    # 6ND FLOP basis over the spec-sheet ceiling — the ONE table
    # (autotune.cost_model.CEILINGS) every MFU field cites, so this
    # number and the perf ledger's transformer MFU can never drift
    from mxnet_tpu.autotune.cost_model import SPEC_MATMUL_TF

    return {"value": round(B * T / dt), "unit": "tokens/sec",
            "protocol": ("%dM-param causal LM, T=%d bs%d bf16, flash "
                         "attention, fwd+bwd+sgd one program"
                         % (round(n_par / 1e6), T, B)),
            "ms_per_step": round(dt * 1e3, 2),
            "params": int(n_par),
            "mfu_spec": round(6 * n_par * B * T / dt
                              / (SPEC_MATMUL_TF * 1e12), 4)}


def bench_serving_resnet50():
    """Serving engine vs the naive per-request executor-forward loop,
    same Poisson arrival schedule for both (ISSUE 5 acceptance: >=3x
    throughput at equal-or-better p99). The offered rate is set to ~4x
    the measured per-request capacity, so the naive loop saturates while
    the engine absorbs the backlog by coalescing into batch buckets."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving import InferenceServer, ServingConfig

    size, layers = (32, 18) if QUICK else (224, 50)
    buckets = (1, 2, 4) if QUICK else (1, 2, 4, 8, 16, 32)
    n_req = 24 if QUICK else 256
    sym = mx.models.get_resnet(num_classes=1000, num_layers=layers,
                               image_shape=(3, size, size), layout="NHWC")
    ctx = _bench_ctx()
    rng = np.random.RandomState(0)
    ex = sym.simple_bind(ctx, data=(1, size, size, 3), grad_req="null")
    for k, v in ex.arg_dict.items():
        if k != "data":
            v[:] = (rng.randn(*v.shape) * 0.01).astype(np.float32)
    img = rng.rand(size, size, 3).astype(np.float32)
    ex.arg_dict["data"][:] = img[None]
    ex.forward()
    ex.outputs[0].asnumpy()  # compile + warm

    # per-request capacity of the naive loop -> offered Poisson rate.
    # The measured ratio is capped by this overload factor (the engine
    # cannot beat the arrival rate once it keeps up), so the full run
    # offers 8x to leave the >=3x acceptance bar real headroom.
    t0 = time.perf_counter()
    probe = 3 if QUICK else 10
    for _ in range(probe):
        ex.forward()
        ex.outputs[0].asnumpy()
    t1 = (time.perf_counter() - t0) / probe
    overload = 4.0 if QUICK else 8.0
    arrivals = np.cumsum(rng.exponential(t1 / overload, n_req))

    def percentiles(lat):
        return (round(float(np.percentile(lat, 50)) * 1e3, 2),
                round(float(np.percentile(lat, 99)) * 1e3, 2))

    def run_baseline():
        lat = []
        start = time.perf_counter()
        for a in arrivals:
            now = time.perf_counter() - start
            if now < a:
                time.sleep(a - now)
            ex.forward()
            ex.outputs[0].asnumpy()
            lat.append(time.perf_counter() - start - a)
        wall = (time.perf_counter() - start) - arrivals[0]
        return lat, n_req / wall

    def run_serving():
        arg_params = {k: v for k, v in ex.arg_dict.items() if k != "data"}
        server = InferenceServer(
            sym, arg_params, aux_params=dict(ex.aux_dict),
            data_shapes=[("data", (1, size, size, 3))],
            config=ServingConfig(buckets=buckets, max_wait_ms=5))
        try:
            server.warmup()
            lat = [None] * n_req
            start = time.perf_counter()

            def make_cb(i, a):
                def cb(_fut):
                    lat[i] = time.perf_counter() - start - a
                return cb

            futs = []
            for i, a in enumerate(arrivals):
                now = time.perf_counter() - start
                if now < a:
                    time.sleep(a - now)
                fut = server.submit(img)
                fut.add_done_callback(make_cb(i, a))
                futs.append(fut)
            for f in futs:
                f.result()
            wall = (time.perf_counter() - start) - arrivals[0]
            # result() waiters wake BEFORE done-callbacks run, so the
            # last lat[i] writes can still be in flight — settle them
            deadline = time.perf_counter() + 10.0
            while any(v is None for v in lat):
                if time.perf_counter() > deadline:
                    raise RuntimeError("latency callbacks never settled")
                time.sleep(0.001)
            return lat, n_req / wall, server.get_stats()
        finally:
            server.stop()

    base_lat, base_rps = run_baseline()
    srv_lat, srv_rps, stats = run_serving()
    b50, b99 = percentiles(base_lat)
    s50, s99 = percentiles(srv_lat)
    return {"value": round(srv_rps / base_rps, 2),
            "unit": "x throughput vs per-request executor loop",
            "protocol": ("resnet%d %dx%d NHWC bs1 requests, Poisson "
                         "arrivals at %gx naive capacity, %d requests, "
                         "buckets %s" % (layers, size, size, overload,
                                         n_req, list(buckets))),
            "baseline_rps": round(base_rps, 1),
            "serving_rps": round(srv_rps, 1),
            "baseline_p50_ms": b50, "baseline_p99_ms": b99,
            "serving_p50_ms": s50, "serving_p99_ms": s99,
            "p99_ok": s99 <= b99,
            "batches": stats["batches"],
            "mean_batch_rows": round(stats["rows_real"]
                                     / max(1, stats["batches"]), 2),
            "bucket_programs": stats["bucket_programs"]}


def bench_generation_lm():
    """Continuous-batching generation vs sequential per-request decode,
    same Poisson arrival schedule for both (ISSUE 7 acceptance:
    continuous batching beats sequential on tokens/s with no per-token
    latency regression at p99). The sequential baseline serves each
    request to completion before touching the next — the decode-path
    analog of the naive per-request serving loop — while the continuous
    generator admits arrivals mid-flight between decode steps."""
    import threading

    import jax

    from mxnet_tpu.parallel.transformer import TransformerParallel
    from mxnet_tpu.serving.generation import (GenerationConfig, Generator,
                                              SamplingParams)

    if QUICK:
        model_kw = dict(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, n_experts=2)
        max_batch, max_seq, n_req, max_new = 4, 64, 12, 8
    else:
        model_kw = dict(vocab=256, d_model=128, n_heads=8, n_layers=4,
                        d_ff=256, n_experts=2)
        max_batch, max_seq, n_req, max_new = 8, 256, 48, 24
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1),
                             ("dp",))
    model = TransformerParallel(mesh, **model_kw)
    params = model.init(seed=0)
    cfg = dict(max_batch=max_batch, max_seq=max_seq)

    rng = np.random.RandomState(0)
    requests = []
    for _ in range(n_req):
        plen = int(rng.randint(2, max_seq - max_new))
        prompt = [int(t) for t in rng.randint(1, model_kw["vocab"],
                                              size=plen)]
        requests.append((prompt, SamplingParams(max_new_tokens=max_new)))

    gen = Generator(model, params, GenerationConfig(**cfg))
    gen.warmup()
    # per-request capacity of sequential decode -> offered Poisson rate
    t0 = time.perf_counter()
    probe = 2 if QUICK else 4
    for p, sp in requests[:probe]:
        gen.generate(p, sp, timeout=600)
    t_req = (time.perf_counter() - t0) / probe
    overload = 2.0 if QUICK else 3.0
    arrivals = np.cumsum(rng.exponential(t_req / overload, n_req))

    def consume(handle, arrival, start, out, idx):
        stream = handle.stream(timeout=600)
        try:
            first = next(stream)
        except StopIteration:
            first = None
        t_first = time.perf_counter() - start
        n = 1 if first is not None else 0
        for _ in stream:
            n += 1
        t_done = time.perf_counter() - start
        # per-token latency is the normalized kind (arrival -> done,
        # over tokens): it charges queueing to the system, which is the
        # number a user of an overloaded endpoint experiences; the
        # decode-only inter-token cadence is reported separately
        out[idx] = (t_first - arrival,
                    (t_done - arrival) / max(1, n),
                    (t_done - t_first) / max(1, n - 1), n)

    def run(sequential):
        g = Generator(model, params, GenerationConfig(**cfg))
        g.warmup()
        try:
            out = [None] * n_req
            threads = []
            start = time.perf_counter()
            for i, (a, (p, sp)) in enumerate(zip(arrivals, requests)):
                now = time.perf_counter() - start
                if now < a:
                    time.sleep(a - now)
                h = g.submit(p, sp)
                if sequential:
                    consume(h, a, start, out, i)  # serve to completion
                else:
                    t = threading.Thread(target=consume,
                                         args=(h, a, start, out, i))
                    t.start()
                    threads.append(t)
            for t in threads:
                t.join(600)
            wall = (time.perf_counter() - start) - arrivals[0]
            assert all(v is not None for v in out)
            tokens = sum(v[3] for v in out)
            ttft = [v[0] * 1e3 for v in out]
            per_tok = [v[1] * 1e3 for v in out]
            itl = [v[2] * 1e3 for v in out]
            pct = lambda xs, p: round(float(np.percentile(xs, p)), 2)  # noqa: E731
            return {"tokens_per_s": round(tokens / wall, 1),
                    "ttft_p50_ms": pct(ttft, 50),
                    "ttft_p99_ms": pct(ttft, 99),
                    "per_token_p50_ms": pct(per_tok, 50),
                    "per_token_p99_ms": pct(per_tok, 99),
                    "inter_token_p50_ms": pct(itl, 50),
                    "inter_token_p99_ms": pct(itl, 99)}
        finally:
            g.stop()

    gen.stop()
    seq = run(sequential=True)
    cont = run(sequential=False)
    return {"value": round(cont["tokens_per_s"] / seq["tokens_per_s"], 2),
            "unit": "x tokens/s vs sequential per-request decode",
            "protocol": ("causal LM %s, %d requests, Poisson arrivals at "
                         "%gx sequential capacity, max_new=%d, "
                         "max_batch=%d"
                         % (model_kw, n_req, overload, max_new,
                            max_batch)),
            "sequential": seq, "continuous": cont,
            "per_token_p99_ok": (cont["per_token_p99_ms"]
                                 <= seq["per_token_p99_ms"] * 1.05)}


def bench_generation_speculative():
    """--generation-speculative: speculative decoding (ISSUE 16) on a
    high-acceptance workload — the regime the optimization exists for.

    A tiny LM is first TRAINED to memorize a cyclic token stream, so its
    greedy continuation of any in-cycle prompt reproduces the cycle and
    the n-gram prompt-lookup proposer predicts it almost perfectly
    (accept_rate ~= 1, the templated/copy-heavy serving regime). The
    same Poisson arrival schedule then runs three arms: sequential
    per-request decode (the PR 7 baseline), continuous batching
    (non-speculative), and continuous batching + speculation. Hard gate:
    speculation must clear >= 1.3x the non-speculative continuous
    tokens/s with no normalized inter-token p99 regression past 1.05x;
    acceptance rate and the tokens-committed-per-verify histogram ride
    into BENCH_ALL.json under "generation_speculative" plus one ledger
    row. CPU QUICK numbers; the on-chip pass rides the TPU bench run."""
    import threading

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import metrics as M
    from mxnet_tpu.parallel.transformer import TransformerParallel
    from mxnet_tpu.serving.generation import (GenerationConfig, Generator,
                                              SamplingParams)

    if QUICK:
        model_kw = dict(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, n_experts=2)
        max_batch, max_seq, n_req, max_new = 4, 64, 12, 24
        train_T, train_B, train_steps = 32, 8, 400
    else:
        model_kw = dict(vocab=256, d_model=128, n_heads=8, n_layers=4,
                        d_ff=256, n_experts=2)
        max_batch, max_seq, n_req, max_new = 8, 256, 32, 48
        train_T, train_B, train_steps = 64, 16, 600
    spec_k = 4
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1),
                             ("dp",))
    model = TransformerParallel(mesh, **model_kw)
    params = model.init(seed=0)
    cfg = dict(max_batch=max_batch, max_seq=max_seq)

    # ---- memorize a cyclic stream: the high-acceptance workload -------
    rng = np.random.RandomState(0)
    period = 8
    pattern = rng.randint(1, model_kw["vocab"], size=period)
    stream = np.tile(pattern, train_T // period + 2)
    batch = np.stack([stream[ph:ph + train_T + 1]
                      for ph in rng.randint(0, period, size=train_B)])
    tokens = jnp.asarray(batch[:, :-1], jnp.int32)
    targets = jnp.asarray(batch[:, 1:], jnp.int32)
    step = model.step_fn(lr=0.3)
    loss = float("inf")
    for i in range(train_steps):
        params, loss = step(params, tokens, targets)
        if i % 25 == 24 and float(loss) < 0.02:
            break
    final_loss = float(loss)

    rng = np.random.RandomState(1)
    requests = []
    for _ in range(n_req):
        plen = int(rng.randint(2 * period, max_seq - max_new))
        prompt = [int(t) for t in np.tile(pattern, plen // period + 1)
                  [:plen]]
        requests.append((prompt, SamplingParams(max_new_tokens=max_new)))

    # probe per-request capacity of sequential decode -> Poisson rate
    gen = Generator(model, params, GenerationConfig(**cfg))
    gen.warmup()
    t0 = time.perf_counter()
    probe = 2 if QUICK else 4
    for p, sp in requests[:probe]:
        gen.generate(p, sp, timeout=600)
    t_req = (time.perf_counter() - t0) / probe
    gen.stop()
    # saturating offered load: the decode loop (not arrival gaps) must
    # dominate the wall clock, or the arrival-limited tail dilutes the
    # throughput contrast this arm exists to measure
    overload = 4.0
    arrivals = np.cumsum(rng.exponential(t_req / overload, n_req))

    def consume(handle, arrival, start, out, idx):
        stream = handle.stream(timeout=600)
        try:
            first = next(stream)
        except StopIteration:
            first = None
        t_first = time.perf_counter() - start
        n = 1 if first is not None else 0
        for _ in stream:
            n += 1
        t_done = time.perf_counter() - start
        out[idx] = (t_first - arrival,
                    (t_done - arrival) / max(1, n),
                    (t_done - t_first) / max(1, n - 1), n)

    def run(sequential=False, spec=0):
        g = Generator(model, params,
                      GenerationConfig(spec_k=spec, **cfg))
        g.warmup()
        try:
            out = [None] * n_req
            threads = []
            start = time.perf_counter()
            for i, (a, (p, sp)) in enumerate(zip(arrivals, requests)):
                now = time.perf_counter() - start
                if now < a:
                    time.sleep(a - now)
                h = g.submit(p, sp)
                if sequential:
                    consume(h, a, start, out, i)
                else:
                    t = threading.Thread(target=consume,
                                         args=(h, a, start, out, i))
                    t.start()
                    threads.append(t)
            for t in threads:
                t.join(600)
            wall = (time.perf_counter() - start) - arrivals[0]
            assert all(v is not None for v in out)
            tokens = sum(v[3] for v in out)
            ttft = [v[0] * 1e3 for v in out]
            per_tok = [v[1] * 1e3 for v in out]
            itl = [v[2] * 1e3 for v in out]
            pct = lambda xs, p: round(float(np.percentile(xs, p)), 2)  # noqa: E731
            res = {"tokens_per_s": round(tokens / wall, 1),
                   "ttft_p50_ms": pct(ttft, 50),
                   "ttft_p99_ms": pct(ttft, 99),
                   "per_token_p50_ms": pct(per_tok, 50),
                   "per_token_p99_ms": pct(per_tok, 99),
                   "inter_token_p50_ms": pct(itl, 50),
                   "inter_token_p99_ms": pct(itl, 99)}
            return res, g.get_stats()["speculative"]
        finally:
            g.stop()

    # tokens-per-verify lands in an integer-bucketed histogram: register
    # it BEFORE the engine's first observe so these buckets win over the
    # latency defaults
    obs.set_enabled(True)
    obs.reset_metrics()
    tpv = M.histogram(
        "generation.spec_tokens_per_verify",
        buckets=tuple(range(1, spec_k + 2)),
        help="tokens committed per slot per batched-verify call "
             "(1 = no draft survived, k+1 = all accepted + bonus)")

    seq, _ = run(sequential=True)
    cont, _ = run()
    spec, spec_stats = run(spec=spec_k)
    tpv_hist = dict(zip([str(b) for b in tpv.buckets] + ["+Inf"],
                        tpv._counts))
    obs.set_enabled(False)

    speedup = round(spec["tokens_per_s"] / cont["tokens_per_s"], 2)
    results = {
        "value": speedup,
        "unit": "x tokens/s vs non-speculative continuous batching",
        "protocol": ("causal LM %s trained %d steps to loss %.4f on a "
                     "period-%d cyclic stream, %d greedy requests, "
                     "Poisson arrivals at %gx sequential capacity, "
                     "max_new=%d, spec_k=%d n-gram proposer"
                     % (model_kw, train_steps, final_loss, period,
                        n_req, overload, max_new, spec_k)),
        "sequential": seq, "continuous": cont, "speculative": spec,
        "vs_sequential": round(spec["tokens_per_s"]
                               / seq["tokens_per_s"], 2),
        "accept_rate": spec_stats["accept_rate"],
        "proposed": spec_stats["proposed"],
        "accepted": spec_stats["accepted"],
        "verify_steps": spec_stats["steps"],
        "tokens_per_verify_hist": tpv_hist,
        "inter_token_p99_ok": (spec["inter_token_p99_ms"]
                               <= cont["inter_token_p99_ms"] * 1.05),
    }

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["generation_speculative"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    try:
        append_perf_ledger({"configs": {"generation_speculative": {
            "value": speedup,
            "unit": results["unit"]}}})
    except Exception:
        traceback.print_exc()
    print(json.dumps({"generation_speculative": results}))
    if speedup < 1.3:
        raise SystemExit(
            "bench_all --generation-speculative: %.2fx tokens/s vs "
            "continuous batching misses the 1.3x gate (accept_rate "
            "%r)" % (speedup, spec_stats["accept_rate"]))
    print("[bench_all] generation_speculative gate passed: %.2fx "
          "tokens/s vs continuous (%.2fx vs sequential), accept_rate "
          "%s, %s tokens/verify histogram"
          % (speedup, results["vs_sequential"],
             spec_stats["accept_rate"], tpv_hist), file=sys.stderr)
    return results


def bench_control():
    """--control: serving control plane (ISSUE 14) — the radix-tree
    prefix cache on a shared-prefix Poisson workload (TTFT cold-cache vs
    warm-cache, prefill tokens skipped, pages shared/saved) plus an SLO
    scheduling witness: with every decode slot busy, a queued
    interactive request must overtake queued batch requests WITHOUT
    starving them. Hard gates (CPU-stable): warm-pass hit rate > 0,
    warm TTFT p50 < cold TTFT p50, the overtake, batch completion, and
    zero leaked pages/refcounts after drain. Merges a "control" section
    into BENCH_ALL.json and appends a ledger row (ISSUE 13)."""
    import threading
    import time as _time

    import jax

    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import metrics as M
    from mxnet_tpu.parallel.transformer import TransformerParallel
    from mxnet_tpu.serving.generation import (GenerationConfig, Generator,
                                              SamplingParams)

    obs.set_enabled(True)
    obs.reset_metrics()
    if QUICK:
        # the shared prefix spans most of the prompt so a hit drops the
        # prefill bucket 128 -> 16: the skipped compute dominates the
        # per-request dispatch floor even at this tiny geometry
        model_kw = dict(vocab=64, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, n_experts=2)
        max_batch, max_seq, n_req, max_new, shared_len = 4, 128, 24, 6, 112
    else:
        model_kw = dict(vocab=256, d_model=128, n_heads=8, n_layers=4,
                        d_ff=256, n_experts=2)
        max_batch, max_seq, n_req, max_new, shared_len = 8, 256, 48, 16, 224
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1),
                             ("dp",))
    model = TransformerParallel(mesh, **model_kw)
    params = model.init(seed=0)
    rng = np.random.RandomState(0)
    vocab = model_kw["vocab"]
    head = [int(t) for t in rng.randint(1, vocab, size=shared_len)]
    prompts = [head + [int(t) for t in rng.randint(
        1, vocab, size=1 + int(rng.randint(8)))] for _ in range(n_req)]
    sp = SamplingParams(max_new_tokens=max_new)

    gen = Generator(model, params, GenerationConfig(
        prefix_cache=True, max_batch=max_batch, max_seq=max_seq))
    gen.warmup()
    # offered load: Poisson at ~2x one request's sequential capacity
    t0 = _time.perf_counter()
    gen.generate(prompts[0], sp, timeout=600)
    t_req = _time.perf_counter() - t0
    arrivals = np.cumsum(rng.exponential(t_req / 2.0, n_req))

    def run_pass(g):
        hits0 = M.get_value("generation.prefix_hits", 0)
        skipped0 = M.get_value("generation.prefill_tokens_skipped", 0)
        ttfts = [None] * n_req
        threads = []
        # sharing is a LIVE quantity (refs drop back to the cache's one
        # per page at drain): sample it while requests are in flight
        sharing = {"pages_shared": 0, "bytes_saved_shared": 0}

        def consume(handle, idx, t_sub):
            stream = handle.stream(timeout=600)
            next(stream)
            ttfts[idx] = (_time.perf_counter() - t_sub) * 1e3
            for _ in stream:
                pass

        start = _time.perf_counter()
        for i, (a, p) in enumerate(zip(arrivals, prompts)):
            now = _time.perf_counter() - start
            if now < a:
                _time.sleep(a - now)
            t_sub = _time.perf_counter()
            h = g.submit(p, sp)
            t = threading.Thread(target=consume, args=(h, i, t_sub))
            t.start()
            threads.append(t)
            if i % 4 == 3:
                snap = g.pool.get_stats()
                for k in sharing:
                    sharing[k] = max(sharing[k], snap[k])
        for t in threads:
            t.join(600)
        assert all(v is not None for v in ttfts)
        pct = lambda xs, p: round(float(np.percentile(xs, p)), 3)  # noqa: E731
        return {"ttft_p50_ms": pct(ttfts, 50), "ttft_p99_ms": pct(ttfts, 99),
                "hits": int(M.get_value("generation.prefix_hits", 0)
                            - hits0),
                "prefill_tokens_skipped": int(M.get_value(
                    "generation.prefill_tokens_skipped", 0) - skipped0),
                "peak_pages_shared": sharing["pages_shared"],
                "peak_bytes_saved_shared": sharing["bytes_saved_shared"]}

    # miss arm: a cache-LESS generator serves the same schedule (every
    # request pays the full prefill); hit arm: the cached generator,
    # tree warmed by the probe + a discarded seeding pass
    gen_off = Generator(model, params, GenerationConfig(
        prefix_cache=False, max_batch=max_batch, max_seq=max_seq))
    gen_off.warmup()
    cold = run_pass(gen_off)
    gen_off.stop(drain=True)
    gen_off.pool.assert_no_leaks()
    run_pass(gen)                       # seed: every block cached
    warm = run_pass(gen)
    pool_peak = gen.pool.get_stats()
    cache_stats = gen.prefix_cache.get_stats()

    # --- SLO witness: overtake without starvation ----------------------
    admit_order = []
    orig_prefill = gen._prefill

    def spy(slot, ent, worst):
        # the 2-token tail marks queued probes; blockers carry bare head
        admit_order.append((ent.slo.name, len(ent.prompt)))
        return orig_prefill(slot, ent, worst)

    gen._prefill = spy
    blockers = [gen.submit(head, SamplingParams(
        max_new_tokens=max_seq - shared_len - 1), slo="batch")
        for _ in range(max_batch)]
    _time.sleep(0.05)  # every slot busy
    batch_hs = [gen.submit(head + [2, i], sp, slo="batch")
                for i in range(2)]
    inter_hs = [gen.submit(head + [3, i], sp, slo="interactive")
                for i in range(2)]
    t0 = _time.perf_counter()
    for h in inter_hs:
        h.result(timeout=600)
    inter_done = _time.perf_counter() - t0
    for h in batch_hs + blockers:
        h.result(timeout=600)
    batch_done = _time.perf_counter() - t0
    gen._prefill = orig_prefill
    queued_admits = [(c, n) for c, n in admit_order
                     if n == shared_len + 2]
    overtake = [c for c, _ in queued_admits][:2] == ["interactive"] * 2
    gen.stop(drain=True)
    gen.pool.assert_no_leaks()

    results = {
        "protocol": ("causal LM %s, %d requests sharing a %d-token "
                     "prefix, Poisson arrivals at 2x sequential "
                     "capacity, max_new=%d, cold pass = cleared cache"
                     % (model_kw, n_req, shared_len, max_new)),
        "cold": cold, "warm": warm,
        "ttft_p50_speedup": round(cold["ttft_p50_ms"]
                                  / max(warm["ttft_p50_ms"], 1e-9), 2),
        "prefix_cache": cache_stats,
        "pool": {k: pool_peak[k] for k in
                 ("cow_copies", "shared_admits", "peak_used", "used")},
        "slo": {"overtake": bool(overtake),
                "admit_order": [c for c, _ in queued_admits],
                "interactive_done_s": round(inter_done, 3),
                "batch_done_s": round(batch_done, 3)},
    }

    # merge into the bench artifact + one ledger row (compared only
    # against other control rows by bench-name intersection)
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["control"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    try:
        append_perf_ledger({"configs": {"control_prefix_ttft": {
            "value": results["ttft_p50_speedup"],
            "unit": "x TTFT p50 cold vs warm prefix cache"}}})
    except Exception:
        traceback.print_exc()
    print(json.dumps({"control": results}))
    if warm["hits"] <= 0:
        raise SystemExit("bench_all --control: warm pass recorded zero "
                         "prefix-cache hits")
    if warm["ttft_p50_ms"] >= cold["ttft_p50_ms"]:
        raise SystemExit(
            "bench_all --control: warm-cache TTFT p50 %.3f ms did not "
            "improve on cold %.3f ms" % (warm["ttft_p50_ms"],
                                         cold["ttft_p50_ms"]))
    if not overtake:
        raise SystemExit(
            "bench_all --control: queued interactive requests did not "
            "overtake the batch queue: %r" % (queued_admits,))
    print("[bench_all] control gate passed: TTFT p50 %.2fms -> %.2fms "
          "(%.2fx), %d tokens skipped warm, overtake ok, batch served "
          "in %.2fs" % (cold["ttft_p50_ms"], warm["ttft_p50_ms"],
                        results["ttft_p50_speedup"],
                        warm["prefill_tokens_skipped"], batch_done),
          file=sys.stderr)
    return results


BENCHES = [
    ("resnet50_train_bs32", bench_resnet50_train),
    ("resnet50_infer_bs32", bench_resnet50_infer),
    ("lenet_mnist_train", bench_lenet_mnist),
    ("gluon_resnet18_train", bench_gluon_resnet),
    ("lstm_ptb_train", bench_lstm_ptb),
    ("ssd300_train", bench_ssd300),
    ("flash_attention_T4096", bench_flash_attention),
    ("flash_attention_train", bench_flash_attention_train),
    ("transformer_lm_T2048", bench_transformer_lm),
    # long-context training anchor: same tokens/step as T2048 but the
    # attention working set only fits because the backward is tiled
    ("transformer_lm_T4096",
     functools.partial(bench_transformer_lm, B=2 if QUICK else 4,
                       T=256 if QUICK else 4096)),
    # request path: micro-batched bucketed serving vs the naive loop
    ("serving_resnet50", bench_serving_resnet50),
    # autoregressive decode path: continuous batching vs sequential
    ("generation_lm", bench_generation_lm),
]


def _start_telemetry():
    """--telemetry: metrics registry on + profiler session over the whole
    bench run. Measurement mode, NOT headline-number mode: the eager
    dispatcher fences per op under telemetry, so eager sub-measurements
    slow down; compiled-step numbers are unaffected (one fence per
    program run, which the benches do anyway)."""
    from mxnet_tpu import observability, profiler

    observability.set_enabled(True)
    observability.reset_metrics()
    profiler.set_config(mode="all", filename=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_TRACE.json"))
    profiler.set_state("run")


def _collect_telemetry(results):
    """Attach dump_metrics() + the trace_report top-K table to the bench
    artifact (the per-op time budget riding along with the numbers)."""
    from mxnet_tpu import observability, profiler

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_report

    trace_path = profiler.dump_profile()
    top = trace_report.report(trace_path, k=15)
    print(trace_report.format_table(
        top, "top 15 by total time — %s" % trace_path), file=sys.stderr)
    results["telemetry"] = {
        "trace": trace_path,
        "top_ops": top,
        "metrics": observability.dump_metrics(),
        "note": ("telemetry mode fences eager dispatches per op; eager "
                 "sub-measurements are attribution numbers, not "
                 "throughput claims"),
    }


def bench_health_overhead(threshold_pct=None):
    """--health-overhead: gate the warn-mode per-step cost of the
    training-health layer (observability/health.py) on the transformer
    microbench. Runs the SAME compiled train-step loop twice — policy
    ``off`` (the zero-cost no-op path) and policy ``warn`` (one fused
    non-finite reduction + one tiny host fetch + a flight-recorder ring
    record per step) — and fails if warn adds more than ``threshold_pct``
    (default 2%, env MXNET_HEALTH_GATE_PCT) to the per-step wall time.
    Best-of-3 per arm to shave scheduler noise."""
    import jax

    from mxnet_tpu.observability import flight_recorder, health
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.transformer import TransformerParallel

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_HEALTH_GATE_PCT", "2.0"))
    B, T = (2, 128) if QUICK else (4, 512)
    d_model, n_layers = (64, 2) if QUICK else (128, 4)
    steps = 10 if QUICK else 30

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tp = TransformerParallel(mesh, vocab=2048, d_model=d_model, n_heads=8,
                             n_layers=n_layers, d_ff=4 * d_model,
                             n_experts=1, dtype=np.dtype("bfloat16"))
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 2048, (B, T)).astype(np.int32)
    tok, tgt = tp.shard_batch(tok, np.roll(tok, -1, axis=1))
    step = tp.step_fn(lr=0.01)

    def run(policy):
        health.set_policy(policy)
        # the step program donates its params, so each arm chains one
        # fresh parameter pytree through every iteration
        params = tp.init(0)
        names = [jax.tree_util.keystr(path) for path, _leaf in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        params, loss = step(params, tok, tgt)
        float(loss)  # compile + warm (also warms the fused check below)
        if policy != "off":
            named = list(zip(names, jax.tree_util.tree_leaves(params)))
            health.guard_step("bench.transformer", losses=[("loss", loss)],
                              params=named, lr=0.01, step=0)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(steps):
                params, loss = step(params, tok, tgt)
                if policy != "off":
                    named = list(zip(names,
                                     jax.tree_util.tree_leaves(params)))
                    health.guard_step(
                        "bench.transformer", losses=[("loss", loss)],
                        params=named, lr=0.01, step=i + 1)
            float(loss)
            best = min(best, (time.perf_counter() - t0) / steps)
        return best

    try:
        off_s = run("off")
        warn_s = run("warn")
    finally:
        # settle the warn arm's lag-1 stash BEFORE the reset, or a later
        # dump/atexit flush would commit a bench record into a user ring
        health.flush(allow_dump=False)
        health.set_policy(None)
        flight_recorder.reset()
    pct = 100.0 * (warn_s - off_s) / off_s
    result = {"off_ms_per_step": round(off_s * 1e3, 3),
              "warn_ms_per_step": round(warn_s * 1e3, 3),
              "overhead_pct": round(pct, 2),
              "threshold_pct": threshold_pct,
              "protocol": ("transformer LM d%d x%d T=%d bs%d, warn = fused "
                           "non-finite check over loss+params + ring record "
                           "per step" % (d_model, n_layers, T, B))}
    print("[bench_all] health overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if pct > threshold_pct:
        raise SystemExit(
            "bench_all --health-overhead: warn-mode costs %.2f%% per step "
            "(> %.2f%% gate) — the health check must stay cheap enough to "
            "leave on" % (pct, threshold_pct))
    print("[bench_all] health-overhead gate passed (%.2f%% <= %.2f%%)"
          % (pct, threshold_pct), file=sys.stderr)
    return result


def bench_resilience_overhead(threshold_pct=None):
    """--resilience-overhead: gate the faults-DISABLED cost of the
    resilience layer on the serving microbench (ISSUE 8). The per-step
    additions to the request path are (a) one ``faults.inject`` no-op
    per replica dispatch and (b) one deadline check per request at pop
    — both host-side constant work. Wall-clock A/B of two serving runs
    measures ambient scheduler noise larger than the effect (the lesson
    the autotune warm-cache gate learned), so the hard gate is on the
    stable quantities: the measured per-call cost of the disabled paths
    times their calls-per-request, as a percentage of the measured
    per-request serving latency. Fails above ``threshold_pct`` (default
    1%, env MXNET_RESILIENCE_GATE_PCT)."""
    import numpy as _np

    import mxnet_tpu as mx
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import InferenceServer, ServingConfig

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_RESILIENCE_GATE_PCT",
                                             "1.0"))
    faults.reset()
    assert not faults.enabled()

    # (a) disabled injection point: per-call ns, best of 3 blocks
    n = 200_000
    inject = faults.inject
    best_inject = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _i in range(n):
            inject("serving.replica_execute", tag=0)
        best_inject = min(best_inject, (time.perf_counter() - t0) / n)
    # (b) the deadline check is one monotonic() read + compare per
    # request (engine._pop_locked); measure the same shape directly
    now = time.monotonic
    deadline = now() + 3600.0
    best_check = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        expired = 0
        for _i in range(n):
            if now() >= deadline:
                expired += 1
        best_check = min(best_check, (time.perf_counter() - t0) / n)
    assert expired == 0

    # per-request serving latency on the tiny-MLP microbench
    rng = _np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=16, name="fc"),
        name="softmax")
    args = {"fc_weight": mx.nd.array(rng.randn(16, 12).astype(_np.float32)),
            "fc_bias": mx.nd.array(rng.randn(16).astype(_np.float32))}
    server = InferenceServer(
        net, args, data_shapes=[("data", (1, 12))],
        config=ServingConfig(buckets=(1, 2, 4, 8), max_wait_ms=0))
    server.warmup()
    n_req = 100 if QUICK else 400
    xs = [rng.rand(1 + (i % 4), 12).astype(_np.float32)
          for i in range(n_req)]
    t0 = time.perf_counter()
    for f in [server.submit(x) for x in xs]:
        f.result(timeout=120)
    per_request_s = (time.perf_counter() - t0) / n_req
    server.stop()

    # worst-case calls per request: one inject per dispatch (<= 1 per
    # request at bucket occupancy 1) + one deadline check per request
    cost_s = best_inject + best_check
    pct = 100.0 * cost_s / per_request_s
    result = {
        "inject_disabled_ns": round(best_inject * 1e9, 1),
        "deadline_check_ns": round(best_check * 1e9, 1),
        "serving_request_us": round(per_request_s * 1e6, 1),
        "overhead_pct": round(pct, 4),
        "threshold_pct": threshold_pct,
        "protocol": ("per-call cost of the disabled inject() + deadline "
                     "check vs measured per-request serving latency "
                     "(%d requests, tiny-MLP, buckets 1-8)" % n_req),
    }
    print("[bench_all] resilience overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if pct > threshold_pct:
        raise SystemExit(
            "bench_all --resilience-overhead: disabled fault/deadline "
            "paths cost %.3f%% per request (> %.2f%% gate) — injection "
            "points must stay cheap enough to leave wired in"
            % (pct, threshold_pct))
    print("[bench_all] resilience-overhead gate passed (%.4f%% <= %.2f%%)"
          % (pct, threshold_pct), file=sys.stderr)
    return result


def bench_obs_overhead(threshold_pct=None):
    """--obs-overhead: gate the request-tracing cost of the
    observability plane (ISSUE 12) on the serving microbench. Wall-clock
    A/B of tracing-on vs tracing-off serving runs measures ambient
    scheduler noise larger than the effect (the autotune/resilience gate
    lesson), so the hard gate is on the stable quantities: the measured
    per-request cost of a FULL trace (begin + the per-phase events +
    finish incl. reservoir offer) and of the sampled-out no-op path,
    each as a percentage of the measured per-request serving LATENCY
    (closed-loop submit->result median — the quantity the tracing
    overhead actually rides on, and what an SLO measures). The burst
    throughput⁻¹ per-request cost is recorded as informational: on a
    CPU toy model it bounds pure Python dispatch, which no real model's
    request resembles. Fails above ``threshold_pct`` (default 1%, env
    MXNET_OBS_GATE_PCT)."""
    import numpy as _np

    import mxnet_tpu as mx
    from mxnet_tpu.config import set_flag
    from mxnet_tpu.observability import request_trace as RT
    from mxnet_tpu.serving import InferenceServer, ServingConfig

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_OBS_GATE_PCT", "1.0"))

    # (a) per-request cost of the traced path: the exact call shape the
    # serving engine performs per request (submit birth, 4 phase ends,
    # finish -> histograms off, reservoir offer)
    n = 20_000
    RT.reset()
    best_traced = float("inf")
    set_flag("MXNET_OBS_TRACE_SAMPLE", 1)  # the engine's real call shape
    for _ in range(3):
        t0 = time.perf_counter()
        for _i in range(n):
            tr = RT.begin("serving")
            tr.event("queue")
            tr.event("batch")
            tr.event("compute")
            tr.event("fetch")
            tr.finish()
        best_traced = min(best_traced, (time.perf_counter() - t0) / n)
    # (b) the sampled-out no-op path (MXNET_OBS_TRACE_SAMPLE=0)
    set_flag("MXNET_OBS_TRACE_SAMPLE", 0)
    best_noop = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _i in range(n):
            tr = RT.begin("serving")
            tr.event("queue")
            tr.event("batch")
            tr.event("compute")
            tr.event("fetch")
            tr.finish()
        best_noop = min(best_noop, (time.perf_counter() - t0) / n)
    set_flag("MXNET_OBS_TRACE_SAMPLE", 1)
    RT.reset()

    # per-request serving latency on the small-MLP microbench (128->256
    # — the tiny 12->16 net of the resilience gate is degenerate enough
    # that throughput is pure Python dispatch; this one still costs the
    # device something, like any real model). Tracing runs at the
    # default sample=1, so the measured latency already INCLUDES the
    # traced path — conservative. Median of 3 runs: single-run wall
    # clock of a burst drain wobbles tens of percent.
    rng = _np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=256, name="fc"),
        name="softmax")
    args = {"fc_weight": mx.nd.array(
                rng.randn(256, 128).astype(_np.float32)),
            "fc_bias": mx.nd.array(rng.randn(256).astype(_np.float32))}
    server = InferenceServer(
        net, args, data_shapes=[("data", (1, 128))],
        config=ServingConfig(buckets=(1, 2, 4, 8), max_wait_ms=0))
    server.warmup()
    n_req = 100 if QUICK else 400
    xs = [rng.rand(1 + (i % 4), 128).astype(_np.float32)
          for i in range(n_req)]
    # (c) closed-loop request latency: submit -> result, one request in
    # flight — the per-request quantity tracing overhead rides on
    n_solo = 30 if QUICK else 100
    solo = []
    for i in range(n_solo):
        t0 = time.perf_counter()
        server.predict(xs[i % len(xs)], timeout=120)
        solo.append(time.perf_counter() - t0)
    latency_s = sorted(solo)[len(solo) // 2]
    # (d) informational: burst throughput⁻¹ (median of 3 drains)
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        for f in [server.submit(x) for x in xs]:
            f.result(timeout=120)
        walls.append(time.perf_counter() - t0)
    burst_per_request_s = sorted(walls)[1] / n_req
    server.stop()
    set_flag("MXNET_OBS_TRACE_SAMPLE", None)

    pct_traced = 100.0 * best_traced / latency_s
    pct_noop = 100.0 * best_noop / latency_s
    result = {
        "traced_request_ns": round(best_traced * 1e9, 1),
        "noop_request_ns": round(best_noop * 1e9, 1),
        "request_latency_us": round(latency_s * 1e6, 1),
        "burst_request_us": round(burst_per_request_s * 1e6, 1),
        "overhead_pct_traced": round(pct_traced, 4),
        "overhead_pct_off": round(pct_noop, 4),
        "overhead_pct_traced_burst": round(
            100.0 * best_traced / burst_per_request_s, 4),
        "threshold_pct": threshold_pct,
        "protocol": ("per-request cost of a full RequestTrace (and of "
                     "the sampled-out no-op path) vs median closed-loop "
                     "request latency (%d solo requests, 128->256 MLP, "
                     "buckets 1-8); burst throughput⁻¹ informational"
                     % n_solo),
    }
    print("[bench_all] obs overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if pct_traced > threshold_pct or pct_noop > threshold_pct:
        raise SystemExit(
            "bench_all --obs-overhead: request tracing costs %.3f%% "
            "traced / %.3f%% sampled-out per request (gate %.2f%% on "
            "BOTH) — the trace path must stay cheap enough to leave on "
            "by default" % (pct_traced, pct_noop, threshold_pct))
    print("[bench_all] obs-overhead gate passed (traced %.4f%% / off "
          "%.4f%% <= %.2f%%)" % (pct_traced, pct_noop, threshold_pct),
          file=sys.stderr)
    return result


def bench_ts_overhead(threshold_pct=None):
    """--ts-overhead: gate the time-series plane's background cost
    (ISSUE 17) on stable quantities. Wall-clock A/B of sampler-on vs
    sampler-off serving runs measures scheduler noise larger than the
    effect (the obs/resilience gate lesson), so the hard gate is on
    DUTY CYCLES: the measured cost of one ``sample_once()`` pass
    (pre-sample hooks -> registry snapshot -> ring appends) over a
    representative registry, and of one fleet ``scrape_once()``
    (parse + reassemble + merge-append of a full exposition body),
    each as a percentage of its own sampling interval — the fraction
    of one core the background thread occupies. Fails above
    ``threshold_pct`` (default 1%, env MXNET_TS_GATE_PCT)."""
    import mxnet_tpu as mx
    from mxnet_tpu.observability import metrics as M
    from mxnet_tpu.observability import timeseries as TS
    from mxnet_tpu.observability.fleet import FleetAggregator

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_TS_GATE_PCT", "1.0"))

    mx.observability.set_enabled(True)
    M.reset_metrics()
    # a registry bigger than any smoke leaves behind: a serving worker's
    # instrument population with room to spare
    for i in range(40):
        M.counter("bench.req", labels={"code": str(i % 8),
                                       "route": "r%d" % (i % 5)}).inc(i)
    for i in range(20):
        M.gauge("bench.depth", labels={"shard": str(i)}).set(float(i))
    for i in range(12):
        h = M.histogram("bench.lat", labels={"engine": "e%d" % i},
                        buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        for v in (0.5, 3.0, 17.0, 200.0):
            h.observe(v)
    series = len(M.all_instruments())

    interval_s = 1.0   # the MXNET_OBS_TS_INTERVAL_MS default
    n = 50 if QUICK else 200
    sampler = TS.TimeSeriesSampler(interval_ms=interval_s * 1e3,
                                   retain=600, clock=lambda: 0.0)
    best_sample = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            sampler.sample_once(now=float(i))
        best_sample = min(best_sample, (time.perf_counter() - t0) / n)

    # fleet side: parse + merge one full worker exposition per scrape
    # (the text is pre-rendered — a real scrape's render happens on the
    # WORKER; fetch latency is network, not CPU duty)
    text = M.dump_metrics()
    agg = FleetAggregator({"w0": "u"}, interval_ms=interval_s * 1e3,
                          stale_after=3, dead_after=10,
                          clock=lambda: 0.0, fetch=lambda url: text,
                          retain=600)
    best_scrape = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            agg.scrape_once(now=float(i))
        best_scrape = min(best_scrape, (time.perf_counter() - t0) / n)
    M.reset_metrics()

    duty_sample = 100.0 * best_sample / interval_s
    duty_scrape = 100.0 * best_scrape / interval_s
    result = {
        "registry_series": series,
        "sample_once_us": round(best_sample * 1e6, 1),
        "scrape_once_us": round(best_scrape * 1e6, 1),
        "interval_ms": interval_s * 1e3,
        "duty_pct_sampler": round(duty_sample, 4),
        "duty_pct_fleet_scrape": round(duty_scrape, 4),
        "threshold_pct": threshold_pct,
        "protocol": ("min-of-3 mean cost over %d sample_once()/"
                     "scrape_once() passes against a %d-instrument "
                     "registry, as %% of the 1s default interval"
                     % (n, series)),
    }
    print("[bench_all] ts overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if duty_sample > threshold_pct or duty_scrape > threshold_pct:
        raise SystemExit(
            "bench_all --ts-overhead: sampler duty %.3f%% / fleet scrape "
            "duty %.3f%% of the sampling interval (gate %.2f%% on BOTH) "
            "— the time-series plane must stay cheap enough to leave on"
            % (duty_sample, duty_scrape, threshold_pct))
    print("[bench_all] ts-overhead gate passed (sampler %.4f%% / scrape "
          "%.4f%% <= %.2f%%)" % (duty_sample, duty_scrape, threshold_pct),
          file=sys.stderr)
    return result


def bench_autotune(gate_pct=None):
    """--autotune: drive the search-based autotuner (ISSUE 6) over its
    three knob families and record tuned-vs-default numbers, so the perf
    trajectory shows what the tuner bought:

    * flash-attention fwd+bwd block bounds — measured sweep, then the
      SAME train-microbench protocol times the config defaults against
      the tuned blocks,
    * the serving bucket ladder — candidate ladders replay one traffic
      sample on a live InferenceServer,
    * per-graph layout (NHWC vs NCHW) — measured ResNet train step, plus
      an hlo_layout_audit artifact (LAYOUT_AUDIT_BENCH.json) diffing the
      two layouts' layout-moving bytes,

    and gates the warm-cache overhead: consulting a warm tuning cache
    (MXNET_TUNE=0 + entries present) must add < MXNET_TUNE_GATE_PCT
    (default 1%) per step over a full bypass (MXNET_TUNE=-1) — same gate
    style as --health-overhead. Off-TPU the kernels run in Pallas
    interpret mode: the recorded flash numbers are only meaningful
    relative to each other (on-chip numbers land with the next bench
    pass); the search space always contains the incumbent defaults, so
    tuned can only beat or match them modulo noise.

    Results merge into BENCH_ALL.json under "autotune".
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import autotune
    from mxnet_tpu import config as mxconfig
    from mxnet_tpu.autotune import median_time
    from mxnet_tpu.config import get_flag

    if gate_pct is None:
        gate_pct = float(os.environ.get("MXNET_TUNE_GATE_PCT", "1.0"))
    interpret = jax.default_backend() != "tpu"
    results = {"device": jax.devices()[0].device_kind, "quick": QUICK,
               "interpret_mode": interpret,
               "fingerprint": autotune.device_fingerprint(),
               "cache": autotune.cache_path()}
    if interpret:
        results["note"] = ("off-TPU run: flash kernels in Pallas "
                           "interpret mode — numbers are relative only; "
                           "on-chip numbers pending next bench pass")
    here = os.path.dirname(os.path.abspath(__file__))

    # ---- flash-attention block bounds: default vs tuned ------------------
    from mxnet_tpu.parallel.flash_attention import flash_attention

    T, D, H = (256, 32, 2) if QUICK else (4096, 64, 8)
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
               for _ in range(3))

    def flash_train_ms(bq, bk, bqb, bkb):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                block_q_bwd=bqb, block_k_bwd=bkb,
                interpret=interpret).astype(jnp.float32))

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return median_time(lambda: jax.block_until_ready(fn(q, k, v)),
                           repeats=3, warmup=1) * 1e3

    default_blocks = (get_flag("MXNET_FLASH_BLOCK_Q"),
                      get_flag("MXNET_FLASH_BLOCK_K"),
                      get_flag("MXNET_FLASH_BWD_BLOCK_Q"),
                      get_flag("MXNET_FLASH_BWD_BLOCK_K"))
    default_ms = flash_train_ms(*default_blocks)
    tuned = autotune.tune_flash_attention(
        T=T, D=D, B=1, H=H, dtype="bfloat16", causal=True,
        interpret=interpret, trials=4 if QUICK else None)
    tf_, tb = tuned["flash_attention.fwd"], tuned["flash_attention.bwd"]
    tuned_blocks = (tf_["block_q"], tf_["block_k"],
                    tb["block_q"], tb["block_k"])
    tuned_ms = flash_train_ms(*tuned_blocks)
    results["flash_attention"] = {
        "protocol": "fwd+bwd grad(q,k,v) b1 h%d T=%d d%d bf16 causal"
                    % (H, T, D),
        "default_blocks": list(default_blocks),
        "tuned_blocks": list(tuned_blocks),
        "default_ms": round(default_ms, 3), "tuned_ms": round(tuned_ms, 3),
        "speedup": round(default_ms / tuned_ms, 3),
    }
    print("[bench_all] autotune flash: default %.2f ms -> tuned %.2f ms "
          "(blocks %s -> %s)" % (default_ms, tuned_ms,
                                 list(default_blocks), list(tuned_blocks)),
          file=sys.stderr)

    # ---- serving bucket ladder: default vs tuned -------------------------
    from mxnet_tpu.autotune.tuners import serving_replay_measurer
    from mxnet_tpu.serving.buckets import parse_buckets

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=32, name="fc"),
        name="softmax")
    arg_params = {"fc_weight": mx.nd.array(
        rng.randn(32, 24).astype(np.float32) * 0.1),
        "fc_bias": mx.nd.zeros((32,))}
    data_shapes = [("data", (1, 24))]
    n_req = 64 if QUICK else 240
    # skewed request-size traffic: mostly singles, a p95 tail of 6-20
    sizes = [int(s) for s in
             rng.choice([1, 1, 1, 1, 2, 2, 3, 4, 6, 20], size=n_req)]

    # the SAME protocol the search uses (tuners.serving_replay_measurer)
    _srv_measure = serving_replay_measurer(net, arg_params, data_shapes,
                                           sizes, max_wait_ms=2)

    def serving_ms(ladder):
        return _srv_measure({"buckets": ladder}) * 1e3

    default_ladder = list(parse_buckets(None))
    default_srv_ms = serving_ms(default_ladder)
    tuned_ladder = autotune.tune_serving_buckets(
        net, arg_params, data_shapes, sizes,
        trials=3 if QUICK else None)
    tuned_srv_ms = serving_ms(tuned_ladder)
    kept_default = False
    if tuned_srv_ms > default_srv_ms and tuned_ladder != default_ladder:
        # head-to-head confirmation: if the search's pick loses the
        # re-measure (noise on tiny CPU runs), keep the incumbent in the
        # cache — a shipped cache must never regress below the default
        from mxnet_tpu.autotune.tuners import model_key
        from mxnet_tpu.serving.buckets import traffic_signature

        mkey = model_key(net)
        for tk in ("default", traffic_signature(sizes)):
            autotune.record("serving.buckets", (mkey, tk),
                            {"buckets": default_ladder},
                            ms=default_srv_ms,
                            extra={"note": "head-to-head kept default"})
        tuned_ladder, tuned_srv_ms = default_ladder, default_srv_ms
        kept_default = True
    results["serving_buckets"] = {
        "protocol": "%d requests, sizes p50=1 p95=6 max=20, MLP fc32"
                    % n_req,
        "default_ladder": default_ladder, "tuned_ladder": tuned_ladder,
        "default_ms": round(default_srv_ms, 1),
        "tuned_ms": round(tuned_srv_ms, 1),
        "speedup": round(default_srv_ms / tuned_srv_ms, 3),
        "kept_default": kept_default,
    }
    print("[bench_all] autotune serving: default %s %.0f ms -> tuned %s "
          "%.0f ms" % (default_ladder, default_srv_ms, tuned_ladder,
                       tuned_srv_ms), file=sys.stderr)

    # ---- per-graph layout: measured NHWC vs NCHW + audit artifact --------
    from mxnet_tpu.models import get_resnet

    layers, size, bs, steps = (18, 32, 2, 2) if QUICK else (50, 224, 16, 8)

    def layout_step_s(cand):
        layout = cand["layout"]
        sym = get_resnet(num_classes=1000, num_layers=layers,
                         image_shape=(3, size, size), layout=layout)
        shape = ((bs, 3, size, size) if layout == "NCHW"
                 else (bs, size, size, 3))
        mod = mx.mod.Module(sym, context=_bench_ctx())
        mod.bind(data_shapes=[("data", shape)],
                 label_shapes=[("softmax_label", (bs,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.rand(*shape).astype(np.float32))],
            label=[mx.nd.array(
                rng.randint(0, 1000, bs).astype(np.float32))])

        def run():
            for _ in range(steps):
                mod.forward_backward(batch)
                mod.update()
            mod.get_outputs()[0].asnumpy()

        return median_time(run, repeats=2, warmup=1) / steps

    layout_key = ("resnet%d" % layers, "b%d" % bs, "s%d" % size)
    per_layout = {}

    def layout_measure(c):  # the tuner's measure hook doubles as the log
        s = layout_step_s(c)
        per_layout[c["layout"]] = round(s * 1e3, 2)
        return s

    layout_winner = autotune.tune_layout(layout_measure, key=layout_key,
                                         default="NHWC")
    results["layout"] = {
        "protocol": "resnet%d bs%d %dx%d fused train step" % (
            layers, bs, size, size),
        "per_layout_ms": per_layout,
        "tuned": layout_winner, "key": list(layout_key),
    }
    print("[bench_all] autotune layout: %s (%s)" % (
        layout_winner, per_layout), file=sys.stderr)

    sys.path.insert(0, os.path.join(here, "tools"))
    import hlo_layout_audit

    audit_layers, audit_bs, audit_size = (18, 2, 64) if QUICK \
        else (50, 32, 224)
    audits = {lay: hlo_layout_audit.run_audit(
        layers=audit_layers, batch=audit_bs, size=audit_size, layout=lay)
        for lay in ("NHWC", "NCHW")}
    audit_path = os.path.join(here, "LAYOUT_AUDIT_BENCH.json")
    with open(audit_path, "w") as f:
        json.dump({"nhwc": audits["NHWC"], "nchw": audits["NCHW"],
                   "diff_nchw_to_nhwc": hlo_layout_audit.compare_reports(
                       audits["NCHW"], audits["NHWC"])}, f, indent=1)
    results["layout"]["audit_artifact"] = os.path.basename(audit_path)
    results["layout"]["transpose_mb"] = {
        lay.lower(): round(audits[lay]["transpose"]["bytes_total"] / 2**20,
                           2) for lay in audits}

    # ---- warm-cache overhead gate (<1% per step, health-gate style) ------
    from mxnet_tpu.executor import _GraphProgram

    fc1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, num_hidden=512, name="g1"), act_type="relu")
    fc2 = mx.sym.Activation(mx.sym.FullyConnected(
        fc1, num_hidden=512, name="g2"), act_type="relu")
    gate_net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        fc2, num_hidden=16, name="g3"), name="softmax")
    # the gate's warm entry is SYNTHETIC (never measured) — stage it in
    # a scratch cache file so it can never leak into the user's
    # persistent cache and silently override a real remat flag later
    import tempfile

    gate_cache = os.path.join(tempfile.mkdtemp(prefix="mxtune_gate_"),
                              "tuning.json")
    prev_cache = os.environ.get("MXNET_TUNE_CACHE")
    os.environ["MXNET_TUNE_CACHE"] = gate_cache
    autotune.cache.reset()
    autotune.record("exec.remat", _GraphProgram(gate_net).tuning_key(),
                    {"mirror": 0})
    # step must be big enough (several ms) that constant
    # per-instance CPU noise sits well under the 1% gate
    gbs, gsteps = 128, (20 if QUICK else 60)
    gbatch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(gbs, 64).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 16, gbs).astype(np.float32))])

    def gate_build(mode):
        # the cache consult happens at program-build (trace) time, so
        # the mode is pinned while this module compiles its train step
        mxconfig.set_flag("MXNET_TUNE", mode)
        mod = mx.mod.Module(gate_net, context=mx.cpu(),
                            data_names=("data",))
        mod.bind(data_shapes=[("data", (gbs, 64))],
                 label_shapes=[("softmax_label", (gbs,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))
        for _ in range(3):  # compile + warm
            mod.forward_backward(gbatch)
            mod.update()
        mod.get_outputs()[0].asnumpy()
        return mod

    def gate_steps(mod):
        t0 = time.perf_counter()
        for _ in range(gsteps):
            mod.forward_backward(gbatch)
            mod.update()
        mod.get_outputs()[0].asnumpy()
        return (time.perf_counter() - t0) / gsteps

    gate_key = _GraphProgram(gate_net).tuning_key()
    try:
        mod_bypass = gate_build(-1)   # no lookups at all
        mod_consult = gate_build(0)   # warm cache consulted at build
        bypass_s = consult_s = float("inf")
        # interleaved A/B walls — INFORMATIONAL: two separately-built
        # executables of the same program differ by a few percent on
        # their own (codegen/allocator instance variance), so the hard
        # gate below is on the stable quantities instead
        for _ in range(6):
            bypass_s = min(bypass_s, gate_steps(mod_bypass))
            consult_s = min(consult_s, gate_steps(mod_consult))
        # (a) the steady-state step path performs ZERO cache lookups —
        # consults happen at program-build time only
        autotune.reset_stats()
        gate_steps(mod_consult)
        lk = autotune.stats()
        per_step_lookups = lk["hits"] + lk["misses"]
        # (b) even if every step DID pay one warm lookup, it would be
        # invisible: measure the warm-probe latency head-on
        n_probe = 2000
        t0 = time.perf_counter()
        for _ in range(n_probe):
            autotune.lookup("exec.remat", gate_key)
        lookup_s = (time.perf_counter() - t0) / n_probe
    finally:
        mxconfig.set_flag("MXNET_TUNE", None)
        if prev_cache is None:
            os.environ.pop("MXNET_TUNE_CACHE", None)
        else:
            os.environ["MXNET_TUNE_CACHE"] = prev_cache
        autotune.cache.reset()
    pct = 100.0 * lookup_s / consult_s
    results["warm_cache_overhead"] = {
        "bypass_ms_per_step": round(bypass_s * 1e3, 4),
        "consult_ms_per_step": round(consult_s * 1e3, 4),
        "ab_delta_pct": round(100.0 * (consult_s - bypass_s) / bypass_s,
                              2),
        "per_step_lookups": per_step_lookups,
        "warm_lookup_us": round(lookup_s * 1e6, 2),
        "overhead_pct": round(pct, 4), "threshold_pct": gate_pct,
        "protocol": "MLP 64-512-512-16 bs%d fused train step; gate = "
                    "zero per-step lookups + one warm lookup as %% of a "
                    "step (A/B walls informational: separately-built "
                    "executables carry instance variance)" % gbs,
    }
    print("[bench_all] autotune warm-cache overhead: %d per-step "
          "lookups, warm lookup %.1f us = %.4f%% of a %.2f ms step "
          "(gate %.2f%%)" % (per_step_lookups, lookup_s * 1e6, pct,
                             consult_s * 1e3, gate_pct), file=sys.stderr)

    # merge into the bench artifact
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["autotune"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps({"autotune": results}))
    if per_step_lookups:
        raise SystemExit(
            "bench_all --autotune: %d cache lookups on the steady-state "
            "step path — consults must stay at program-build time"
            % per_step_lookups)
    if pct > gate_pct:
        raise SystemExit(
            "bench_all --autotune: a warm lookup costs %.4f%% of a step "
            "(> %.2f%% gate) — trace-time lookups must stay free"
            % (pct, gate_pct))
    print("[bench_all] autotune gate passed (%.2f%% <= %.2f%%)"
          % (pct, gate_pct), file=sys.stderr)
    return results


def bench_graph_passes():
    """--graph-passes: optimized-vs-unoptimized inference on the bench
    resnet-style model (ISSUE 9 acceptance): the default pass pipeline
    must reduce compiled-program node count, and measured inference
    latency/throughput for both arms is recorded into BENCH_ALL.json
    (CPU QUICK now, on-chip numbers next bench pass)."""
    import time as _time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import graph_pass
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.models import get_resnet

    rng = np.random.RandomState(0)
    layers, size, bs = (18, 32, 4) if QUICK else (50, 224, 16)
    steps = 10 if QUICK else 50
    x = rng.rand(bs, 3, size, size).astype(np.float32)

    def build(spec):
        graph_pass.set_passes(spec)
        try:
            sym = get_resnet(num_classes=1000, num_layers=layers,
                             image_shape=(3, size, size))
            mod = mx.mod.Module(sym, context=_bench_ctx())
            mod.bind(data_shapes=[("data", x.shape)], for_training=False)
            mod.init_params(mx.init.Xavier())
            return mod
        finally:
            graph_pass.set_passes(None)

    def run(mod):
        it = lambda: NDArrayIter(x, None, batch_size=bs)  # noqa: E731
        mod.predict(it())  # compile + warm
        t0 = _time.perf_counter()
        for _ in range(steps):
            mod.predict(it())
        return (_time.perf_counter() - t0) / steps

    base = build("off")
    base_s = run(base)
    opt = build("default")
    opt_s = run(opt)
    ex = opt._exec_group.execs[0]
    info = ex._opt.summary() if ex._opt is not None else {}
    results = {
        "protocol": "resnet%d %dx%d bs%d predict, %d timed iters" % (
            layers, size, size, bs, steps),
        "unoptimized_ms": round(base_s * 1e3, 2),
        "optimized_ms": round(opt_s * 1e3, 2),
        "speedup": round(base_s / opt_s, 3),
        "images_per_s": {"unoptimized": round(bs / base_s, 1),
                         "optimized": round(bs / opt_s, 1)},
        "nodes_before": info.get("nodes_before"),
        "nodes_after": info.get("nodes_after"),
        "folded_constants": info.get("folded_constants"),
        "passes": info.get("passes"),
        "quick": QUICK,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["graph_passes"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps({"graph_passes": results}))
    if not info or info["nodes_after"] >= info["nodes_before"]:
        raise SystemExit(
            "bench_all --graph-passes: no node-count reduction (%s -> %s)"
            % (info.get("nodes_before"), info.get("nodes_after")))
    print("[bench_all] graph passes: %d -> %d nodes, %.2f ms -> %.2f ms "
          "(%.3fx)" % (results["nodes_before"], results["nodes_after"],
                       results["unoptimized_ms"], results["optimized_ms"],
                       results["speedup"]), file=sys.stderr)
    return results


def bench_fusion():
    """--fusion: fused-vs-unfused step time + the learned cost model's
    ranking-quality gate (ISSUE 15).

    **Regions** — the bench resnet-style model (predict; bn_fold feeds
    the conv+relu+residual chains) and a transformer block (train step;
    FC/batch_dot chains) run under ``default`` vs ``default,-fuse``.
    CPU-stable hard gates: fused region count > 0 on both, analytic
    interior-bytes saved > 0, and numeric parity between the arms.
    Wall-clock ratios are recorded (CPU QUICK they are informational;
    the on-chip MFU delta lands in BENCH_LEDGER.jsonl next bench pass).

    **Learned ranking** — measured ``fusion.blocks`` sweeps at several
    shape buckets populate the sample dataset; training computes the
    held-out-group Spearman of the learned ranking vs the analytic
    roofline's.  Hard gate: the degradation CONTRACT — when the holdout
    gate passes, the next search ranks "learned" AND its holdout
    Spearman >= the analytic baseline; when it fails, the next search
    provably ranks "analytic" (never worse than the roofline either
    way, docs/autotune.md)."""
    import time as _time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autotune, graph_pass
    from mxnet_tpu.autotune import learned
    from mxnet_tpu.autotune import search as _search
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.models import get_resnet

    rng = np.random.RandomState(0)
    layers, size, bs = (18, 32, 4) if QUICK else (50, 224, 16)
    steps = 10 if QUICK else 50

    def fuse_report():
        for rep in reversed(graph_pass.recent_reports()):
            if "fuse" in rep:
                return rep["fuse"]
        return {"regions": [], "saved_bytes": 0}

    # ---- resnet predict arm ------------------------------------------
    x = rng.rand(bs, 3, size, size).astype(np.float32)

    def build_resnet(spec):
        graph_pass.set_passes(spec)
        try:
            sym = get_resnet(num_classes=1000, num_layers=layers,
                             image_shape=(3, size, size))
            mod = mx.mod.Module(sym, context=_bench_ctx())
            mod.bind(data_shapes=[("data", x.shape)], for_training=False)
            mod.init_params(mx.init.Xavier())
            return mod
        finally:
            graph_pass.set_passes(None)

    def run_predict(mod):
        it = lambda: NDArrayIter(x, None, batch_size=bs)  # noqa: E731
        out = mod.predict(it()).asnumpy()  # compile + warm
        t0 = _time.perf_counter()
        for _ in range(steps):
            mod.predict(it())
        return (_time.perf_counter() - t0) / steps, out

    base = build_resnet("default,-fuse")
    base_s, base_out = run_predict(base)
    graph_pass.reset_stats()
    fused = build_resnet("default")
    # parity must compare the SAME parameters, not two Xavier draws
    arg_p, aux_p = base.get_params()
    fused.set_params(arg_p, aux_p)
    fused_s, fused_out = run_predict(fused)
    resnet_fuse = fuse_report()
    np.testing.assert_allclose(fused_out, base_out, rtol=1e-4, atol=1e-5)

    # ---- transformer-block train arm ---------------------------------
    T, D = (16, 32) if QUICK else (64, 128)
    tb = 8

    def tblock():
        data = mx.sym.var("data")
        q = mx.sym.FullyConnected(data, num_hidden=D, flatten=False,
                                  name="q")
        k = mx.sym.FullyConnected(data, num_hidden=D, flatten=False,
                                  name="k")
        v = mx.sym.FullyConnected(data, num_hidden=D, flatten=False,
                                  name="v")
        scores = mx.sym.batch_dot(q, mx.sym.transpose(k, axes=(0, 2, 1)))
        attn = mx.sym.softmax(scores / float(np.sqrt(D)), axis=-1)
        ctxv = mx.sym.batch_dot(attn, v)
        out = mx.sym.FullyConnected(ctxv + data, num_hidden=D,
                                    flatten=False, name="proj")
        flat = mx.sym.Flatten(out)
        return mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(flat, num_hidden=16, name="head"),
            name="softmax")

    tx = rng.rand(tb, T, D).astype(np.float32)
    ty = (np.arange(tb) % 16).astype(np.float32)

    def train_wall(spec):
        graph_pass.set_passes(spec)
        try:
            mod = mx.mod.Module(tblock(), context=mx.cpu())
            mod.bind(data_shapes=[("data", tx.shape)],
                     label_shapes=[("softmax_label", ty.shape)],
                     for_training=True)
            mod.init_params(mx.init.Uniform(0.05))
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.01})
            batch = mx.io.DataBatch(data=[mx.nd.array(tx)],
                                    label=[mx.nd.array(ty)])
            for _ in range(2):  # compile + warm
                mod.forward_backward(batch)
                mod.update()
            t0 = _time.perf_counter()
            for _ in range(steps):
                mod.forward_backward(batch)
                mod.update()
            mx.nd.waitall()
            return (_time.perf_counter() - t0) / steps
        finally:
            graph_pass.set_passes(None)

    tb_base_s = train_wall("default,-fuse")
    graph_pass.reset_stats()
    tb_fused_s = train_wall("default")
    tblock_fuse = fuse_report()

    # ---- learned ranking-quality gate --------------------------------
    # the whole phase runs against a SCRATCH tuning cache (the
    # bench_autotune gate discipline): the contract probe below drives
    # the real search with a constant fake measurer, and neither its
    # fabricated timing nor a bench-trained model file may ever leak
    # into the user's persistent cache/samples/model
    import tempfile

    scratch = tempfile.mkdtemp(prefix="mxfusion_gate_")
    prev_cache = os.environ.get("MXNET_TUNE_CACHE")
    prev_model = os.environ.get("MXNET_COST_MODEL_PATH")
    os.environ["MXNET_TUNE_CACHE"] = os.path.join(scratch, "tuning.json")
    os.environ.pop("MXNET_COST_MODEL_PATH", None)
    autotune.cache.reset()
    learned.reset()
    try:
        sweeps = [(128, 128, 256), (256, 128, 256), (128, 256, 512)] \
            if QUICK else [(128, 128, 256), (256, 128, 256),
                           (128, 256, 512), (512, 256, 512),
                           (256, 512, 1024)]
        for (m, n, k) in sweeps:
            autotune.tune_fused_matmul(m, n, k,
                                       trials=4 if QUICK else None,
                                       repeats=2)
        model = learned.train(min_samples=4)
        meta = dict(model.meta) if model is not None else {}
        gate_ok = bool(meta.get("gate_ok"))
        # the degradation contract, witnessed on a real search
        res = _search.search(
            autotune.get_tunable("fusion.blocks"),
            # the measured value is irrelevant here — only which RANKER
            # the search consulted is under test
            lambda c: 1e-3,
            ctx={"M": 64, "N": 64, "K": 128, "dtype_bytes": 4},
            cfg=_search.SearchConfig(trials=1))
        n_samples = learned.sample_count()
    finally:
        if prev_cache is None:
            os.environ.pop("MXNET_TUNE_CACHE", None)
        else:
            os.environ["MXNET_TUNE_CACHE"] = prev_cache
        if prev_model is not None:
            os.environ["MXNET_COST_MODEL_PATH"] = prev_model
        autotune.cache.reset()
        learned.reset()
    expected = "learned" if gate_ok else "analytic"
    if res.ranker != expected:
        raise SystemExit(
            "bench_all --fusion: ranking contract broken — gate_ok=%s "
            "but search ranked %r" % (gate_ok, res.ranker))
    if gate_ok and meta.get("spearman_analytic") is not None and \
            meta["spearman_learned"] < meta["spearman_analytic"] - 1e-9:
        raise SystemExit(
            "bench_all --fusion: gate passed with learned Spearman %.3f "
            "< analytic %.3f" % (meta["spearman_learned"],
                                 meta["spearman_analytic"]))

    results = {
        "protocol": "resnet%d %dx%d bs%d predict + transformer block "
                    "T%d D%d bs%d train, %d timed iters" % (
                        layers, size, size, bs, T, D, tb, steps),
        "resnet_predict": {
            "unfused_ms": round(base_s * 1e3, 2),
            "fused_ms": round(fused_s * 1e3, 2),
            "speedup": round(base_s / fused_s, 3),
            "fused_regions": len(resnet_fuse["regions"]),
            "interior_bytes_saved": resnet_fuse["saved_bytes"],
        },
        "transformer_train": {
            "unfused_ms": round(tb_base_s * 1e3, 2),
            "fused_ms": round(tb_fused_s * 1e3, 2),
            "speedup": round(tb_base_s / tb_fused_s, 3),
            "fused_regions": len(tblock_fuse["regions"]),
            "interior_bytes_saved": tblock_fuse["saved_bytes"],
        },
        "cost_model": {
            "samples": n_samples,
            "holdout_groups": meta.get("n_holdout_groups"),
            "spearman_learned": meta.get("spearman_learned"),
            "spearman_analytic": meta.get("spearman_analytic"),
            "gate_ok": gate_ok,
            "search_ranker": res.ranker,
        },
        "quick": QUICK,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["fusion"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps({"fusion": results}))
    for arm in ("resnet_predict", "transformer_train"):
        if results[arm]["fused_regions"] < 1:
            raise SystemExit("bench_all --fusion: %s carved no regions"
                             % arm)
        if results[arm]["interior_bytes_saved"] <= 0:
            raise SystemExit("bench_all --fusion: %s saved no interior "
                             "bytes" % arm)
    print("[bench_all] fusion: resnet %.2f -> %.2f ms (%.3fx, %d regions)"
          ", tblock train %.2f -> %.2f ms (%.3fx, %d regions), learned "
          "gate_ok=%s ranker=%s"
          % (results["resnet_predict"]["unfused_ms"],
             results["resnet_predict"]["fused_ms"],
             results["resnet_predict"]["speedup"],
             results["resnet_predict"]["fused_regions"],
             results["transformer_train"]["unfused_ms"],
             results["transformer_train"]["fused_ms"],
             results["transformer_train"]["speedup"],
             results["transformer_train"]["fused_regions"],
             gate_ok, res.ranker), file=sys.stderr)
    return results


def bench_quantize():
    """--quantize: int8 end-to-end numbers (ISSUE 11), two halves.

    **Predict** — calibrate → quantize → serve on the bench resnet-style
    model: fp32 vs int8 predict throughput plus the top-1 agreement the
    accuracy budget is stated in.

    **Decode** — paged-KV generation at kv_dtype model/bf16/int8: decode
    tokens/s (informational on CPU QUICK; on-chip numbers next bench
    pass), token agreement vs the model-dtype decode, and the stable
    witnessed quantity, HBM-bytes-per-generated-token from the pool's
    byte model — the GATE asserts int8 at most 0.55x of bf16 (halved).

    Merges a "quantize" section into BENCH_ALL.json.
    """
    import time as _time

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import graph_pass
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel.transformer import TransformerParallel
    from mxnet_tpu.serving.generation import (GenerationConfig, Generator,
                                              SamplingParams)

    rng = np.random.RandomState(0)

    # ---------------------------------------------------------- predict
    layers, size, bs = (8, 16, 4) if QUICK else (50, 224, 16)
    steps = 10 if QUICK else 50
    sym = get_resnet(num_classes=10 if QUICK else 1000, num_layers=layers,
                     image_shape=(3, size, size))
    x = rng.rand(bs, 3, size, size).astype(np.float32)

    def build(spec):
        graph_pass.set_passes(spec)
        try:
            mod = mx.mod.Module(sym, context=_bench_ctx())
            mod.bind(data_shapes=[("data", x.shape)], for_training=False)
            mod.init_params(mx.init.Xavier())
            # an untrained net's logits are near-tied (argmax = noise);
            # scaling the classifier head emulates the class margins of
            # a trained checkpoint so top-1 agreement measures the
            # quantization error, not init degeneracy
            args, auxs = mod.get_params()
            args = dict(args)
            args["fc1_weight"] = args["fc1_weight"] * 8.0
            mod.set_params(args, auxs)
            return mod
        finally:
            graph_pass.set_passes(None)

    # agreement is judged on a few hundred rows — with one bs-row batch
    # the attainable values under 1.0 (e.g. 3/4) sit below any 99%
    # budget, so a single near-tie argmax flip would hard-fail the gate
    eval_rows = 64 if QUICK else 256
    eval_x = rng.rand(eval_rows, 3, size, size).astype(np.float32)

    def run(mod):
        it = lambda: NDArrayIter(x, None, batch_size=bs)  # noqa: E731
        mod.predict(it())  # compile + warm
        t0 = _time.perf_counter()
        for _ in range(steps):
            mod.predict(it())
        dt = (_time.perf_counter() - t0) / steps
        out = mod.predict(
            NDArrayIter(eval_x, None, batch_size=bs)).asnumpy()
        return dt, out

    fp32 = build("default")
    table = graph_pass.calibrate(
        fp32, [rng.rand(bs, 3, size, size).astype(np.float32)
               for _ in range(3)])
    fp32_s, fp32_out = run(fp32)
    graph_pass.set_calibration_table(table)
    try:
        q = build("default,quantize")
        q.set_params(*fp32.get_params())  # identical weights, both arms
        q_s, q_out = run(q)
    finally:
        graph_pass.set_calibration_table(None)
    ex = q._exec_group.execs[0]
    qinfo = (ex._opt.summary().get("quantize", {})
             if ex._opt is not None else {})
    top1 = float((fp32_out.argmax(1) == q_out.argmax(1)).mean())
    predict = {
        "protocol": "resnet%d %dx%d bs%d predict, %d timed iters" % (
            layers, size, size, bs, steps),
        "fp32_ms": round(fp32_s * 1e3, 2),
        "int8_ms": round(q_s * 1e3, 2),
        "speedup": round(fp32_s / q_s, 3),
        "images_per_s": {"fp32": round(bs / fp32_s, 1),
                         "int8": round(bs / q_s, 1)},
        "top1_agreement": round(top1, 4),
        "coverage": qinfo,
    }

    # ----------------------------------------------------------- decode
    # head_dim 64 (the realistic transformer regime): the int8 pools'
    # per-(position, head) fp32 scales amortize over head_dim, so toy
    # head dims would overstate the scale overhead the gate measures
    if QUICK:
        model_kw = dict(vocab=64, d_model=128, n_heads=2, n_layers=2,
                        d_ff=128, n_experts=2)
        max_batch, max_seq, max_new, n_req = 4, 64, 12, 8
    else:
        model_kw = dict(vocab=256, d_model=256, n_heads=4, n_layers=4,
                        d_ff=256, n_experts=2)
        max_batch, max_seq, max_new, n_req = 8, 256, 24, 24
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1),
                             ("dp",))
    model = TransformerParallel(mesh, **model_kw)
    params = model.init(seed=0)
    prompts = [[int(t) for t in rng.randint(1, model_kw["vocab"],
                                            size=int(p))]
               for p in rng.randint(2, max_seq - max_new, size=n_req)]
    mean_ctx = float(np.mean([len(p) + max_new / 2 for p in prompts]))

    def decode_arm(kv_dtype):
        gen = Generator(model, params,
                        GenerationConfig(max_batch=max_batch,
                                         max_seq=max_seq,
                                         kv_dtype=kv_dtype))
        try:
            gen.warmup()
            sp = SamplingParams(max_new_tokens=max_new)  # greedy
            t0 = _time.perf_counter()
            toks = [h.result(timeout=600)
                    for h in [gen.submit(p, sp) for p in prompts]]
            wall = _time.perf_counter() - t0
            n_tok = sum(len(t) for t in toks)
            return {"tokens_per_s": round(n_tok / wall, 1),
                    "hbm_bytes_per_token": gen.kv_read_bytes_per_token(
                        mean_ctx),
                    "bytes_per_cached_token": gen.pool.bytes_per_token,
                    "tokens": toks}
        finally:
            gen.stop()

    arms = {kv: decode_arm(kv) for kv in ("model", "bfloat16", "int8")}
    ref_tokens = arms["model"].pop("tokens")
    for kv in ("bfloat16", "int8"):
        toks = arms[kv].pop("tokens")
        pairs = [(a, b) for r, s in zip(ref_tokens, toks)
                 for a, b in zip(r, s)]
        arms[kv]["token_agreement"] = round(
            float(np.mean([a == b for a, b in pairs])), 4)
    bytes_ratio = (arms["int8"]["hbm_bytes_per_token"]
                   / max(1, arms["bfloat16"]["hbm_bytes_per_token"]))
    decode = {
        "protocol": ("causal LM %s, %d greedy requests, max_new=%d, "
                     "mean ctx %.0f tokens" % (model_kw, n_req, max_new,
                                               mean_ctx)),
        "arms": arms,
        "int8_vs_bf16_bytes_ratio": round(bytes_ratio, 3),
        "int8_vs_bf16_tokens_ratio": round(
            arms["int8"]["tokens_per_s"]
            / max(1e-9, arms["bfloat16"]["tokens_per_s"]), 3),
    }

    results = {"predict": predict, "decode": decode, "quick": QUICK}
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["quantize"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps({"quantize": results}))
    # hard gates: the stable witnessed quantities (wall-clock is
    # informational on CPU QUICK — the HBM story needs the chip)
    if top1 < 0.99:
        raise SystemExit("bench_all --quantize: predict top-1 agreement "
                         "%.4f < 0.99" % top1)
    if bytes_ratio > 0.55:
        raise SystemExit("bench_all --quantize: int8 bytes/token %.3fx "
                         "of bf16 (gate: <= 0.55)" % bytes_ratio)
    if arms["int8"]["token_agreement"] < 0.9:
        raise SystemExit("bench_all --quantize: int8 decode token "
                         "agreement %.4f < 0.9 documented tolerance"
                         % arms["int8"]["token_agreement"])
    print("[bench_all] quantize: predict %.3fx @ top1 %.3f; decode "
          "bytes/token %d (int8) vs %d (bf16), tokens/s ratio %.2fx"
          % (predict["speedup"], top1,
             arms["int8"]["hbm_bytes_per_token"],
             arms["bfloat16"]["hbm_bytes_per_token"],
             decode["int8_vs_bf16_tokens_ratio"]), file=sys.stderr)
    return results


def bench_input_pipeline(gate_ratio=None):
    """--input-pipeline: streaming pipeline vs the synchronous iterators
    (ISSUE 10 acceptance). Three measurements plus two hard guards:

    * iterator-only throughput — the MXNet-1.0 synchronous shape
      (serial decode under a depth-2 PrefetchingIter) vs the async
      streaming pipeline; the GATE is streaming >= 1.5x (the pooled
      synchronous variant is recorded for context);
    * fit-loop feed — a small conv net trained from each backend:
      img/s and host-stall % (time the training thread spends waiting
      on the iterator);
    * exactness + compile flatness — both backends must produce
      identical batch sequences, and the steady-state per-fit compile
      delta must not grow under streaming.

    Merges an "input_pipeline" section into BENCH_ALL.json.
    """
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import metrics as M
    from tools.io_smoke import build_rec

    obs.set_enabled(True)
    if gate_ratio is None:
        gate_ratio = float(os.environ.get("MXNET_IO_GATE_RATIO", "1.5"))
    # decode-bound geometry even under QUICK: the pipeline exists for
    # JPEG-decode-dominated feeds (224px ImageNet-style), not toy tiles
    n, size, bs = (160, 224, 16) if QUICK else (512, 224, 32)
    epochs = 2 if QUICK else 3
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_io_")
    # reclaimed on process exit (covers the SystemExit gate paths too):
    # repeated bench runs must not accumulate jpeg datasets in /tmp
    atexit.register(shutil.rmtree, tmp, ignore_errors=True)
    rec, idx = build_rec(os.path.join(tmp, "data"), n=n, size=size)
    shape = (3, size, size)

    def make(kind):
        if kind == "sync_serial":  # the MXNet-1.0 synchronous shape
            return mx.io.ImageRecordIter(rec, shape, bs, path_imgidx=idx,
                                         streaming=False,
                                         preprocess_threads=1,
                                         prefetch_buffer=2)
        if kind == "sync_pooled":  # pre-ISSUE-10 default (decode pool)
            return mx.io.ImageRecordIter(rec, shape, bs, path_imgidx=idx,
                                         streaming=False)
        return mx.io.ImageRecordIter(rec, shape, bs, path_imgidx=idx,
                                     streaming=True)

    def iter_throughput(kind):
        it = make(kind)
        try:
            for _ in it:  # warm epoch (page cache, pools, staging)
                pass
            rows = 0
            t0 = _time.perf_counter()
            for _ in range(epochs):
                it.reset()
                for b in it:
                    rows += bs - (b.pad or 0)
            return rows / (_time.perf_counter() - t0)
        finally:
            it.close()

    ips = {kind: iter_throughput(kind)
           for kind in ("sync_serial", "sync_pooled", "streaming")}

    # ---- exactness guard: identical batch sequences, sync vs streaming
    # (lockstep compare-and-discard: a full 224px epoch materialized
    # per arm would hold ~300 MB x2 of host RAM for the equality check)
    ref_it, got_it = make("sync_pooled"), make("streaming")
    try:
        sentinel = object()
        for i, (rb, gb) in enumerate(
                itertools.zip_longest(ref_it, got_it, fillvalue=sentinel)):
            if rb is sentinel or gb is sentinel:
                raise SystemExit("bench_all --input-pipeline: exactness "
                                 "guard failed: batch count diverged")
            if int(rb.pad or 0) != int(gb.pad or 0) or \
                    not np.array_equal(rb.data[0].asnumpy(),
                                       gb.data[0].asnumpy()) or \
                    not np.array_equal(rb.label[0].asnumpy(),
                                       gb.label[0].asnumpy()):
                raise SystemExit("bench_all --input-pipeline: exactness "
                                 "guard failed at batch %d" % i)
    finally:
        ref_it.close()
        got_it.close()

    # ---- fit-loop feed: img/s + host-stall %
    class _TimedIter:
        """Times next()/StopIteration on the consumer thread — the
        synchronous path's host-stall measurement."""

        def __init__(self, inner):
            self._it = inner
            self.wait_s = 0.0
            self.provide_data = inner.provide_data
            self.provide_label = inner.provide_label
            self.batch_size = inner.batch_size

        def __iter__(self):
            return self

        def __next__(self):
            t0 = _time.perf_counter()
            try:
                return next(self._it)
            finally:
                self.wait_s += _time.perf_counter() - t0

        next = __next__

        def reset(self):
            self._it.reset()

        def close(self):
            self._it.close()

    def build_net():
        x = mx.sym.Variable("data")
        x = mx.sym.Convolution(x, num_filter=16, kernel=(3, 3),
                               stride=(2, 2), name="c1")
        x = mx.sym.Activation(x, act_type="relu")
        x = mx.sym.Pooling(x, kernel=(2, 2), stride=(2, 2),
                           pool_type="max")
        x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=10,
                                  name="fc")
        return mx.sym.SoftmaxOutput(x, name="softmax")

    def fit_arm(kind):
        np.random.seed(5)
        mx.random.seed(5)
        it = _TimedIter(make(kind))
        mod = mx.mod.Module(build_net(), context=_bench_ctx())
        c0 = M.get_value("jit.compile_count", 0)
        t0 = _time.perf_counter()
        try:
            mod.fit(it, num_epoch=epochs, optimizer="sgd",
                    optimizer_params=(("learning_rate", 0.01),),
                    initializer=mx.init.Uniform(0.1))
        finally:
            it.close()
        wall = _time.perf_counter() - t0
        compiles = M.get_value("jit.compile_count", 0) - c0
        return {"img_per_s": round(epochs * n / wall, 1),
                "host_stall_pct": round(100.0 * it.wait_s / wall, 1),
                "compiles": compiles}

    fit_arm("sync_pooled")           # warm: model compiles once
    fit_sync = fit_arm("sync_pooled")
    fit_stream = fit_arm("streaming")
    if fit_stream["compiles"] > fit_sync["compiles"]:
        raise SystemExit(
            "bench_all --input-pipeline: streaming added XLA compiles "
            "(%d vs %d)" % (fit_stream["compiles"], fit_sync["compiles"]))

    ratio = ips["streaming"] / ips["sync_serial"]
    results = {
        "protocol": "%d %dx%d jpgs, bs%d, %d epochs (iterator-only "
                    "throughput; fit = conv net on %s)" % (
                        n, size, size, bs, epochs,
                        __import__("jax").devices()[0].platform),
        "iterator_img_per_s": {k: round(v, 1) for k, v in ips.items()},
        "streaming_vs_sync_serial": round(ratio, 3),
        "streaming_vs_sync_pooled": round(
            ips["streaming"] / ips["sync_pooled"], 3),
        "fit": {"sync": fit_sync, "streaming": fit_stream},
        "exactness": "identical batch sequences (sync == streaming)",
        "gate_ratio": gate_ratio,
        "quick": QUICK,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["input_pipeline"] = results
    tmp_path = out_path + ".tmp.%d" % os.getpid()
    with open(tmp_path, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp_path, out_path)
    print(json.dumps({"input_pipeline": results}))
    if ratio < gate_ratio:
        raise SystemExit(
            "bench_all --input-pipeline: streaming %.0f img/s is only "
            "%.2fx the synchronous iterator's %.0f img/s (gate %.1fx)"
            % (ips["streaming"], ratio, ips["sync_serial"], gate_ratio))
    print("[bench_all] input pipeline: %.0f -> %.0f img/s (%.2fx), fit "
          "host-stall %.1f%% -> %.1f%%, compiles flat"
          % (ips["sync_serial"], ips["streaming"], ratio,
             fit_sync["host_stall_pct"], fit_stream["host_stall_pct"]),
          file=sys.stderr)
    return results


def _perf_probe(steps=6, bs=64):
    """A short instrumented fit whose per-program predicted-vs-measured
    residuals ride the ledger row (observability.perf): the attribution
    registry fills from the fit loop's fenced step scopes, so the probe
    runs OUTSIDE the timed benches and cannot perturb their numbers.
    Returns (programs, last waterfall)."""
    import mxnet_tpu as mx
    from mxnet_tpu.observability import perf

    perf.reset()
    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    c1 = mx.sym.Activation(mx.sym.Convolution(
        data, kernel=(3, 3), num_filter=8, pad=(1, 1), name="pc1"),
        act_type="relu")
    p1 = mx.sym.Pooling(c1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    f1 = mx.sym.FullyConnected(mx.sym.Flatten(p1), num_hidden=64,
                               name="pf1")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Activation(f1, act_type="relu"), num_hidden=10, name="pf2"),
        name="softmax")
    x = rng.rand(bs * steps, 1, 16, 16).astype(np.float32)
    y = rng.randint(0, 10, bs * steps).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=bs, label_name="softmax_label")
    mod = mx.mod.Module(net, context=_bench_ctx())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.05),))
    programs = []
    for p in perf.program_table():
        programs.append({k: p[k] for k in (
            "graph", "mode", "flops", "hbm_bytes", "roofline_ms", "runs",
            "device_ms_ema", "device_ms_best", "mfu_pct", "hbm_util_pct",
            "residual")})
    return programs, perf.last_waterfall()


def _ledger_fingerprint():
    import platform
    import subprocess

    import jax

    fp = {"device": jax.devices()[0].device_kind,
          "platform": jax.default_backend(),
          "jax": jax.__version__,
          "python": sys.version.split()[0],
          "host": platform.node()}
    try:
        fp["git"] = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL, timeout=10).decode().strip()
    except Exception:
        pass
    return fp


def append_perf_ledger(results, path=None):
    """One append-only BENCH_LEDGER.jsonl row per bench run (ISSUE 13):
    env/device fingerprint, per-bench throughput + MFU (the transformer
    rows' MFU uses the SAME 6ND/spec-ceiling basis as BENCH_ALL.json's
    ``mfu_spec``), and predicted-vs-measured residual per program from
    a short instrumented probe fit — the dataset a learned cost model
    trains on.  Prints the regression verdict vs the previous
    comparable row."""
    import time as _time

    from mxnet_tpu.observability import perf

    here = os.path.dirname(os.path.abspath(__file__))
    path = path or os.path.join(here, "BENCH_LEDGER.jsonl")
    benches = {}
    for name, entry in results.get("configs", {}).items():
        if "error" in entry:
            benches[name] = {"error": entry["error"]}
            continue
        row = {"value": entry.get("value"), "unit": entry.get("unit")}
        if entry.get("mfu_spec") is not None:
            # same FLOP basis as BENCH_ALL.json mfu_spec, as a percent
            row["mfu_pct"] = round(100.0 * entry["mfu_spec"], 2)
            row["mfu_basis"] = "6ND / spec ceiling (cost_model.CEILINGS)"
        benches[name] = row
    try:
        programs, waterfall = _perf_probe()
    except Exception as err:
        traceback.print_exc()
        programs, waterfall = [], None
        benches["_perf_probe"] = {"error": repr(err)}
    row = {
        "ts": _time.strftime("%Y-%m-%dT%H:%M:%S"),
        "quick": QUICK,
        "fingerprint": _ledger_fingerprint(),
        "benches": benches,
        "programs": programs,
        "waterfall": waterfall,
    }
    perf.append_ledger(row, path)
    rows = perf.read_ledger(path)
    verdict = perf.ledger_verdict(rows)
    print("[bench_all] ledger row appended to %s (%d rows); verdict: %s"
          % (path, len(rows), json.dumps(verdict)), file=sys.stderr)
    return path, verdict


def bench_perf_overhead(threshold_pct=None):
    """--perf-overhead: gate the per-step cost of the roofline
    attribution layer (observability/perf.py).  Wall-clock A/B measures
    ambient noise larger than the effect (the PR 8/12 lesson), so the
    hard gate is on the stable quantities:

    * the steady-state step path performs ZERO cost walks — the
      analytic accounting is memoized per (program, shape signature)
      (witnessed: walk count flat across timed steps);
    * the full per-step perf work — scope begin, one fenced
      ``block_until_ready`` on already-ready outputs, the memo probe +
      attribution update, a data-wait and a kvstore note, scope end —
      measured per-call and taken as a percentage of the measured
      per-step wall of a small fit.

    Fails above ``threshold_pct`` (default 1%, env MXNET_PERF_GATE_PCT).
    """
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.observability import perf

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_PERF_GATE_PCT", "1.0"))
    rng = np.random.RandomState(0)

    # ---- the measured per-step wall of a small fused-train-step loop
    bs, steps = 128, (20 if QUICK else 60)
    data = mx.sym.Variable("data")
    fc1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, num_hidden=512, name="o1"), act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        fc1, num_hidden=16, name="o2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(), data_names=("data",))
    mod.bind(data_shapes=[("data", (bs, 64))],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(bs, 64).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 16, bs).astype(np.float32))])
    for _ in range(3):  # compile + warm
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    step_s = (time.perf_counter() - t0) / steps

    # ---- witness: steady-state steps pay ZERO cost walks
    ex = mod._exec_group.execs[0]
    prog = ex._prog if ex._train_prog is None else ex._train_prog
    perf.reset()
    perf.step_begin()
    mod.forward_backward(batch)
    mod.update()
    perf.step_end(step=0)
    walks_before = len(prog._perf_costs)
    n_check = 10
    for i in range(n_check):
        perf.step_begin()
        mod.forward_backward(batch)
        mod.update()
        perf.step_end(step=i + 1)
    walks = len(prog._perf_costs) - walks_before

    # ---- per-call cost of the full per-step perf work
    arg_d = ex._arg_datas(prog)
    aux_d = {n: ex.aux_dict[n]._data for n in prog.aux_names}
    outs = [o._data for o in ex.outputs]
    n = 5_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            perf.step_begin()
            jax.block_until_ready(outs)  # the fence, on ready outputs
            perf.note_program_run(prog.perf_cost(arg_d, aux_d, train=True),
                                  device_s=1e-6, host_s=1e-6)
            perf.note_data_wait(1e-9)
            perf.note_kv(1e-9)
            perf.step_end(step=i)
        best = min(best, (time.perf_counter() - t0) / n)
    perf.reset()

    pct = 100.0 * best / step_s
    result = {
        "per_step_cost_us": round(best * 1e6, 2),
        "step_ms": round(step_s * 1e3, 3),
        "steady_state_cost_walks": walks,
        "overhead_pct": round(pct, 4),
        "threshold_pct": threshold_pct,
        "protocol": ("full per-step perf work (scope + fence + memoized "
                     "attribution + waterfall record) per-call vs the "
                     "measured per-step wall of an MLP 64-512-16 bs%d "
                     "fused train step" % bs),
    }
    print("[bench_all] perf overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if walks:
        raise SystemExit(
            "bench_all --perf-overhead: %d cost walks on the steady-state "
            "step path — accounting must stay memoized per shape" % walks)
    if pct > threshold_pct:
        raise SystemExit(
            "bench_all --perf-overhead: perf layer costs %.3f%% per step "
            "(> %.2f%% gate) — attribution must stay cheap enough to "
            "leave on by default" % (pct, threshold_pct))
    print("[bench_all] perf-overhead gate passed (%.4f%% <= %.2f%%, 0 "
          "steady-state walks)" % (pct, threshold_pct), file=sys.stderr)
    return result


def bench_dist_obs_overhead(threshold_pct=None):
    """--dist-obs-overhead: gate the per-step cost of the
    distributed-training observability plane (observability/dist_trace)
    at < 1% of a fit step (docs/observability.md).  Wall-clock A/B of a
    2-process run measures network jitter far larger than the effect,
    so the gate is on the stable per-call quantities along the hot
    per-step path, summed and taken against the measured per-step wall
    of the same small fit --perf-overhead uses:

    * worker side: one ``sentinel_note`` (fingerprint build + policy
      check + transport call; no-op transport so the gate excludes the
      RPC the step already pays for its barrier) plus the rank stamp
      ``step_end`` adds to every waterfall record;
    * server side, per rank: two ``RoundTracker.note`` arrivals (the
      push round and the barrier round, metrics published) and one
      ``SentinelTracker.note`` cross-rank comparison against a peer.

    Report-time merge cost (``merge_steps`` + ``critical_path`` over a
    4-rank x 64-step fleet) is recorded but not gated — it runs in
    tools/dist_report.py, never on the step path.
    """
    import mxnet_tpu as mx
    from mxnet_tpu.observability import dist_trace, metrics

    if threshold_pct is None:
        threshold_pct = float(os.environ.get("MXNET_DIST_OBS_GATE_PCT",
                                             "1.0"))
    rng = np.random.RandomState(0)

    # ---- the measured per-step wall of a small fused-train-step loop
    bs, steps = 128, (20 if QUICK else 60)
    data = mx.sym.Variable("data")
    fc1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, num_hidden=512, name="o1"), act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        fc1, num_hidden=16, name="o2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(), data_names=("data",))
    mod.bind(data_shapes=[("data", (bs, 64))],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(bs, 64).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 16, bs).astype(np.float32))])
    for _ in range(3):  # compile + warm
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    mod.get_outputs()[0].asnumpy()
    step_s = (time.perf_counter() - t0) / steps

    # ---- per-call cost of the full per-step dist-obs work
    was_enabled = metrics.enabled()
    metrics.set_enabled(True)    # the realistic config: histograms live
    os.environ["MXNET_DIST_SENTINEL"] = "warn"
    dist_trace.set_rank(0)
    dist_trace.arm_sentinel(lambda fp: {"ok": True})
    rounds = dist_trace.RoundTracker()
    sentinel = dist_trace.SentinelTracker()
    # a steady peer one step behind: every note() does the real
    # cross-rank comparison (the match path — desyncs are exceptional)
    n = 5_000
    best = float("inf")
    try:
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(n):
                # worker side
                dist_trace.sentinel_note(i, grad_norm=1.0,
                                         param_norm=4.0, loss=0.5)
                # server side, this rank's share of the two rounds
                rounds.note("push", "w", 0, 2)
                rounds.note("push", "w", 1, 2)
                rounds.note("barrier", i, 0, 2)
                rounds.note("barrier", i, 1, 2)
                sentinel.note({"rank": 0, "step": i, "grad_norm": 1.0,
                               "param_norm": 4.0, "loss": 0.5})
                sentinel.note({"rank": 1, "step": i, "grad_norm": 1.0,
                               "param_norm": 4.0, "loss": 0.5})
            # both ranks' work was timed; a single rank's step pays half
            best = min(best, (time.perf_counter() - t0) / n / 2)
    finally:
        dist_trace.disarm_sentinel()
        rounds.unpublish()
        sentinel.unpublish()
        os.environ.pop("MXNET_DIST_SENTINEL", None)
        metrics.set_enabled(was_enabled)

    # ---- report-time merge cost (recorded, not gated)
    fleet = {r: [{"step": s, "rank": r, "wall_s": 0.1,
                  "data_wait_s": 0.01, "device_s": 0.07,
                  "kvstore_s": 0.01, "host_s": 0.01}
                 for s in range(64)] for r in range(4)}
    t0 = time.perf_counter()
    cp = dist_trace.critical_path(dist_trace.merge_steps(fleet))
    merge_s = time.perf_counter() - t0
    assert cp["steps"] == 64, cp

    pct = 100.0 * best / step_s
    result = {
        "per_step_cost_us": round(best * 1e6, 2),
        "step_ms": round(step_s * 1e3, 3),
        "merge_4x64_ms": round(merge_s * 1e3, 3),
        "overhead_pct": round(pct, 4),
        "threshold_pct": threshold_pct,
        "protocol": ("per-rank per-step dist-obs work (sentinel "
                     "fingerprint + 2 round arrivals + 1 cross-rank "
                     "compare, metrics on) per-call vs the measured "
                     "per-step wall of an MLP 64-512-16 bs%d fused "
                     "train step" % bs),
    }
    print("[bench_all] dist-obs overhead: %s" % json.dumps(result),
          file=sys.stderr)
    if pct > threshold_pct:
        raise SystemExit(
            "bench_all --dist-obs-overhead: dist observability costs "
            "%.3f%% per step (> %.2f%% gate) — straggler attribution "
            "and sentinels must stay cheap enough to leave on in "
            "production fleets" % (pct, threshold_pct))
    print("[bench_all] dist-obs-overhead gate passed (%.4f%% <= %.2f%%)"
          % (pct, threshold_pct), file=sys.stderr)
    return result


def bench_ingest_ledger():
    """--ingest-ledger: bulk-feed the learned cost model (ISSUE 20
    satellite).  Two free-data paths drain into the sample store:

    * committed ``BENCH_LEDGER.jsonl`` program rows (analytic
      flops/bytes + roofline vs measured device ms behind every
      residual the ledger has ever recorded),
    * accumulated ``MXNET_TUNE=1`` cache winners carrying a measured
      ``ms`` (idempotent back-fill — re-running never duplicates).

    Then retrains and REPORTS sample count + the holdout ranking gate.
    Reporting, not gating: a cold/thin dataset legitimately leaves the
    gate closed (ranking degrades to the analytic roofline by
    construction) — the artifact records how far from opening it is."""
    from mxnet_tpu.autotune import learned

    here = os.path.dirname(os.path.abspath(__file__))
    ledger_path = os.path.join(here, "BENCH_LEDGER.jsonl")
    before = learned.sample_count()
    from_ledger = learned.ingest_ledger(ledger_path) \
        if os.path.exists(ledger_path) else 0
    from_cache = learned.ingest_tune_cache()
    model = learned.train()
    meta = dict(model.meta) if model is not None else {}
    results = {
        "ledger_rows": from_ledger,
        "tune_cache_rows": from_cache,
        "samples_before": before,
        "samples": learned.sample_count(),
        "model_trained": model is not None,
        "gate_ok": bool(meta.get("gate_ok")),
        "holdout_groups": meta.get("n_holdout_groups"),
        "spearman_learned": meta.get("spearman_learned"),
        "spearman_analytic": meta.get("spearman_analytic"),
        "samples_path": learned.samples_path(),
    }
    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["cost_model_ingest"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps({"cost_model_ingest": results}))
    print("[bench_all] ingest-ledger: +%d ledger +%d tune-cache rows "
          "-> %d samples; gate %s (learned %s vs analytic %s over %s "
          "holdout groups)"
          % (from_ledger, from_cache, results["samples"],
             "OPEN" if results["gate_ok"] else "closed",
             results["spearman_learned"], results["spearman_analytic"],
             results["holdout_groups"]), file=sys.stderr)
    return results


#: --dist-train worker (written to a temp dir, launched via
#: tools/launch.py).  One fake-cluster fit per arm: jax.distributed is
#: wired BEFORE any computation, the steady-state epoch wall is the
#: measurement (first epoch = compile), and mesh arms report the ZeRO-1
#: shard bytes + collective-stamped waterfall the parent gates on.
_DIST_TRAIN_WORKER = r'''
import json
import os
import sys
import time

mode, outdir = sys.argv[1], sys.argv[2]
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1"
                           ).strip()
from mxnet_tpu.kvstore import _ensure_distributed

_ensure_distributed()

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.observability import metrics, perf

rank = int(os.environ["MXTPU_WORKER_ID"])
EPOCHS = int(os.environ["BENCH_DT_EPOCHS"])
BATCH = int(os.environ["BENCH_DT_BATCH"])
SAMPLES = int(os.environ["BENCH_DT_SAMPLES"])
DIM = int(os.environ["BENCH_DT_DIM"])
HID = int(os.environ["BENCH_DT_HID"])

net = mx.sym.Variable("data")
for i, h in enumerate((HID, HID, HID // 2)):
    net = mx.sym.FullyConnected(net, num_hidden=h, name="fc%%d" %% i)
    net = mx.sym.Activation(net, act_type="relu", name="act%%d" %% i)
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    net, num_hidden=8, name="fcout"), name="softmax")

rng = np.random.RandomState(7 + rank)     # per-rank shard
X = rng.rand(SAMPLES, DIM).astype(np.float32)
y = (rng.rand(SAMPLES) * 8).astype(np.float32)
it = mx.io.NDArrayIter(X, y, batch_size=BATCH, shuffle=False,
                       label_name="softmax_label")

np.random.seed(3)
mx.random.seed(3)
mod = mx.mod.Module(net, context=mx.cpu())
marks = [time.perf_counter()]
base_rpc = metrics.get_value("kvstore.rpc") or 0
mod.fit(it, num_epoch=EPOCHS, optimizer="sgd",
        optimizer_params=(("learning_rate", 0.01), ("momentum", 0.9)),
        initializer=mx.init.Uniform(0.1),
        kvstore="dist_async" if mode == "ps" else "mesh",
        epoch_end_callback=lambda *a: marks.append(time.perf_counter()))
steps = SAMPLES // BATCH
walls = [b - a for a, b in zip(marks[1:], marks[2:])]  # epoch 0 = compile
rpcs = (metrics.get_value("kvstore.rpc") or 0) - base_rpc
args, _ = mod.get_params()
section = {
    "rank": rank, "mode": mode, "steps_per_epoch": steps,
    "step_ms": min(walls) / steps * 1e3,
    "kvstore_rpcs": rpcs,
    # full (unsharded) momentum footprint: one fp32 slot per element
    "full_opt_bytes": int(sum(int(np.prod(v.shape)) * 4
                              for v in args.values())),
}
if mode != "ps":
    kvs = mod._kvstore
    section["opt_state_bytes"] = kvs.optimizer_state_bytes()
    stale = kvs.push_staleness()
    section["buckets"] = stale.get("buckets")
    section["bucket_bytes"] = stale.get("bucket_bytes")
    section["zero1"] = stale.get("zero1")
    rows = perf.waterfalls()
    section["waterfall_rows"] = len(rows)
    section["collective_rows"] = sum(
        1 for r in rows if r.get("collective"))
    kvs.close()
tmp = os.path.join(outdir, "%%s_rank%%d.json.tmp" %% (mode, rank))
with open(tmp, "w") as f:
    json.dump(section, f)
os.replace(tmp, os.path.join(outdir, "%%s_rank%%d.json" %% (mode, rank)))
print("DT_WORKER_OK mode=%%s rank=%%d" %% (mode, rank))
'''


def bench_dist_train():
    """--dist-train: the ISSUE 20 tentpole's perf claim, measured on a
    real fake cluster (``MXNET_MESH_PROCS`` processes, default 2).
    Three gradient-exchange arms run the same MLP fit:

    * ``ps`` — dist_async parameter server: every step is per-key
      push/pull RPC round-trips (pickled tensors over TCP),
    * ``collective`` — mesh kvstore, one huge bucket: a single fused
      in-program all-reduce per step, zero RPCs,
    * ``overlap`` — mesh kvstore, small buckets: early buckets'
      collectives dispatch while later grads are still being pushed.

    Hard gates: collective step wall <= ps step wall; mesh arms issue
    ZERO kvstore RPCs (the collapsed-kvstore-segment witness) with
    collective-stamped waterfall rows; ZeRO-1 per-rank optimizer bytes
    ~ full/N (sharding witness).  The overlap-vs-collective delta is
    recorded, not gated: on CPU the exchange is host-driven, so the
    bucketed win shows up at scale, not on a 2-proc smoke.  Merges a
    "dist_train" section into BENCH_ALL.json + one ledger row."""
    import tempfile

    try:
        from tools.launch import launch_local
    except ImportError:
        from launch import launch_local

    here = os.path.dirname(os.path.abspath(__file__))
    nprocs = int(os.environ.get("MXNET_MESH_PROCS", "2") or 2)
    outdir = tempfile.mkdtemp(prefix="mxdist_train_")
    script = os.path.join(outdir, "dt_worker.py")
    with open(script, "w") as f:
        f.write(_DIST_TRAIN_WORKER % {"repo": here})

    if QUICK:
        sizes = {"BENCH_DT_EPOCHS": "4", "BENCH_DT_BATCH": "32",
                 "BENCH_DT_SAMPLES": "128", "BENCH_DT_DIM": "128",
                 "BENCH_DT_HID": "256"}
        overlap_bytes = 64 << 10
    else:
        sizes = {"BENCH_DT_EPOCHS": "6", "BENCH_DT_BATCH": "64",
                 "BENCH_DT_SAMPLES": "512", "BENCH_DT_DIM": "256",
                 "BENCH_DT_HID": "512"}
        overlap_bytes = 256 << 10

    arms = [
        ("ps", {}, 1),
        # the scratch MXNET_TUNE_CACHE below keeps a user's tuned
        # dist.bucket_bytes from overriding the arm's explicit setting
        ("collective", {"MXNET_DIST_BUCKET_BYTES": str(1 << 30)}, 0),
        ("overlap", {"MXNET_DIST_BUCKET_BYTES": str(overlap_bytes)}, 0),
    ]
    per_arm = {}
    for mode, extra, num_servers in arms:
        env = {"MXNET_TELEMETRY": "1", "MXNET_DIST_SENTINEL": "off",
               "MXNET_TUNE_CACHE": os.path.join(outdir, "tuning.json")}
        env.update(sizes)
        env.update(extra)
        # children run on the CPU platform only (launch_local's default;
        # it refuses several TPU workers): a chip belongs to one process
        # and this parent may hold it
        procs = launch_local(
            nprocs, [sys.executable, script, mode, outdir],
            env_extra=env, num_servers=num_servers)
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=600)
                outs.append(out.decode())
        finally:
            for p in procs.ps_procs:
                p.terminate()
            for p in procs.ps_procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        if any(p.returncode != 0 or "DT_WORKER_OK" not in o
               for p, o in zip(procs, outs)):
            for r, text in enumerate(outs):
                sys.stdout.write("---- %s worker %d (rc=%s) ----\n%s\n"
                                 % (mode, r, procs[r].returncode, text))
            raise SystemExit("bench_all --dist-train: %s arm worker(s) "
                             "failed" % mode)
        sections = []
        for r in range(nprocs):
            with open(os.path.join(outdir,
                                   "%s_rank%d.json" % (mode, r))) as f:
                sections.append(json.load(f))
        per_arm[mode] = sections

    def _mean_ms(mode):
        return sum(s["step_ms"] for s in per_arm[mode]) / nprocs

    ps_ms = _mean_ms("ps")
    coll_ms = _mean_ms("collective")
    over_ms = _mean_ms("overlap")
    full_bytes = per_arm["collective"][0]["full_opt_bytes"]
    shard_bytes = [s["opt_state_bytes"] for s in per_arm["collective"]]
    results = {
        "protocol": "%d procs, MLP dim %s hid %s, bs %s, %s samples/rank,"
                    " steady-state epoch wall / %d steps" % (
                        nprocs, sizes["BENCH_DT_DIM"],
                        sizes["BENCH_DT_HID"], sizes["BENCH_DT_BATCH"],
                        sizes["BENCH_DT_SAMPLES"],
                        per_arm["ps"][0]["steps_per_epoch"]),
        "ps_step_ms": round(ps_ms, 3),
        "collective_step_ms": round(coll_ms, 3),
        "overlap_step_ms": round(over_ms, 3),
        "collective_vs_ps": round(ps_ms / coll_ms, 3),
        "overlap_vs_collective": round(coll_ms / over_ms, 3),
        "ps_rpcs": sum(s["kvstore_rpcs"] for s in per_arm["ps"]),
        "mesh_rpcs": sum(s["kvstore_rpcs"]
                         for m in ("collective", "overlap")
                         for s in per_arm[m]),
        "collective_buckets": per_arm["collective"][0]["buckets"],
        "overlap_buckets": per_arm["overlap"][0]["buckets"],
        "zero1": bool(per_arm["collective"][0]["zero1"]),
        "full_opt_bytes": full_bytes,
        "shard_opt_bytes": shard_bytes,
        "collective_rows": sum(s["collective_rows"]
                               for m in ("collective", "overlap")
                               for s in per_arm[m]),
        "quick": QUICK,
    }

    out_path = os.path.join(here, "BENCH_ALL.json")
    try:
        with open(out_path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = {}
    artifact["dist_train"] = results
    tmp = out_path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp, out_path)
    try:
        append_perf_ledger({"configs": {"dist_train": {
            "value": results["collective_vs_ps"],
            "unit": "x step-wall, fused collective vs PS push/pull "
                    "(%d procs)" % nprocs}}})
    except Exception:
        traceback.print_exc()
    print(json.dumps({"dist_train": results}))

    # ---- hard gates ---------------------------------------------------
    if results["ps_rpcs"] <= 0:
        raise SystemExit("bench_all --dist-train: the PS arm recorded "
                         "zero kvstore RPCs — the baseline is not "
                         "exercising the server path")
    if results["mesh_rpcs"] != 0:
        raise SystemExit(
            "bench_all --dist-train: mesh arms must issue ZERO kvstore "
            "RPCs, counted %d — the kvstore segment did not collapse "
            "into the program" % results["mesh_rpcs"])
    if results["collective_rows"] <= 0:
        raise SystemExit("bench_all --dist-train: no collective-stamped "
                         "waterfall rows on the mesh arms")
    if coll_ms > ps_ms:
        raise SystemExit(
            "bench_all --dist-train: fused collective step %.3f ms is "
            "SLOWER than PS push/pull %.3f ms — the in-program exchange "
            "must beat per-key RPC round-trips" % (coll_ms, ps_ms))
    if results["collective_buckets"] != 1 or \
            results["overlap_buckets"] < 2:
        raise SystemExit(
            "bench_all --dist-train: bucket plan wrong (collective=%s, "
            "overlap=%s) — the arms did not exercise fused vs bucketed "
            "exchange" % (results["collective_buckets"],
                          results["overlap_buckets"]))
    if not results["zero1"]:
        raise SystemExit("bench_all --dist-train: ZeRO-1 sharding was "
                         "not active on the mesh arms")
    shard_cap = full_bytes / nprocs * 1.1 + 4096  # bucket-pad slack
    if any(b > shard_cap for b in shard_bytes) or \
            not sum(shard_bytes) >= full_bytes * 0.9:
        raise SystemExit(
            "bench_all --dist-train: ZeRO-1 bytes witness failed — "
            "per-rank %r vs full %d (cap/rank %.0f): optimizer state is "
            "not sharded ~1/N" % (shard_bytes, full_bytes, shard_cap))
    print("[bench_all] dist-train: ps %.2f ms, collective %.2f ms "
          "(%.2fx), overlap %.2f ms (%.2fx vs collective, "
          "informational); mesh rpcs=0, zero1 bytes/rank %r of %d"
          % (ps_ms, coll_ms, results["collective_vs_ps"], over_ms,
             results["overlap_vs_collective"], shard_bytes, full_bytes),
          file=sys.stderr)
    return results


def assert_lint_clean():
    """--lint-clean: graftlint must exit 0 against the committed baseline
    AND finish inside a wall-time budget.

    Bench artifacts are the repo's perf claims; refusing to bench a tree
    with NEW static-analysis violations (hidden host syncs, retrace
    hazards, lock cycles — exactly what corrupts bench numbers) keeps
    the baseline from silently rotting. The wall gate
    (``MXNET_LINT_BUDGET_S``, default 30s) keeps the lint itself
    seconds-fast as the package grows — the whole-program lock/call
    graph phase is the part that scales, and ``--jobs`` keeps the
    per-file rule phase flat. Pure assertion: exits 0 on a clean tree."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    budget_s = float(os.environ.get("MXNET_LINT_BUDGET_S", "30"))
    jobs = os.cpu_count() or 1
    t0 = time.perf_counter()
    rc = subprocess.call(
        [sys.executable, "-m", "tools.graftlint", "mxnet_tpu", "tools",
         "--disable", "G003:tools/", "--jobs", str(min(jobs, 8)),
         "--baseline", os.path.join("tools", "graftlint", "baseline.json")],
        cwd=here)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SystemExit(
            "bench_all --lint-clean: graftlint found NEW violations "
            "(rc %d); fix them or baseline with a justification "
            "(docs/static_analysis.md)" % rc)
    if wall > budget_s:
        raise SystemExit(
            "bench_all --lint-clean: graftlint took %.1fs (> %.0fs "
            "budget, MXNET_LINT_BUDGET_S) — the analyzer must stay "
            "seconds-fast; profile the new rule or raise --jobs"
            % (wall, budget_s))
    print("[bench_all] graftlint clean against committed baseline "
          "(%.1fs, budget %.0fs)" % (wall, budget_s), file=sys.stderr)


def main(out_path=None, skip=(), quiet=False, telemetry=False):
    import jax

    if telemetry:
        _start_telemetry()
    import mxnet_tpu as mx

    mx.config.enable_compile_cache()
    dev = _bench_ctx().jax_device()
    results = {"device": dev.device_kind, "platform": dev.platform,
               "device_count": jax.local_device_count(),
               "quick": QUICK, "configs": {}}
    for name, fn in BENCHES:
        if name in skip:
            continue
        try:
            entry, wall = _timed(fn)
            entry["bench_wall_s"] = round(wall, 1)
            results["configs"][name] = entry
            print("[bench_all] %s: %s %s" % (name, entry["value"],
                                             entry["unit"]), file=sys.stderr)
        except Exception as err:  # record, don't abort the artifact
            traceback.print_exc()
            results["configs"][name] = {"error": repr(err)}
    if telemetry:
        try:
            _collect_telemetry(results)
        except Exception as err:
            traceback.print_exc()
            results["telemetry"] = {"error": repr(err)}
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_ALL.json")
    with open(out_path, "w") as sink:
        json.dump(results, sink, indent=1)
    try:
        # one append-only ledger row per run (ISSUE 13) — the bench
        # trajectory tools/perf_report.py --ledger diffs and CI gates on
        append_perf_ledger(results)
    except Exception:
        traceback.print_exc()
    print(json.dumps(results), file=sys.stderr if quiet else sys.stdout)
    return results


if __name__ == "__main__":
    if "--lint-clean" in sys.argv[1:]:
        # standalone smoke: assert the committed tree is graftlint-clean
        # and exit without benching (CI/driver guard; seconds, no TPU)
        assert_lint_clean()
    elif "--health-overhead" in sys.argv[1:]:
        # standalone gate: warn-mode health checking must cost <= 2% per
        # step on the transformer microbench (docs/health.md)
        bench_health_overhead()
    elif "--resilience-overhead" in sys.argv[1:]:
        # standalone gate: faults-disabled injection points + deadline
        # checks must cost < 1% of a serving request (docs/resilience.md)
        bench_resilience_overhead()
    elif "--obs-overhead" in sys.argv[1:]:
        # standalone gate: request tracing (on AND sampled-out) must
        # cost < 1% of a serving request (docs/observability.md)
        bench_obs_overhead()
    elif "--ts-overhead" in sys.argv[1:]:
        # standalone gate: the time-series sampler and the fleet scrape
        # loop must each occupy < 1% of their sampling interval
        # (docs/observability.md)
        bench_ts_overhead()
    elif "--perf-overhead" in sys.argv[1:]:
        # standalone gate: the roofline-attribution layer (fenced split,
        # memoized cost accounting, waterfall records) must cost < 1% of
        # a fit step on the stable quantities (docs/perf_observability.md)
        bench_perf_overhead()
    elif "--dist-obs-overhead" in sys.argv[1:]:
        # standalone gate: per-step straggler attribution + divergence
        # sentinels must cost < 1% of a fit step on the stable per-call
        # quantities (docs/observability.md)
        bench_dist_obs_overhead()
    elif "--autotune" in sys.argv[1:]:
        # tuned-vs-default on the autotuner's three knob families +
        # the warm-cache (<1%/step) overhead gate (docs/autotune.md);
        # merges an "autotune" section into BENCH_ALL.json
        bench_autotune()
    elif "--graph-passes" in sys.argv[1:]:
        # optimized-vs-unoptimized inference under the default pass
        # pipeline (node-count reduction is a hard gate; latency is
        # recorded); merges a "graph_passes" section into BENCH_ALL.json
        bench_graph_passes()
    elif "--fusion" in sys.argv[1:]:
        # fused-vs-unfused step time (regions > 0, interior bytes
        # saved, parity are the CPU-stable gates) + the learned cost
        # model's ranking-quality/degradation contract — merges a
        # "fusion" section into BENCH_ALL.json (docs/fusion.md)
        bench_fusion()
    elif "--quantize" in sys.argv[1:]:
        # int8 PTQ predict (throughput + top-1 agreement gate) and
        # int8 paged-KV decode (HBM-bytes-per-token halved vs bf16 is
        # the gate; tokens/s recorded) — merges a "quantize" section
        # into BENCH_ALL.json (docs/quantization.md)
        bench_quantize()
    elif "--generation-speculative" in sys.argv[1:]:
        # speculative decoding on a high-acceptance (memorized cyclic)
        # workload: >= 1.3x tokens/s over non-speculative continuous
        # batching is the gate; acceptance rate + tokens-per-verify
        # histogram recorded (docs/generation.md) — merges a
        # "generation_speculative" section into BENCH_ALL.json
        bench_generation_speculative()
    elif "--control" in sys.argv[1:]:
        # serving control plane: prefix-cache TTFT cold-vs-warm on a
        # shared-prefix Poisson workload + SLO overtake-without-
        # starvation witness (docs/serving_control.md) — merges a
        # "control" section into BENCH_ALL.json + one ledger row
        bench_control()
    elif "--ingest-ledger" in sys.argv[1:]:
        # bulk-feed the learned cost model: BENCH_LEDGER.jsonl program
        # residuals + MXNET_TUNE=1 cache measurements drain into the
        # sample store, retrain, report sample count + gate status
        # (reporting, not gating) — merges a "cost_model_ingest"
        # section into BENCH_ALL.json
        bench_ingest_ledger()
    elif "--dist-train" in sys.argv[1:]:
        # collectives-backed sharded training on a fake cluster: PS
        # push/pull vs fused collective vs bucketed-overlap step walls
        # (collective <= ps is the hard gate; overlap delta recorded),
        # zero-RPC + collective-waterfall witnesses, ZeRO-1 ~1/N
        # optimizer-bytes witness (docs/distributed.md) — merges a
        # "dist_train" section into BENCH_ALL.json + one ledger row
        bench_dist_train()
    elif "--input-pipeline" in sys.argv[1:]:
        # streaming vs synchronous input pipeline: >=1.5x iterator
        # throughput gate, fit-loop img/s + host-stall %, exactness +
        # compile-flatness guards (docs/data_pipeline.md); merges an
        # "input_pipeline" section into BENCH_ALL.json
        bench_input_pipeline()
    else:
        done = main(telemetry="--telemetry" in sys.argv[1:])
        failed = sorted(name for name, entry in done["configs"].items()
                        if "error" in entry)
        if failed:
            # the artifact records every config, failed ones included;
            # the exit code says the run is not a clean measurement
            sys.exit("bench_all: %d config(s) recorded an error: %s"
                     % (len(failed), ", ".join(failed)))

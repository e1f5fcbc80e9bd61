#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user
calls, at the full width of the models the repo supports (depth is what it
is; weights are random, made from a seed), and checks what comes out by the
repo's own means. One process, all local chips, no arguments:

    python chip_smoke.py

Legs (every leg runs even after another failed; exit 0 only if all passed):

1. ``resnet50_sharded``     the ResNet cells' path: ResNet-50 NHWC
                            bf16 through ``ShardedTrainer`` over a dp mesh.
2. ``resnet50_module_fit``  the entry point users call: ``mx.mod.Module(...,
                            context=[mx.tpu(i)...]).fit`` over an NDArrayIter.
3. ``lm_train_flash``       the causal LM through ``TransformerParallel.
                            step_fn`` with the Pallas flash kernels, plus an
                            on-chip parity check of the kernels vs dense.
4. ``fused_kernels``        ``fused_matmul``/``fused_batch_matmul`` at the
                            shapes ResNet-50 NHWC produces: compiled and
                            matching the reference, or statically declined.
5. ``generate``             checkpoint -> ``Generator`` -> eight requests,
                            compared with a full-recompute forward.
6. ``device_trace``         ``mx.profiler`` around two steps of leg 1; the
                            written .xplane.pb has a TPU plane with events.

Output: one JSON header line, one JSON line per leg, and as the LAST line
of stdout ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": n}}``. Without a TPU — or on a ``device_kind`` that has no row in
``mxnet_tpu.context.DEVICE_PEAKS`` — it names what it found and exits
non-zero without a result; nothing on its path falls back to the CPU, the
Pallas interpreter or a reference formula. The only other mode is the
rehearsal chosen by argument, never inferred from a missing chip:

    python chip_smoke.py --dryrun     # tiny sizes, CPU, kernels interpreted

which prints ``"dryrun": true`` everywhere and can never print the pass line.
``--legs a,b`` runs a subset while debugging (a subset is not a pass either).
"""
import argparse
import glob
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

LEGS = ("resnet50_sharded", "resnet50_module_fit", "lm_train_flash",
        "fused_kernels", "generate", "device_trace")


# --------------------------------------------------------------- sizes
def sizes(dryrun):
    """Everything a leg is sized by. The chip column is the full width
    of each model; the dryrun column only rehearses the control flow."""
    if dryrun:
        return dict(
            resnet=dict(num_classes=10, num_layers=8,
                        image_shape=(3, 28, 28)),
            image=28, per_chip=4, classes=10, dtype="float32", lr=0.05,
            lm=dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                    n_experts=1),
            lm_batch=(4, 128), lm_dtype="float32",
            parity=(1, 2, 128, 16), parity_dtype="float32", parity_tol=2e-5,
            fused=[(64, 32, 48, "conv"), (48, 64, 16, "fc")],
            fused_bmm=(2, 32, 16, 32),
            fused_dtype="float32", fused_tol=2e-5,
            gen=dict(max_batch=4, max_seq=64, prefill_buckets=(16, 32, 64),
                     page_size=8),
            gen_prompts=(5, 11, 23, 40), gen_new=6, gen_agree=1.0,
            gen_tie=0.0)
    return dict(
        resnet=dict(num_classes=1000, num_layers=50),
        image=224, per_chip=32, classes=1000, dtype="bfloat16", lr=0.1,
        # a 59M LM at full width (the pre-chip rounds' transformer)
        lm=dict(vocab=32768, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
                n_experts=1),
        lm_batch=(8, 2048), lm_dtype="bfloat16",
        parity=(1, 8, 1024, 64), parity_dtype="bfloat16", parity_tol=2e-2,
        # (M, K, N) of the 1x1 stride-1 convs (with the bottleneck's
        # residual add) and the classifier (FullyConnected: bias + act,
        # (N, K) weight) of ResNet-50 NHWC at bs32 and bs8 — M=392 (stage
        # 4 at bs8) and N=1000 are the two the old tile rule got wrong
        fused=[(m, k, n, "conv") for m, k, n in (
            (100352, 64, 256), (100352, 64, 64), (100352, 256, 64),
            (100352, 256, 128), (25088, 128, 512), (25088, 512, 128),
            (25088, 512, 256), (6272, 256, 1024), (6272, 1024, 256),
            (6272, 1024, 512), (1568, 512, 2048), (1568, 2048, 512),
            (25088, 64, 256), (6272, 128, 512), (1568, 256, 1024),
            (392, 512, 2048), (392, 2048, 512))]
        + [(32, 2048, 1000, "fc"), (8, 2048, 1000, "fc")],
        fused_bmm=(64, 512, 64, 512), fused_dtype="bfloat16", fused_tol=2e-2,
        gen=dict(max_batch=8, max_seq=2048, prefill_buckets=(128, 512, 2048)),
        gen_prompts=(100, 300, 500, 700, 900, 1100, 1300, 1500), gen_new=32,
        # bf16 on the chip breaks token-exactness at near-ties of the
        # reference: measured 0.9883 teacher-forced agreement (253 of 256
        # tokens, all first tokens exact), worst disagreement 0.0097
        # logits below the reference's argmax (PR 21, CHANGES.md). The
        # bound asserted instead: agreement, and every miss a near-tie
        gen_agree=0.95, gen_tie=0.05)


# ------------------------------------------------------------ utilities
def _rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _need(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _devices(env):
    """The devices every leg runs on, resolved through the user-facing
    contexts: ``mx.tpu(i)`` (raises without a chip); ``mx.gpu(i)`` — the
    documented CPU-harness alias — only in the dryrun."""
    import mxnet_tpu as mx

    ctx = mx.gpu if env["dryrun"] else mx.tpu
    return [ctx(i) for i in range(env["n"])]


def _compiles():
    from mxnet_tpu.observability import metrics as M

    return M.get_value("jit.compile_count", 0)


def _cross_entropy(probs, labels):
    import numpy as np

    p = np.asarray(probs, np.float32)
    idx = np.asarray(labels).astype(np.int64)
    return float(-np.mean(np.log(p[np.arange(len(idx)), idx] + 1e-30)))


# ------------------------------------------------------------------ legs
def leg_resnet50_sharded(env, shared):
    import jax
    import numpy as np

    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    sz, n = env["sz"], env["n"]
    devices = [c.jax_device() for c in _devices(env)]
    mesh = make_mesh({"dp": n}, devices=devices)
    symbol = get_resnet(layout="NHWC", **sz["resnet"])
    trainer = ShardedTrainer(
        symbol, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": sz["lr"], "momentum": 0.9,
                          "wd": 1e-4},
        dtype=np.dtype(sz["dtype"]))
    batch_size = sz["per_chip"] * n
    data_shape = (batch_size, sz["image"], sz["image"], 3)
    state = trainer.init({"data": data_shape,
                          "softmax_label": (batch_size,)})
    rng = np.random.RandomState(0)
    label = rng.randint(0, sz["classes"], batch_size).astype(np.float32)
    batch = trainer.shard_batch({
        "data": rng.uniform(0, 1, data_shape).astype(np.float32),
        "softmax_label": label})

    # five step calls and one multi_step(5); each step's loss is the
    # cross-entropy of ITS forward, i.e. of the parameters before its
    # update — the last step call reads the loss after all ten updates
    losses = []

    def step():
        nonlocal state
        state, outs = trainer.step(state, batch)
        losses.append(_cross_entropy(outs[0], label))
        return _compiles()

    after_first = step()
    for _ in range(3):
        before_scan = step()
    state, scan_out = trainer.multi_step(state, batch, 5)
    scan_out = np.asarray(jax.block_until_ready(scan_out))
    after_scan = _compiles()
    after_last = step()
    _need(np.isfinite(losses).all() and np.isfinite(scan_out).all(),
          "non-finite loss: %s / %s" % (losses, scan_out))
    _need(losses[-1] < losses[0],
          "loss did not fall over ten updates: %s" % losses)
    _need(after_first == before_scan and after_scan == after_last,
          "step recompiled after its first call: compile counts %s"
          % [after_first, before_scan, after_scan, after_last])
    want = set(mesh.devices.flat)
    for name, arr in state["params"].items():
        have = {s.device for s in arr.addressable_shards}
        _need(have == want, "%s lives on %s, mesh is %s"
              % (name, sorted(map(str, have)), sorted(map(str, want))))
    facts = {"losses": [round(v, 4) for v in losses],
             "global_batch": batch_size, "params": len(state["params"])}
    if n > 1:
        facts.update(_multichip_facts(env, trainer, state, batch, devices))
    shared["trainer"] = (trainer, state, batch)
    return facts


def _multichip_facts(env, trainer, state, batch, devices):
    """dp>1: the compiled step all-reduces, and nothing piles on chip 0."""
    hlo = trainer.lower_step(state, batch).compile().as_text()
    _need("all-reduce" in hlo, "no all-reduce in the compiled dp step")
    if env["dryrun"]:
        return {"all_reduce": True}  # the CPU backend has no memory_stats
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    spread = (max(in_use) - min(in_use)) / max(in_use)
    _need(spread < 0.05, "device memory uneven: %s" % in_use)
    return {"all_reduce": True, "bytes_in_use": in_use,
            "memory_spread": round(spread, 4)}


def leg_resnet50_module_fit(env, shared):
    import numpy as np

    import mxnet_tpu as mx

    sz, n = env["sz"], env["n"]
    ctxs = _devices(env)
    batch_size = sz["per_chip"] * n
    rng = np.random.RandomState(1)
    shape = (4 * batch_size, 3, sz["image"], sz["image"])
    train = mx.io.NDArrayIter(
        rng.uniform(0, 1, shape).astype(np.float32),
        rng.randint(0, sz["classes"], shape[0]).astype(np.float32),
        batch_size=batch_size)
    mod = mx.mod.Module(mx.models.get_resnet(**sz["resnet"]), context=ctxs)
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                              "wd": 1e-4},
            kvstore="device", eval_metric="acc")
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    _need(all(np.isfinite(v).all() for v in after.values()),
          "non-finite parameter after fit")
    moved = sum(not np.array_equal(before[k], after[k]) for k in after)
    _need(moved == len(after), "only %d of %d parameters changed"
          % (moved, len(after)))
    # outputs and parameters live where the contexts say
    want = env["platform"]
    execs = mod._exec_group.execs
    for ctx, ex in zip(ctxs, execs):
        held = list(ex.arg_dict.values()) + list(ex.outputs)
        for arr in held:
            devs = arr._data.devices()
            _need(devs == {ctx.jax_device()} and
                  all(d.platform == want for d in devs),
                  "array on %s, context is %s" % (devs, ctx))
    regions = execs[0].fused_regions()
    by_reason = {}
    for r in regions:
        key = r["lowering"] + (": " + r["reason"] if r["reason"] else "")
        by_reason[key] = by_reason.get(key, 0) + 1
    return {"params_changed": moved, "executors": len(execs),
            "fused_regions": len(regions), "fused_lowering": by_reason,
            "fused_example": regions[0]["members"] if regions else None}


def _lm_steps(env, axes, devices, n_steps=3):
    """``n_steps`` of the LM through step_fn on one mesh; returns
    (facts, model, params)."""
    import numpy as np

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.transformer import TransformerParallel

    sz = env["sz"]
    mesh = make_mesh(axes, devices=devices)
    model = TransformerParallel(mesh, dtype=np.dtype(sz["lm_dtype"]),
                                **sz["lm"])
    params = model.init(0)
    B, T = sz["lm_batch"]
    tok = np.random.RandomState(0).randint(
        0, sz["lm"]["vocab"], (B, T)).astype(np.int32)
    tok, tgt = model.shard_batch(tok, np.roll(tok, -1, axis=1))
    step = model.step_fn(lr=0.01)
    # the program the step compiles, read BEFORE it runs: on the chip it
    # must hold the flash kernels — forward and the fused backward per
    # layer (one pair per ring step under sp) — and never the dense
    # formula. Counted in the COMPILED text: the layers share one lowered
    # function per kernel, which XLA inlines at every call
    hlo = model._step_jit.lower(params, tok, tgt, 0.01).compile().as_text()
    mosaic = hlo.count("tpu_custom_call")
    losses = []
    before = None
    for i in range(n_steps):
        params, loss = step(params, tok, tgt)
        losses.append(float(loss))
        if i == 0:
            before = _compiles()
    _need(np.isfinite(losses).all(), "non-finite LM loss %s" % losses)
    _need(losses[-1] < losses[0], "LM loss did not fall: %s" % losses)
    _need(_compiles() == before, "LM step recompiled")
    layers = sz["lm"]["n_layers"]
    if env["dryrun"]:
        _need(mosaic == 0, "dryrun lowered %d Mosaic calls" % mosaic)
    else:
        ring = dict(axes).get("sp", 1)
        _need(mosaic == 2 * layers * ring,
              "%d Mosaic calls in the step, expected %d (fwd, fused bwd "
              "x %d layers x %d ring steps)"
              % (mosaic, 2 * layers * ring, layers, ring))
    return ({"mesh": dict(axes), "losses": [round(v, 4) for v in losses],
             "mosaic_calls": mosaic}, model, params)


def leg_lm_train_flash(env, shared):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.flash_attention import flash_attention
    from mxnet_tpu.parallel.ring_attention import attention_reference

    sz, n = env["sz"], env["n"]
    devices = [c.jax_device() for c in _devices(env)]
    facts, model, params = _lm_steps(env, {"dp": n}, devices)
    shared["lm"] = (model, params)
    facts = {"dp": facts}
    if n == 4:
        # flash under shard_map, and ring attention with the per-step
        # flash kernel
        facts["dp2_tp2"] = _lm_steps(env, {"dp": 2, "tp": 2}, devices)[0]
        facts["sp4"] = _lm_steps(env, {"sp": 4}, devices)[0]

    # parity of the kernels themselves against the dense formula, here
    B, H, T, D = sz["parity"]
    dt = np.dtype(sz["parity_dtype"])
    q, k, v = (jax.device_put(
        np.random.RandomState(i).randn(B, H, T, D).astype(dt), devices[0])
        for i in range(3))
    interpret = env["dryrun"]

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def dense_loss(q, k, v):
        out = attention_reference(*(a.astype(jnp.float32)
                                    for a in (q, k, v)), causal=True)
        return jnp.sum(out ** 2), out

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))
    g_flash, o_flash = grad(flash_loss)(q, k, v)
    g_dense, o_dense = grad(dense_loss)(q, k, v)
    errs = {"out": _rel_err(o_flash, o_dense)}
    for name, a, b in zip(("dq", "dk", "dv"), g_flash, g_dense):
        errs[name] = _rel_err(a, b)
    _need(all(np.isfinite(e) and e <= sz["parity_tol"]
              for e in errs.values()),
          "flash vs dense beyond %g: %s" % (sz["parity_tol"], errs))
    facts["parity_rel_err"] = {k: float("%.3g" % e) for k, e in errs.items()}
    return facts


def leg_fused_kernels(env, shared):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.fused import (fused_batch_matmul, fused_matmul,
                                          kernel_plan)

    sz = env["sz"]
    dt = np.dtype(sz["fused_dtype"])
    dev = _devices(env)[0].jax_device()
    interpret = env["dryrun"]
    rng = np.random.RandomState(0)

    def put(*shape):
        return jax.device_put((rng.randn(*shape) / 8).astype(dt), dev)

    compiled, declined, worst = [], {}, 0.0
    for M, K, N, kind in sz["fused"]:
        fc = kind == "fc"
        epilogue = ((("bias",), ("act", "relu")) if fc
                    else (("res", "elemwise_add"),))
        extra_shape = (N,) if fc else (M, N)
        tiles, why = kernel_plan(M, N, K, dt, epilogue, [extra_shape],
                                 interpret=interpret)
        tag = "M%d K%d N%d" % (M, K, N)
        if tiles is None:
            declined[tag] = why  # a static rule, named; nothing was run
            continue
        x, w, e = put(M, K), put(*((N, K) if fc else (K, N))), put(
            *extra_shape)
        got = jax.jit(lambda x, w, e: fused_matmul(
            x, w, extras=[e], epilogue=epilogue, wt=fc,
            interpret=interpret))(x, w, e)

        def ref(x, w, e):
            y = jnp.dot(x.astype(jnp.float32),
                        (w.T if fc else w).astype(jnp.float32),
                        precision="highest") + e.astype(jnp.float32)
            return jnp.maximum(y, 0.0) if fc else y

        err = _rel_err(got, jax.jit(ref)(x, w, e))
        _need(err <= sz["fused_tol"], "%s tiles %s: rel err %g"
              % (tag, tiles, err))
        worst = max(worst, err)
        compiled.append("%s %s" % (tag, "x".join(map(str, tiles))))
    B, M, K, N = sz["fused_bmm"]
    epilogue = (("scalar", "_mul_scalar", 0.125), ("res", "elemwise_add"))
    x, w, r = put(B, M, K), put(B, K, N), put(B, M, N)
    got = jax.jit(lambda x, w, r: fused_batch_matmul(
        x, w, extras=[r], epilogue=epilogue, interpret=interpret))(x, w, r)
    _need(got is not None, "fused_batch_matmul declined %s" % (sz["fused_bmm"],))
    want = jax.jit(lambda x, w, r: jnp.einsum(
        "bmk,bkn->bmn", x.astype(jnp.float32), w.astype(jnp.float32),
        precision="highest") * 0.125 + r.astype(jnp.float32))(x, w, r)
    err = _rel_err(got, want)
    _need(err <= sz["fused_tol"], "batch matmul rel err %g" % err)
    if not env["dryrun"]:
        _need(compiled and declined, "expected both compiled and "
              "declined shapes: %s / %s" % (compiled, declined))
    return {"compiled": compiled, "declined": declined,
            "batch_matmul": "B%d M%d K%d N%d" % (B, M, K, N),
            "worst_rel_err": float("%.3g" % max(worst, err))}


def leg_generate(env, shared):
    import jax
    import numpy as np

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.transformer import TransformerParallel
    from mxnet_tpu.serving.generation import (GenerationConfig, Generator,
                                              SamplingParams)

    sz = env["sz"]
    dev = _devices(env)[0].jax_device()
    mesh = make_mesh({"dp": 1}, devices=[dev])
    serve = TransformerParallel(mesh, dtype=np.dtype(sz["lm_dtype"]),
                                **sz["lm"])
    # the training-to-serving handoff: leg 3's parameters through a
    # checkpoint file (seeded fresh ones if leg 3 left none)
    trained, params = shared.get("lm") or (serve, serve.init(0))
    ckpt = os.path.join(env["tmp"], "lm_ckpt.npz")
    trained.save_checkpoint(params, ckpt)
    gen = Generator.from_checkpoint(ckpt, serve,
                                    config=GenerationConfig(**sz["gen"]))
    try:
        warmed = gen.warmup()
        _need(warmed == len(sz["gen"]["prefill_buckets"]) + 1,
              "warmup compiled %d programs" % warmed)
        _need(gen._donating == (not env["dryrun"]),
              "donation %s" % gen._donating)
        after_warmup = _compiles()
        rng = np.random.RandomState(7)
        prompts = [[int(t) for t in rng.randint(1, sz["lm"]["vocab"], n)]
                   for n in sz["gen_prompts"]]
        handles = [gen.submit(p, SamplingParams(
            max_new_tokens=sz["gen_new"])) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        _need(_compiles() == after_warmup,
              "%d compiles under traffic" % (_compiles() - after_warmup))
    finally:
        gen.stop(drain=True)
    gen.pool.assert_no_leaks()
    _need(all(len(o) == sz["gen_new"] for o in outs),
          "short outputs: %s" % [len(o) for o in outs])

    # the repo's own contract (verify surface 4): greedy output equals a
    # full recompute. ONE causal forward over prompt + generated tokens
    # gives, at position len(prompt)-1+j, the logits the recompute loop
    # would see before emitting token j — all 32 steps of the loop at once
    T = sz["gen"]["max_seq"]
    toks = np.zeros((len(prompts), T), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p) + len(o)] = p + o
    n_new = sz["gen_new"]

    def recompute(params, toks, starts):
        logits = serve.prefill_forward(params, toks)[0]
        return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
            row, s, n_new, axis=0))(logits, starts)

    starts = np.asarray([len(p) - 1 for p in prompts], np.int32)
    logits = np.asarray(jax.jit(recompute)(
        serve.load_checkpoint(ckpt), jax.device_put(toks, dev), starts))
    agree, first, margins = [], [], []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i]
        ref = rows.argmax(-1)
        hit = ref == np.asarray(o)
        agree.append(float(hit.mean()))
        first.append(bool(hit[0]))
        for j in np.nonzero(~hit)[0]:
            # a disagreement should be a near-tie of the reference
            margins.append(float(rows[j, ref[j]] - rows[j, o[j]]))
    mean_agree = float(np.mean(agree))
    _need(mean_agree >= sz["gen_agree"]
          and all(m <= sz["gen_tie"] for m in margins),
          "greedy agreement with full recompute %.4f (bound %.2f, %s), "
          "disagreement margins %s (bound %g)"
          % (mean_agree, sz["gen_agree"], agree, margins, sz["gen_tie"]))
    return {"warmed_programs": warmed, "donating": gen._donating,
            "requests": len(prompts), "new_tokens": sz["gen_new"],
            "token_agreement": round(mean_agree, 4),
            "per_request": [round(a, 3) for a in agree],
            "first_token_exact": sum(first),
            "worst_disagreement_margin": (round(max(margins), 4)
                                          if margins else None)}


def leg_device_trace(env, shared):
    import jax

    import mxnet_tpu as mx

    _need("trainer" in shared, "leg resnet50_sharded left no trainer")
    trainer, state, batch = shared["trainer"]
    base = os.path.join(env["tmp"], "profile.json")
    mx.profiler.profiler_set_config(mode="all", filename=base)
    mx.profiler.profiler_set_state("run")
    try:
        for _ in range(2):
            state, outs = trainer.step(state, batch)
        jax.block_until_ready(outs)
    finally:
        mx.profiler.profiler_set_state("stop")
    found = glob.glob(os.path.join(os.path.splitext(base)[0] + "_trace",
                                   "**", "*.xplane.pb"), recursive=True)
    _need(len(found) == 1, "expected one .xplane.pb, found %s" % found)
    prof = jax.profiler.ProfileData.from_file(found[0])
    planes = {p.name: sum(len(list(line.events)) for line in p.lines)
              for p in prof.planes}
    prefix = "/host:CPU" if env["dryrun"] else "/device:TPU:"
    device_events = {k: v for k, v in planes.items() if k.startswith(prefix)}
    _need(device_events and all(device_events.values()),
          "no %s plane with events among %s" % (prefix, planes))
    return {"xplane_bytes": os.path.getsize(found[0]),
            "device_plane_events": device_events}


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dryrun", action="store_true",
                    help="rehearse at tiny sizes on the CPU, kernels "
                         "interpreted; never a pass")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset, for debugging")
    args = ap.parse_args(argv)
    legs = [name for name in args.legs.split(",") if name]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error("unknown legs %s (known: %s)" % (unknown, ", ".join(LEGS)))
    if args.dryrun:
        # chosen by argument: the rehearsal never touches a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
    t_start = time.perf_counter()

    import jax

    import jaxlib

    try:
        import mxnet_tpu as mx
    except ImportError as err:
        sys.exit("chip_smoke: it drives the repo it sits in, and here "
                 "there is none (%s) — nothing was run." % err)
    from mxnet_tpu import native
    from mxnet_tpu.observability import metrics as M

    cache_dir = mx.config.enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.dryrun:
        if dev.platform != "tpu":
            sys.exit("chip_smoke: jax found platform %r (%s x%d, "
                     "JAX_PLATFORMS=%r), not a TPU — nothing was run. "
                     "`--dryrun` rehearses on the CPU."
                     % (dev.platform, dev.device_kind, device["count"],
                        os.environ.get("JAX_PLATFORMS")))
        mx.context.device_peaks(dev.device_kind)  # unknown kind: raises
    mx.observability.set_enabled(True)  # jit.compile_count and friends
    print(json.dumps({
        "chip_smoke": "header", "dryrun": args.dryrun, "device": device,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "python": sys.version.split()[0],
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "jax_platforms": jax.config.jax_platforms,
        "compile_cache_dir": cache_dir,
        "native": native.status(),
        "peaks": mx.context.DEVICE_PEAKS}), flush=True)

    failed = []
    shared = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        env = {"dryrun": args.dryrun, "sz": sizes(args.dryrun),
               "n": device["count"], "platform": dev.platform, "tmp": tmp}
        for name in legs:
            t0 = time.perf_counter()
            compiles0 = _compiles()
            compile_ms0 = M.histogram("jit.compile.ms").sum
            row = {"leg": name, "dryrun": args.dryrun}
            # the boundary that must keep running: a failed leg is
            # recorded with its traceback and the next leg still runs
            try:
                row.update(globals()["leg_" + name](env, shared))
                row["ok"] = True
            except Exception as err:
                traceback.print_exc()
                row["ok"] = False
                row["error"] = "%s: %s" % (type(err).__name__, err)
                failed.append(name)
            row["wall_s"] = round(time.perf_counter() - t0, 2)
            row["compile_s"] = round(
                (M.histogram("jit.compile.ms").sum - compile_ms0) / 1e3, 2)
            row["compiles"] = _compiles() - compiles0
            print(json.dumps(row), flush=True)
        shared.clear()

    summary = {"ok": not failed, "device": device}
    if args.dryrun:
        summary["dryrun"] = True
    if failed:
        summary["failed"] = failed
    if set(legs) != set(LEGS):
        summary["ok"] = False
        summary["partial"] = legs
    summary_extra = {
        "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_s": round(M.histogram("jit.compile.ms").sum / 1e3, 1),
        "jit.compile_count": _compiles(),
        "jit.persistent_cache_hits": M.get_value(
            "jit.persistent_cache_hits", 0)}
    print(json.dumps(dict(summary_extra, chip_smoke="totals",
                          dryrun=args.dryrun)), flush=True)
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

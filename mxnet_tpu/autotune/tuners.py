"""Concrete tuners: build a real measurer for each declared tunable and
drive the search (ISSUE 6).

Each ``tune_*`` function is the explicit "tune once, ship the cache"
entry point for one knob family:

* :func:`tune_fused_matmul` — sweeps the fused matmul+epilogue kernel's
  block bounds by timing the actual kernel at the given shape (per-call
  block overrides, no env mutation),
* :func:`tune_serving_buckets` — replays a traffic sample of request
  sizes against a live :class:`~mxnet_tpu.serving.InferenceServer` per
  candidate ladder,
* :func:`tune_layout` / :func:`tune_remat` — generic measured choices
  over a caller-supplied step measurer.

:func:`auto_tune` is the ``MXNET_TUNE=1`` miss hook: shape-local knobs
(the fused kernel's blocks) can be tuned on the spot from their
call-site context; workload-dependent knobs (bucket ladders, layout,
remat) need a traffic sample or a train step and only tune through
their explicit entry point.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from . import cache, registry
from .search import SearchConfig, median_time, search

__all__ = ["tune_fused_matmul",
           "serving_replay_measurer", "tune_serving_buckets",
           "tune_layout", "tune_remat", "tune_generation",
           "tune_generation_kv", "tune_generation_spec",
           "tune_quantize_layers", "tune_control",
           "generation_replay_measurer", "control_replay_measurer",
           "pipeline_replay_measurer", "tune_input_pipeline", "auto_tune"]


def tune_fused_matmul(M, N, K, dtype="float32", epilogue=("bias",
                                                          ("act", "relu")),
                      wt=True, interpret=None, trials=None, repeats=3):
    """Measured search over the fused matmul+epilogue kernel's block
    bounds at one (M, N, K) shape (parallel/fused.py); records a
    ``fusion.blocks`` entry under the pow2 shape-bucket key and returns
    the winning value dict.  ``interpret=None`` auto-detects: Pallas
    interpret mode off-TPU (the numbers are then only meaningful
    relative to each other on the same host — real block tuning belongs
    on the chip).

    The default epilogue — bias + relu — is the modal carved region;
    block choice is dominated by the matmul tiling, not the epilogue
    arithmetic, so one sweep serves every region at the shape bucket.
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.fused import fused_matmul, fused_shape_key

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K), dt)
    w = jnp.asarray(rng.randn(N, K) if wt else rng.randn(K, N), dt)
    extras = []
    steps = tuple(tuple(s) if isinstance(s, (list, tuple)) else (s,)
                  for s in epilogue)
    for s in steps:
        if s[0] in ("bias", "vmul", "vadd"):
            extras.append(jnp.asarray(rng.randn(N), dt))
        elif s[0] == "res":
            extras.append(jnp.asarray(rng.randn(M, N), dt))
    key = fused_shape_key(M, N, K)
    ctx = {"M": int(M), "N": int(N), "K": int(K),
           "dtype_bytes": dt.itemsize}
    cfg = SearchConfig(trials=trials, repeats=repeats, warmup=1)

    def measure(c):
        fn = jax.jit(lambda x, w, *e: fused_matmul(  # graftlint: disable=G002 — one fresh program per measured candidate is the point of the sweep
            x, w, extras=e, epilogue=steps, wt=wt,
            block_m=int(c["block_m"]), block_n=int(c["block_n"]),
            block_k=int(c["block_k"]), interpret=interpret))
        out = fn(x, w, *extras)
        if out is None:
            raise MXNetError("fused_matmul: candidate %r has no tiling "
                             "at (%d, %d, %d)" % (c, M, N, K))
        return median_time(lambda: jax.block_until_ready(fn(x, w, *extras)),
                           repeats=cfg.repeats, warmup=cfg.warmup)

    res = search(registry.get("fusion.blocks"), measure, ctx=ctx, cfg=cfg)
    cache.record("fusion.blocks", key, res.best, dtype=str(dt),
                 ms=res.best_s * 1e3, trials=res.measured)
    return res.best


def model_key(symbol):
    """Stable fingerprint of a Symbol graph (the executor's program
    tuning key)."""
    from ..executor import _GraphProgram

    return _GraphProgram(symbol).tuning_key()


def serving_replay_measurer(symbol, arg_params, data_shapes, sizes,
                            aux_params=None, max_wait_ms=2, devices=None,
                            repeats=3, warmup=1):
    """``measure(candidate)`` for bucket-ladder candidates: build a live
    InferenceServer with the candidate ladder, warm every bucket, replay
    the traffic sample, return median wall seconds (the protocol of
    :func:`tune_serving_buckets`)."""
    from ..serving import InferenceServer, ServingConfig

    row_shapes = [tuple(d[1][1:]) for d in data_shapes]

    def _request(n):
        arrs = [np.zeros((n,) + s, np.float32) for s in row_shapes]
        return arrs[0] if len(arrs) == 1 else arrs

    def measure(c):
        server = InferenceServer(
            symbol, arg_params, aux_params, data_shapes=data_shapes,
            devices=devices,
            config=ServingConfig(buckets=c["buckets"],
                                 max_wait_ms=max_wait_ms))
        try:
            server.warmup()

            def run():
                futs = [server.submit(_request(n)) for n in sizes]
                for f in futs:
                    f.result(timeout=300)

            return median_time(run, repeats=repeats, warmup=warmup)
        finally:
            server.stop(drain=True)

    return measure


def tune_serving_buckets(symbol, arg_params, data_shapes, sizes,
                         aux_params=None, traffic_key="default",
                         trials=None, max_wait_ms=2, measure=None,
                         devices=None):
    """Measured search over serving bucket ladders for one model and one
    traffic shape (``sizes``: a sample of request row counts). Each
    candidate ladder serves the whole sample on a live InferenceServer;
    wall time decides. Records the winner under BOTH the quantized
    traffic signature and ``traffic_key`` (the ladder a plain
    ``InferenceServer(...)`` construction picks up). Returns the winning
    ladder as a list.

    ``measure`` (tests/smoke) replaces the live-server measurer:
    ``measure(candidate) -> seconds``.
    """
    sizes = [int(n) for n in sizes]
    if not sizes:
        raise ValueError("need a non-empty traffic sample")
    mkey = model_key(symbol)
    ctx = {"sizes": sizes, "max_size": max(sizes)}
    cfg = SearchConfig(trials=trials, repeats=3, warmup=1)

    if measure is None:
        measure = serving_replay_measurer(
            symbol, arg_params, data_shapes, sizes,
            aux_params=aux_params, max_wait_ms=max_wait_ms,
            devices=devices, repeats=cfg.repeats, warmup=cfg.warmup)

    res = search(registry.get("serving.buckets"), measure, ctx=ctx, cfg=cfg)
    ladder = sorted(int(b) for b in res.best["buckets"])
    value = {"buckets": ladder}
    from ..serving.buckets import traffic_signature

    cache.record("serving.buckets", (mkey, traffic_signature(sizes)),
                 value, ms=res.best_s * 1e3, trials=res.measured)
    cache.record("serving.buckets", (mkey, traffic_key), value,
                 ms=res.best_s * 1e3, trials=res.measured)
    return ladder


def generation_replay_measurer(model, params, prompts, max_new=8,
                               max_batch=4, max_seq=128, fixed=None,
                               repeats=2, warmup=1):
    """``measure(candidate)`` for generation knobs: build a live
    continuous-batching :class:`~mxnet_tpu.serving.generation.Generator`
    with the candidate knob (merged over ``fixed``), warm every program,
    replay the prompt sample end to end, return median wall seconds
    (the protocol of :func:`tune_generation`)."""
    from ..serving.generation import (GenerationConfig, Generator,
                                      SamplingParams)

    def measure(c):
        kw = dict(fixed or {})
        kw.update(c)
        gen = Generator(model, params,
                        GenerationConfig(max_batch=max_batch,
                                         max_seq=max_seq, **kw))
        try:
            gen.warmup()
            sp = SamplingParams(max_new_tokens=max_new)

            def run():
                handles = [gen.submit(p, sp) for p in prompts]
                for h in handles:
                    h.result(timeout=300)

            return median_time(run, repeats=repeats, warmup=warmup)
        finally:
            gen.stop(drain=True)

    return measure


def tune_generation(model, params, prompts=None, max_new=8, max_batch=4,
                    max_seq=128, trials=None, measure=None):
    """Measured search over ``generation.page_size`` and
    ``generation.decode_blocks`` for one checkpoint + slot geometry:
    each candidate serves a mixed-length prompt sample on a live
    continuous-batching generator; wall time decides. The two knobs are
    searched sequentially (page size first, then decode blocks at the
    winning page size — the blocks knob is downstream of the page
    layout). Records both under the generator's tuning key
    (``generation_tune_key``) so a plain ``Generator(model, params)``
    construction picks the winners up. Returns ``{op: value dict}``.

    ``measure`` (tests/smoke) replaces the live-generator measurer:
    ``measure(candidate) -> seconds``.
    """
    from ..serving.generation.engine import generation_tune_key

    if prompts is None:
        vocab = int(model.cfg["vocab"])
        rng = np.random.RandomState(0)
        # every sample length must satisfy the generator's admission
        # bound (prompt + max_new <= max_seq), not just the largest
        top = max(1, max_seq - max_new)
        lengths = sorted({min(n, top) for n in (3, 9, 17, 29)})
        prompts = [list(rng.randint(1, vocab, size=n) % vocab)
                   for n in lengths]
    prompts = [[int(t) for t in p] for p in prompts]
    key = generation_tune_key(model, max_batch, max_seq)
    ctx = {"max_seq": max_seq}
    cfg = SearchConfig(trials=trials, repeats=2, warmup=1)
    out = {}

    mk = measure if measure is not None else None
    page_measure = mk or generation_replay_measurer(
        model, params, prompts, max_new=max_new, max_batch=max_batch,
        max_seq=max_seq, repeats=cfg.repeats, warmup=cfg.warmup)
    res_p = search(registry.get("generation.page_size"), page_measure,
                   ctx=ctx, cfg=cfg)
    cache.record("generation.page_size", key, res_p.best,
                 ms=res_p.best_s * 1e3, trials=res_p.measured)
    out["generation.page_size"] = res_p.best

    blk_measure = mk or generation_replay_measurer(
        model, params, prompts, max_new=max_new, max_batch=max_batch,
        max_seq=max_seq, fixed=dict(res_p.best),
        repeats=cfg.repeats, warmup=cfg.warmup)
    res_b = search(registry.get("generation.decode_blocks"), blk_measure,
                   ctx=ctx, cfg=cfg)
    cache.record("generation.decode_blocks", key, res_b.best,
                 ms=res_b.best_s * 1e3, trials=res_b.measured)
    out["generation.decode_blocks"] = res_b.best
    return out


def tune_generation_spec(model, params, prompts=None, max_new=16,
                         max_batch=4, max_seq=128, trials=None,
                         measure=None):
    """Measured search over ``generation.spec_k`` (speculation depth,
    ISSUE 16) for one checkpoint + slot geometry: each candidate k
    (including 0 = off, so speculation must BEAT the plain decode loop
    to win) serves a prompt sample on a live generator through the
    shared replay measurer; wall time decides. The default sample is
    deliberately repetition-heavy — cyclic token patterns the n-gram
    prompt-lookup proposer can actually hit — because spec_k's payoff
    is workload-dependent in a way the geometry knobs are not: pass
    real prompts for production numbers. Records the winner under
    ``generation_tune_key`` so a plain ``Generator(model, params)``
    construction picks it up (explicit config > this cache entry >
    MXNET_GEN_SPEC_K). Returns ``{"generation.spec_k": value dict}``.

    ``measure`` (tests/smoke) replaces the live-generator measurer:
    ``measure(candidate) -> seconds``.
    """
    from ..serving.generation.engine import generation_tune_key

    if prompts is None:
        vocab = int(model.cfg["vocab"])
        rng = np.random.RandomState(0)
        top = max(1, max_seq - max_new)
        prompts = []
        for n, period in ((12, 3), (17, 2), (24, 4), (31, 5)):
            pat = [int(t) for t in rng.randint(1, vocab, size=period)]
            reps = min(n, top) // period + 1
            prompts.append((pat * reps)[:min(n, top)])
    prompts = [[int(t) for t in p] for p in prompts]
    key = generation_tune_key(model, max_batch, max_seq)
    cfg = SearchConfig(trials=trials, repeats=2, warmup=1)
    mk = measure if measure is not None else generation_replay_measurer(
        model, params, prompts, max_new=max_new, max_batch=max_batch,
        max_seq=max_seq, repeats=cfg.repeats, warmup=cfg.warmup)
    res = search(registry.get("generation.spec_k"), mk,
                 ctx={"max_seq": max_seq}, cfg=cfg)
    cache.record("generation.spec_k", key, res.best,
                 ms=res.best_s * 1e3, trials=res.measured)
    return {"generation.spec_k": res.best}


def control_replay_measurer(model, params, prompts=None, shared_prefix=32,
                            max_new=8, max_batch=4, max_seq=128,
                            fixed=None, repeats=2, warmup=1):
    """``measure(candidate)`` for the serving-control-plane knobs
    (ISSUE 14): build a live Generator with the prefix cache ON and the
    candidate knob (merged over ``fixed``), replay a shared-prefix
    prompt sample TWICE — the first pass seeds the radix tree on
    eviction, the second serves from it — and return median wall
    seconds (the protocol of :func:`tune_control`)."""
    from ..serving.generation import (GenerationConfig, Generator,
                                      SamplingParams)

    if prompts is None:
        vocab = int(model.cfg["vocab"])
        rng = np.random.RandomState(0)
        head = [int(t) for t in rng.randint(1, vocab, size=shared_prefix)]
        top = max(1, max_seq - max_new - shared_prefix)
        prompts = [head + [int(t) for t in rng.randint(
            1, vocab, size=1 + (n % top))] for n in (3, 9, 17, 29)]

    # knob fields -> GenerationConfig keyword names
    _ARGS = {"prefix_pages": "prefix_pages", "aging_ms": "slo_aging_ms"}

    # the replay is mixed-class so the aging knob is semantically LIVE
    # during its own search (on a single-class workload every aging
    # candidate would produce an identical schedule and noise would
    # pick the recorded winner)
    _TIERS = ("interactive", "standard", "batch")

    def measure(c):
        merged = dict(fixed or {})
        merged.update(c)
        kw = {_ARGS.get(k, k): v for k, v in merged.items()}
        gen = Generator(model, params,
                        GenerationConfig(max_batch=max_batch,
                                         max_seq=max_seq,
                                         prefix_cache=True, **kw))
        try:
            gen.warmup()
            sp = SamplingParams(max_new_tokens=max_new)

            def run():
                for _ in range(2):  # pass 1 seeds, pass 2 hits
                    handles = [gen.submit(p, sp, slo=_TIERS[i % 3])
                               for i, p in enumerate(prompts)]
                    for h in handles:
                        h.result(timeout=300)

            return median_time(run, repeats=repeats, warmup=warmup)
        finally:
            gen.stop(drain=True)

    return measure


def tune_control(model, params, prompts=None, shared_prefix=32, max_new=8,
                 max_batch=4, max_seq=128, trials=None, measure=None):
    """Measured search over the serving control plane's two knobs —
    ``control.prefix_pages`` (prefix-cache capacity) then
    ``control.slo_aging`` (admission aging interval) at the winning
    capacity — on a shared-prefix replay (the workload the cache
    exists for). Records both under the generator's tuning key
    (``generation_tune_key``) so a plain Generator construction picks
    the winners up. Returns ``{op: value dict}``.

    ``measure`` (tests/smoke) replaces the live-generator measurer:
    ``measure(candidate) -> seconds``.
    """
    from ..serving.generation.engine import generation_tune_key

    key = generation_tune_key(model, max_batch, max_seq)
    # capacity candidates scale off the default pool geometry (the
    # auto-sized pool at the flag-default 16-token page)
    pool_pages = max_batch * (-(-max_seq // 16)) + 1
    ctx = {"pool_pages": pool_pages}
    cfg = SearchConfig(trials=trials, repeats=2, warmup=1)
    out = {}

    mk = measure if measure is not None else None
    cap_measure = mk or control_replay_measurer(
        model, params, prompts, shared_prefix=shared_prefix,
        max_new=max_new, max_batch=max_batch, max_seq=max_seq,
        repeats=cfg.repeats, warmup=cfg.warmup)
    res_c = search(registry.get("control.prefix_pages"), cap_measure,
                   ctx=ctx, cfg=cfg)
    cache.record("control.prefix_pages", key, res_c.best,
                 ms=res_c.best_s * 1e3, trials=res_c.measured)
    out["control.prefix_pages"] = res_c.best

    age_measure = mk or control_replay_measurer(
        model, params, prompts, shared_prefix=shared_prefix,
        max_new=max_new, max_batch=max_batch, max_seq=max_seq,
        fixed=dict(res_c.best), repeats=cfg.repeats, warmup=cfg.warmup)
    res_a = search(registry.get("control.slo_aging"), age_measure,
                   ctx=ctx, cfg=cfg)
    cache.record("control.slo_aging", key, res_a.best,
                 ms=res_a.best_s * 1e3, trials=res_a.measured)
    out["control.slo_aging"] = res_a.best
    return out


def tune_generation_kv(model, params, prompts=None, max_new=8, max_batch=4,
                       max_seq=128, budget=0.9, measure=None):
    """Arbitrate the KV-page storage dtype against a measured accuracy
    budget (ISSUE 11): every ``generation.kv_dtype`` candidate decodes
    the same greedy prompt sample on a live generator; a candidate is
    admissible when its token agreement vs the model-dtype decode is at
    least ``budget``, and the fastest admissible candidate wins (decode
    is gather-bound, so narrower pages usually do — this tuner is the
    guard-rail that proves it on THIS checkpoint before serving flips).
    Records the winner under the generator's tuning key and returns
    ``{"kv_dtype": ..., "candidates": {dtype: {s, agreement}}}``.

    ``measure`` (tests) replaces the live run:
    ``measure(kv_dtype) -> (seconds, agreement)``.
    """
    from ..serving.generation import (GenerationConfig, Generator,
                                      SamplingParams)
    from ..serving.generation.engine import KV_DTYPES, generation_tune_key

    if prompts is None:
        vocab = int(model.cfg["vocab"])
        rng = np.random.RandomState(0)
        top = max(1, max_seq - max_new)
        lengths = sorted({min(n, top) for n in (3, 9, 17, 29)})
        prompts = [list(rng.randint(1, vocab, size=n)) for n in lengths]
    prompts = [[int(t) for t in p] for p in prompts]
    key = generation_tune_key(model, max_batch, max_seq)

    def live_run(kv_dtype):
        import time

        gen = Generator(model, params,
                        GenerationConfig(max_batch=max_batch,
                                         max_seq=max_seq,
                                         kv_dtype=kv_dtype))
        try:
            gen.warmup()
            sp = SamplingParams(max_new_tokens=max_new)  # greedy
            t0 = time.perf_counter()
            toks = [gen.submit(p, sp) for p in prompts]
            toks = [h.result(timeout=300) for h in toks]
            return time.perf_counter() - t0, toks
        finally:
            gen.stop(drain=True)

    ref_tokens = None
    ref_secs = None
    if measure is None:
        # the reference run doubles as the "model" candidate: greedy
        # decode of the same arm is deterministic, a second full
        # build+warmup+decode would buy zero information
        ref_secs, ref_tokens = live_run("model")

    def agreement(toks):
        pairs = [(a, b) for r, s in zip(ref_tokens, toks)
                 for a, b in zip(r, s)]
        return float(np.mean([a == b for a, b in pairs])) if pairs else 1.0

    report = {}
    for kv in sorted(KV_DTYPES):
        if measure is not None:
            secs, agree = measure(kv)
        elif kv == "model":
            secs, agree = ref_secs, 1.0
        else:
            secs, toks = live_run(kv)
            agree = agreement(toks)
        report[kv] = {"s": float(secs), "agreement": float(agree)}
        cache.note_measurements()
    admissible = {kv: r for kv, r in report.items()
                  if r["agreement"] >= budget}
    if not admissible:  # budget impossible: the exact baseline stands
        admissible = {"model": report["model"]}
    winner = min(admissible, key=lambda kv: admissible[kv]["s"])
    cache.record("generation.kv_dtype", key, {"kv_dtype": winner},
                 ms=admissible[winner]["s"] * 1e3, trials=len(report),
                 extra={"budget": budget, "candidates": report})
    return {"kv_dtype": winner, "candidates": report}


def tune_quantize_layers(module, batches, table, budget=0.99, key=None,
                         max_drops=None):
    """Per-layer int8-vs-fp32 arbitration for the ``quantize`` graph
    pass (ISSUE 11): starting from everything-quantized, greedily pin
    the most damaging layer back to fp32 until the measured top-1
    agreement vs the fp32 module meets ``budget``. Records
    ``quantize.layers`` ``{"skip": [...]}`` under the graph fingerprint
    (``key``) so every later quantized bind of this graph consults it.

    ``module``: a bound fp32 inference Module (the baseline);
    ``batches``: numpy arrays / DataBatches to score on; ``table``: the
    CalibrationTable. Returns ``{"skip": [...], "agreement": float}``.

    The consulted/recorded entry always lives under the graph
    FINGERPRINT (what ``run_quantize`` looks up); a custom ``key`` gets
    a bookkeeping copy of the winner but never steers the consult.
    """
    from .. import graph_pass
    from ..graph_pass import quantize as _quant

    symbol = module.symbol
    fp_key = graph_pass.graph_fingerprint(symbol)
    arg_params, aux_params = module.get_params()
    data_shapes = [(d.name, d.shape) for d in module.data_shapes]

    def top1(mod, arrays):
        import mxnet_tpu as mx

        outs = []
        for arr in arrays:
            mod.forward(mx.io.DataBatch(data=[mx.nd.array(a)
                                              for a in arr]),
                        is_train=False)
            outs.append(mod.get_outputs()[0].asnumpy().argmax(axis=-1))  # graftlint: disable=G001 — accuracy measurement over a handful of calibration batches, not a hot path
        return np.concatenate(outs)

    def as_arrays(b):
        if isinstance(b, np.ndarray):  # BEFORE the .data duck-check:
            return [b]                 # ndarray.data is a memoryview
        if isinstance(b, (list, tuple)):
            return list(b)
        if hasattr(b, "data"):  # a DataBatch (docstring contract)
            return [np.asarray(a.asnumpy() if hasattr(a, "asnumpy")
                               else a) for a in b.data]  # graftlint: disable=G001 — one-time measurement-input staging
        return [b]

    arrays = [as_arrays(b) for b in batches]
    ref = top1(module, arrays)
    # trial binds must be pure functions of THIS tuner's skip list: a
    # stale quantize.layers entry from a previous run would otherwise
    # union into every trial (run_quantize consults the cache), and the
    # recorded winner's agreement would never have been measured. The
    # prior entry is restored if the tune dies mid-run (an unmeasured
    # empty-skip stub must not clobber a previously tuned pin list).
    prior_entry = cache.lookup("quantize.layers", fp_key)
    cache.record("quantize.layers", fp_key, {"skip": []},
                 extra={"status": "tuning"})
    # save/restore the caller's process-wide overrides: clearing them to
    # None would silently disable a set_calibration_table/set_passes the
    # user had armed for later binds
    from ..graph_pass import core as _gp_core

    prior_spec = _gp_core._SPEC_OVERRIDE
    prior_table = _quant._TABLE_OVERRIDE
    prior_skip = _quant._SKIP_OVERRIDE

    def agreement(skip):
        import mxnet_tpu as mx

        _quant.set_quantize_skip(skip)
        graph_pass.set_calibration_table(table)
        graph_pass.set_passes(_ambient_passes_plus_quantize())
        try:
            mod = mx.mod.Module(symbol, context=mx.cpu(),
                                data_names=[n for n, _ in data_shapes])
            mod.bind(data_shapes=data_shapes, for_training=False)
            mod.set_params(arg_params, aux_params, allow_missing=False)
            got = top1(mod, arrays)
        finally:
            graph_pass.set_passes(prior_spec)
            graph_pass.set_calibration_table(prior_table)
            _quant.set_quantize_skip(prior_skip)
        return float((got == ref).mean())

    try:
        # candidate set: the ops a fully-quantized rewrite touches
        opt = graph_pass.optimize(
            symbol, for_training=False,
            frozen=set(arg_params) | set(aux_params),
            arg_shapes=dict(data_shapes),
            config=graph_pass.PassConfig(
                passes=set(graph_pass.DEFAULT_PASSES) | {"quantize"},
                quant_table=table))
        quantized = []
        if opt is not None:
            for rep in opt.reports:
                if rep["pass"] == "quantize" and "detail" in rep:
                    quantized = list(rep["detail"].get("quantized", ()))
        skip = []
        agree = agreement(skip)
        drops = 0
        bound = max_drops if max_drops is not None else len(quantized)
        while agree < budget and quantized and drops < bound:
            trials = [(agreement(skip + [name]), name) for name in quantized]  # graftlint: disable=G001 — the greedy arbitration loop IS the measurement (tune-once, ship the cache)
            cache.note_measurements(len(trials))
            best_agree, best_name = max(trials)
            if best_agree <= agree:
                break  # no single drop helps: stop instead of thrashing
            skip.append(best_name)
            quantized.remove(best_name)
            agree = best_agree
            drops += 1
    except BaseException:
        if isinstance(prior_entry, dict):
            cache.record("quantize.layers", fp_key, prior_entry,
                         extra={"status": "restored_after_failed_tune"})
        raise
    cache.record("quantize.layers", fp_key, {"skip": sorted(skip)},
                 trials=drops + 1,
                 extra={"budget": budget, "agreement": agree})
    if key is not None and key != fp_key:
        # caller bookkeeping copy only — run_quantize consults fp_key
        cache.record("quantize.layers", key, {"skip": sorted(skip)},
                     trials=drops + 1,
                     extra={"budget": budget, "agreement": agree,
                            "consulted_key": str(fp_key)})
    return {"skip": sorted(skip), "agreement": agree}


def _ambient_passes_plus_quantize():
    """The ambient pass spec — an active ``graph_pass.set_passes``
    override first, else MXNET_GRAPH_PASSES — with ``quantize`` appended
    (the tuner must trial-quantize under the user's own pipeline)."""
    import os

    from ..graph_pass import core as _gp_core

    spec = _gp_core._SPEC_OVERRIDE
    if spec is None:
        spec = os.environ.get("MXNET_GRAPH_PASSES", "default")
    spec = str(spec).strip()
    if spec.lower() in ("off", "none", "0", ""):
        spec = "default"
    return spec + ",quantize"


def tune_layout(measure, key, default="NHWC", trials=None):
    """Measured NHWC-vs-NCHW choice: ``measure({"layout": L}) ->
    seconds`` (the caller owns the model/step). Records ``graph.layout`` under
    ``key`` and returns the winning layout string."""
    cfg = SearchConfig(trials=trials or 2, repeats=3, warmup=1)
    res = search(registry.get("graph.layout"), measure,
                 ctx={"default": default}, cfg=cfg)
    cache.record("graph.layout", key, res.best, ms=res.best_s * 1e3,
                 trials=res.measured)
    return res.best["layout"]


def tune_remat(measure, graph_key, trials=None):
    """Measured store-vs-recompute choice for one graph's fused train
    program: ``measure({"mirror": 0|1}) -> seconds``. Records
    ``exec.remat`` under the graph's tuning key (see
    ``_GraphProgram.tuning_key``) and returns the winning mirror flag."""
    cfg = SearchConfig(trials=trials or 2, repeats=3, warmup=1)
    res = search(registry.get("exec.remat"), measure, ctx={}, cfg=cfg)
    cache.record("exec.remat", graph_key, res.best, ms=res.best_s * 1e3,
                 trials=res.measured)
    return int(res.best["mirror"])


def pipeline_replay_measurer(make_iter, batches=8):
    """``measure(candidate) -> seconds`` over a live streaming input
    pipeline: builds the iterator with the candidate's
    ``workers``/``depth`` via the caller's ``make_iter(decode_workers=,
    prefetch_depth=)`` factory and times the delivery of ``batches``
    batches (the consumer-side rate is exactly what training sees)."""
    import time

    def measure(c):
        it = make_iter(decode_workers=c.get("workers"),
                       prefetch_depth=c.get("depth"))
        try:
            t0 = time.perf_counter()
            n = 0
            starved = 0
            while n < batches:
                try:
                    next(it)
                except StopIteration:
                    # two consecutive epoch ends with no batch in
                    # between = the stream yields nothing (empty record
                    # file / empty shard): fail with a diagnostic
                    # instead of spinning the search forever
                    starved += 1
                    if starved > 1:
                        raise MXNetError(
                            "pipeline_replay_measurer: iterator yields "
                            "no batches (empty dataset or shard)")
                    it.reset()
                    continue
                starved = 0
                n += 1
            return time.perf_counter() - t0
        finally:
            closer = getattr(it, "close", None)
            if closer is not None:
                closer()

    return measure


def tune_input_pipeline(make_iter, key, batches=8, trials=None,
                        measure=None):
    """Measured search over the streaming input pipeline's
    ``io.decode_workers`` and ``io.prefetch_depth`` (worker count first,
    then queue depth at the winning worker count); records both under
    ``key`` (see ``runtime.pipeline.io_pipeline_key`` — the pipeline
    self-sizes per HOST) and returns ``{op: winning value dict}``.

    ``make_iter(decode_workers=, prefetch_depth=)`` must build a fresh
    iterator (None = that knob's default); ``measure`` overrides the
    live replay measurer (tests use a stub)."""
    import os

    ctx = {"cpus": os.cpu_count() or 4}
    cfg = SearchConfig(trials=trials or 4, repeats=2, warmup=0)
    base = measure or pipeline_replay_measurer(make_iter, batches)

    res_w = search(registry.get("io.decode_workers"),
                   lambda c: base({"workers": int(c["workers"])}),
                   ctx=ctx, cfg=cfg)
    cache.record("io.decode_workers", key, res_w.best,
                 ms=res_w.best_s * 1e3, trials=res_w.measured)
    workers = int(res_w.best["workers"])
    res_d = search(registry.get("io.prefetch_depth"),
                   lambda c: base({"workers": workers,
                                   "depth": int(c["depth"])}),
                   ctx=ctx, cfg=cfg)
    cache.record("io.prefetch_depth", key, res_d.best,
                 ms=res_d.best_s * 1e3, trials=res_d.measured)
    return {"io.decode_workers": res_w.best,
            "io.prefetch_depth": res_d.best}


def auto_tune(op, key, ctx):
    """MXNET_TUNE=1 cache-miss hook (called via ``lookup_or_tune`` from
    consulting call sites, never inside a jax trace). Only shape-local
    knobs can tune from call-site context; returns the freshly recorded
    value, or None when the op needs an explicit workload."""
    # shape-local: the region's (M, N, K) rides in the consult context
    # (parallel/fused.py resolve_blocks)
    if op != "fusion.blocks" or not all(k in ctx for k in ("M", "N", "K")):
        return None
    db = int(ctx.get("dtype_bytes", 4))
    dtype = {2: "bfloat16", 4: "float32"}.get(db, "float32")
    return tune_fused_matmul(int(ctx["M"]), int(ctx["N"]), int(ctx["K"]),
                             dtype=dtype)

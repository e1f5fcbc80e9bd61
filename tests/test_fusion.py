"""Fusion-region codegen tests (ISSUE 15).

Three surfaces:

* the ``fuse`` graph pass — region grammar, parity (reference AND
  Pallas-kernel lowering), training-bind grads, re-bind caching,
* the fused matmul+epilogue kernels (interpret mode on CPU) vs a numpy
  reference,
* the post-fusion perf accounting — the fused-vs-unfused analytic byte
  identity is pinned EXACTLY.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune, graph_pass
from mxnet_tpu.config import set_flag
from mxnet_tpu.graph_pass import PassConfig
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.observability import perf


@pytest.fixture(autouse=True)
def _passes_reset():
    graph_pass.set_passes(None)
    graph_pass.reset_stats()
    yield
    graph_pass.set_passes(None)


@pytest.fixture
def own_tune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TUNE_CACHE", str(tmp_path / "tuning.json"))
    autotune.reset()
    yield
    autotune.reset()


@pytest.fixture
def kernel_path():
    set_flag("MXNET_FUSION_INTERPRET", 1)
    yield
    set_flag("MXNET_FUSION_INTERPRET", None)


# ------------------------------------------------------------- model zoo

def _conv_residual():
    data = mx.sym.var("data")
    x = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                           name="c0")
    x = mx.sym.Activation(x, act_type="relu", name="a0")
    sc = mx.sym.Convolution(data, kernel=(1, 1), num_filter=8, name="proj")
    x = x + sc
    x = mx.sym.Activation(x, act_type="relu", name="a1")
    x = mx.sym.Flatten(x)
    x = mx.sym.FullyConnected(x, num_hidden=7, name="fc")
    return mx.sym.SoftmaxOutput(x, name="softmax"), (4, 3, 10, 10)


def _transformer_block():
    T, D = 6, 8
    data = mx.sym.var("data")
    q = mx.sym.FullyConnected(data, num_hidden=D, flatten=False, name="q")
    k = mx.sym.FullyConnected(data, num_hidden=D, flatten=False, name="k")
    v = mx.sym.FullyConnected(data, num_hidden=D, flatten=False, name="v")
    scores = mx.sym.batch_dot(q, mx.sym.transpose(k, axes=(0, 2, 1)))
    attn = mx.sym.softmax(scores / float(np.sqrt(D)), axis=-1)
    ctx = mx.sym.batch_dot(attn, v)
    out = mx.sym.FullyConnected(ctx + data, num_hidden=D, flatten=False,
                                name="proj")
    flat = mx.sym.Flatten(out)
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(flat, num_hidden=4, name="head"),
        name="softmax"), (3, T, D)


def _mlp():
    data = mx.sym.var("data")
    h = mx.sym.Activation(mx.sym.FullyConnected(data, num_hidden=16,
                                                name="fc1"),
                          act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(h, num_hidden=6,
                                                      name="fc2"),
                                name="softmax"), (5, 8)


ZOO = {"conv_residual": _conv_residual,
       "transformer_block": _transformer_block, "mlp": _mlp}


def _materialize(builder, seed=7):
    sym, dshape = builder()
    rng = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    args = {n: mx.nd.array(rng.uniform(-0.5, 0.5, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n != "data" and not n.endswith("label")}
    auxs = {n: mx.nd.array(rng.uniform(0.5, 1.5, s).astype(np.float32))
            for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    x = rng.uniform(0, 1, dshape).astype(np.float32)
    return sym, dshape, args, auxs, x


def _predict(builder, spec, args, auxs, x, dshape):
    graph_pass.set_passes(spec)
    try:
        sym, _ = builder()
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[("data", dshape)], for_training=False)
        mod.init_params(mx.init.Uniform(0.1))
        mod.set_params(args, auxs)
        out = mod.predict(NDArrayIter(x, None, batch_size=x.shape[0]))
        return mod, out.asnumpy()
    finally:
        graph_pass.set_passes(None)


def _last_fuse_report():
    for rep in reversed(graph_pass.recent_reports()):
        if "fuse" in rep:
            return rep["fuse"]
    return {"regions": [], "rejected": {}, "saved_bytes": 0}


# -------------------------------------------------------- pass + parity

@pytest.mark.parametrize("name", sorted(ZOO))
def test_fused_parity_fp32(name):
    builder = ZOO[name]
    _sym, dshape, args, auxs, x = _materialize(builder)
    _m0, ref = _predict(builder, "default,-fuse", args, auxs, x, dshape)
    graph_pass.reset_stats()
    m1, fused = _predict(builder, "default", args, auxs, x, dshape)
    assert _last_fuse_report()["regions"], "no regions carved on %s" % name
    np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-6)
    # the executor surfaces the carved regions without a dump
    regions = m1._exec_group.execs[0].fused_regions()
    assert regions and all(r["base_op"] in
                           ("Convolution", "FullyConnected", "dot",
                            "batch_dot") for r in regions)
    # ... and says how each lowers, and why: the CPU composes the reference
    assert all(r["lowering"] == "reference" and "cpu" in r["reason"]
               for r in regions), regions


@pytest.mark.parametrize("name", ["conv_residual", "transformer_block"])
def test_fused_kernel_path_parity(name, kernel_path, own_tune_cache):
    builder = ZOO[name]
    _sym, dshape, args, auxs, x = _materialize(builder)
    _m0, ref = _predict(builder, "default,-fuse", args, auxs, x, dshape)
    m1, fused = _predict(builder, "default", args, auxs, x, dshape)
    # the Pallas kernel accumulates fp32 and applies the epilogue on the
    # accumulator — documented tolerance (docs/fusion.md)
    np.testing.assert_allclose(fused, ref, rtol=2e-5, atol=1e-5)
    # the region report asks the same static decision the lowering did:
    # some region took the kernel, and every reference one names a reason
    regions = m1._exec_group.execs[0].fused_regions()
    took_kernel = [r["name"] for r in regions if r["lowering"] == "kernel"]
    assert bool(took_kernel) == (name == "transformer_block"), regions
    assert all(r["reason"] for r in regions if r["lowering"] == "reference")


def test_residual_region_carved():
    builder = ZOO["conv_residual"]
    _sym, dshape, args, auxs, x = _materialize(builder)
    _m1, _ = _predict(builder, "default", args, auxs, x, dshape)
    report = _last_fuse_report()
    ops = [tuple(r["ops"]) for r in report["regions"]]
    # one region must carry the residual add + trailing relu
    assert any("broadcast_add" in o or "elemwise_add" in o
               for o in ops), ops
    assert report["saved_bytes"] > 0


def test_region_grammar_rejections():
    # multi-consumer base output and softmax consumers are rejected with
    # reasons the adoption report can surface
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    # fc1 feeds BOTH a relu and a sigmoid: multi-consumer, no region
    out = mx.sym.Group([mx.sym.Activation(h, act_type="relu"),
                        mx.sym.sigmoid(h)])
    shapes = {"data": (4, 6)}
    arg_shapes, _, _ = out.infer_shape(data=shapes["data"])
    all_shapes = dict(zip(out.list_arguments(), arg_shapes))
    opt = graph_pass.optimize(out, for_training=False,
                              arg_shapes=all_shapes,
                              config=PassConfig(spec="fuse"))
    assert opt is None
    # self-add (x + x) can never fuse: both add inputs come from the base
    x2 = mx.sym.FullyConnected(data, num_hidden=8, name="fcx")
    dbl = x2 + x2
    arg_shapes, _, _ = dbl.infer_shape(data=(4, 6))
    all_shapes = dict(zip(dbl.list_arguments(), arg_shapes))
    assert graph_pass.optimize(dbl, for_training=False,
                               arg_shapes=all_shapes,
                               config=PassConfig(spec="fuse")) is None


def test_expanding_broadcast_not_absorbed():
    """An epilogue broadcast whose OTHER operand is larger than the
    chain would change the region's output shape — it must terminate
    the chain, not mis-infer (review repro: FC (1,8) + big (5,8))."""
    data = mx.sym.var("data")
    big = mx.sym.var("big")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fcx")
    out = mx.sym.broadcast_add(fc, big)
    shapes = {"data": (1, 4), "big": (5, 8), "fcx_weight": (8, 4),
              "fcx_bias": (8,)}
    opt = graph_pass.optimize(out, for_training=False, arg_shapes=shapes,
                              config=PassConfig(spec="fuse"))
    assert opt is None  # nothing fusable: the only candidate expands
    # and when it DOES run through a full bind, shapes stay correct
    graph_pass.set_passes("default")
    try:
        ex = out.simple_bind(mx.cpu(), data=(1, 4), big=(5, 8))
        for v in ex.arg_dict.values():
            v[:] = np.random.RandomState(0).rand(*v.shape).astype(
                np.float32)
        res = ex.forward(is_train=False)[0]
        assert res.shape == (5, 8)
    finally:
        graph_pass.set_passes(None)


def test_fuse_idempotent():
    builder = ZOO["conv_residual"]
    sym, dshape = builder()
    arg_shapes, _, _ = sym.infer_shape(data=dshape)
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    cfg = PassConfig(spec="fuse")
    opt = graph_pass.optimize(sym, for_training=False, arg_shapes=shapes,
                              config=cfg)
    assert opt is not None
    # a second pipeline run over the fused graph carves nothing new
    opt2 = graph_pass.optimize(opt.symbol, for_training=False,
                               arg_shapes=shapes, config=cfg)
    assert opt2 is None


def test_training_parity_reference_and_kernel(own_tune_cache):
    builder = ZOO["transformer_block"]
    _sym, dshape, args, auxs, x = _materialize(builder)
    y = (np.arange(dshape[0]) % 4).astype(np.float32)

    def fit(spec, interpret=0):
        graph_pass.set_passes(spec)
        set_flag("MXNET_FUSION_INTERPRET", interpret)
        try:
            sym, _ = builder()
            mod = mx.mod.Module(sym, context=mx.cpu())
            it = NDArrayIter(x, y, batch_size=dshape[0],
                             label_name="softmax_label")
            mod.fit(it, num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1},
                    initializer=mx.init.Uniform(0.1), force_init=True,
                    arg_params=dict(args), aux_params=dict(auxs),
                    allow_missing=False)
            return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
        finally:
            set_flag("MXNET_FUSION_INTERPRET", None)
            graph_pass.set_passes(None)

    p_ref = fit("default,-fuse")
    p_fused = fit("default")
    p_kern = fit("default", interpret=1)
    for k in sorted(p_ref):
        np.testing.assert_allclose(p_fused[k], p_ref[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
        # kernel fwd + reference-recompute bwd (custom_vjp)
        np.testing.assert_allclose(p_kern[k], p_ref[k], rtol=2e-4,
                                   atol=1e-5, err_msg=k)


# --------------------------------------------------- fused kernel units

def _np_reference(x, w, wt, extras, epilogue):
    y = x.astype(np.float64) @ (w.T if wt else w).astype(np.float64)
    ei = 0
    for step in epilogue:
        kind = step[0]
        if kind in ("bias", "vadd"):
            y = y + np.asarray(extras[ei], np.float64)
            ei += 1
        elif kind == "vmul":
            y = y * np.asarray(extras[ei], np.float64)
            ei += 1
        elif kind == "res":
            r = np.asarray(extras[ei], np.float64)
            y = y * r if step[1] == "elemwise_mul" else y + r
            ei += 1
        elif kind == "act":
            if step[1] == "relu":
                y = np.maximum(y, 0.0)
            elif step[1] == "sigmoid":
                y = 1.0 / (1.0 + np.exp(-y))
            elif step[1] == "tanh":
                y = np.tanh(y)
            elif step[1] == "softrelu":
                y = np.log1p(np.exp(y))
            elif step[1] == "softsign":
                y = y / (1.0 + np.abs(y))
        elif kind == "scalar":
            op, v = step[1], step[2]
            y = {"_mul_scalar": y * v, "_div_scalar": y / v,
                 "_plus_scalar": y + v, "_minus_scalar": y - v,
                 "_rminus_scalar": v - y}[op]
    return y


@pytest.mark.parametrize("wt", [True, False])
@pytest.mark.parametrize("epilogue", [
    (("bias",), ("act", "relu")),
    (("vmul",), ("vadd",)),
    (("scalar", "_div_scalar", 2.0), ("res", "elemwise_add")),
    (("act", "sigmoid"),),
])
def test_fused_matmul_kernel_vs_reference(wt, epilogue, own_tune_cache):
    from mxnet_tpu.parallel.fused import fused_matmul

    rng = np.random.RandomState(3)
    M, N, K = 16, 8, 32
    x = rng.randn(M, K).astype(np.float32)
    w = (rng.randn(N, K) if wt else rng.randn(K, N)).astype(np.float32)
    extras = []
    for s in epilogue:
        if s[0] in ("bias", "vmul", "vadd"):
            extras.append(rng.randn(N).astype(np.float32))
        elif s[0] == "res":
            extras.append(rng.randn(M, N).astype(np.float32))
    out = fused_matmul(x, w, extras=extras, epilogue=epilogue, wt=wt,
                       block_m=8, block_n=8, block_k=16, interpret=True)
    assert out is not None
    ref = _np_reference(x, w, wt, extras, epilogue)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_fused_batch_matmul_kernel_vs_reference(own_tune_cache):
    from mxnet_tpu.parallel.fused import fused_batch_matmul

    rng = np.random.RandomState(4)
    B, M, K, N = 3, 8, 16, 8
    x = rng.randn(B, M, K).astype(np.float32)
    w = rng.randn(B, K, N).astype(np.float32)
    res = rng.randn(B, M, N).astype(np.float32)
    epilogue = (("scalar", "_mul_scalar", 0.5), ("res", "elemwise_add"),
                ("act", "relu"))
    out = fused_batch_matmul(x, w, extras=[res], epilogue=epilogue,
                             block_m=4, block_n=4, block_k=8,
                             interpret=True)
    assert out is not None
    ref = np.stack([_np_reference(x[b], w[b], False, [res[b]], epilogue)
                    for b in range(B)])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_fused_matmul_tiling_fallback():
    from mxnet_tpu.parallel.fused import fused_matmul, pick_blocks

    # a dim SMALLER than the bound always tiles (the dim itself is a
    # divisor — one full block)
    assert pick_blocks(97, 89, 101, 128, 128, 512) == ((97, 89, 101), None)
    # a prime dim LARGER than its bound has only tiny divisors: the
    # kernel declines and the op falls back to the unfused composition
    # (mid-trace safe, the flash-attention prime-T rule)
    tiles, why = pick_blocks(1009, 89, 1013, 128, 128, 512, interpret=True)
    assert tiles is None and "M=1009" in why
    x = np.zeros((1009, 1013), np.float32)
    w = np.zeros((89, 1013), np.float32)
    assert fused_matmul(x, w, epilogue=(("act", "relu"),), wt=True,
                        block_m=128, block_n=128, block_k=512,
                        interpret=True) is None


def test_epilogue_act_sets_agree():
    from mxnet_tpu.ops.fused import EPILOGUE_ACTS
    from mxnet_tpu.parallel.fused import supported_act

    for act in EPILOGUE_ACTS:
        assert supported_act(act), act


# ------------------------------------------- post-fusion perf accounting

def _walk(sym, shapes, spec):
    opt = graph_pass.optimize(
        sym, for_training=False,
        frozen=[n for n in shapes if n != "data"],
        arg_shapes=shapes, config=PassConfig(spec=spec))
    s2 = opt.symbol if opt is not None else sym
    topo = [n for n in s2.topo_nodes() if not n.is_variable]
    return perf.program_cost(s2, topo, shapes, dtype_bytes=4)


def test_fused_vs_unfused_analytic_bytes_pinned():
    """THE satellite regression: once a region is fused, the roofline
    accounting stops charging its interior traffic — exactly
    ``2 * steps * out_bytes`` per region, byte-for-byte."""
    sym, dshape = _conv_residual()
    arg_shapes, _, _ = sym.infer_shape(data=dshape)
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    unfused = _walk(sym, shapes, "prune,bn_fold")
    fused = _walk(sym, shapes, "prune,bn_fold,fuse")
    assert fused["fused_regions"]
    assert fused["fused_saved_bytes"] > 0
    assert unfused["hbm_bytes"] - fused["hbm_bytes"] \
        == fused["fused_saved_bytes"]
    # FLOPs are conserved exactly — fusion moves bytes, not arithmetic
    assert unfused["flops"] == fused["flops"]


def test_fused_rows_leave_candidate_list():
    sym, dshape = _conv_residual()
    arg_shapes, _, _ = sym.infer_shape(data=dshape)
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    fused = _walk(sym, shapes, "prune,bn_fold,fuse")
    fused_names = {r["name"] for r in fused["fused_regions"]}
    for cand in fused["fusion_candidates"]:
        assert not (set(cand["ops"]) & fused_names), \
            "a consumed region re-listed as candidate"
    rows = {r["name"]: r for r in fused["ops"]}
    for name in fused_names:
        assert rows[name].get("fused") is True
        assert rows[name]["interior_saved_bytes"] > 0


def test_perf_report_fusion_adoption():
    from tools.perf_report import format_fusion, fusion_adoption

    section = {"programs": [{
        "graph": "g", "mode": "infer",
        "fused_regions": [{"name": "a1", "members": ["c0", "a1"],
                           "saved_bytes": 2048}],
        "fused_saved_bytes": 2048,
        "fusion_candidates": [
            {"ops": ["fc", "softmax0"], "saved_bytes": 512}],
    }]}
    gp = {"recent": [{"fuse": {"rejected": {"fc": "op:softmax"},
                               "regions": []}}]}
    rows = fusion_adoption(section, gp)
    assert rows[0]["fused_regions"][0]["name"] == "a1"
    assert rows[0]["remaining"][0]["status"] == "unfused: op:softmax"
    text = format_fusion(section, "x.json", gp)
    assert "FUSED" in text and "op:softmax" in text


# ----------------------------------------------------------- the tuner

def test_tune_fused_matmul_records(own_tune_cache):
    from mxnet_tpu.parallel.fused import fused_shape_key

    autotune.reset_stats()
    best = autotune.tune_fused_matmul(64, 64, 128, trials=3, repeats=1)
    entry = autotune.lookup_entry("fusion.blocks",
                                  fused_shape_key(64, 64, 128),
                                  dtype="float32")
    assert entry["value"] == best and entry["trials"] == 3
    assert autotune.stats()["measurements"] == 3

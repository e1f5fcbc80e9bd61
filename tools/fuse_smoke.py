#!/usr/bin/env python
"""CPU-fast fusion-region smoke (tier-1 CI guard, docs/fusion.md).

End-to-end in seconds on CPU, the way production uses the layer:

1. **regions carved** — the default pipeline must fuse >= 1 region on
   BOTH a resnet-toy (conv + relu + residual-add chains after bn_fold)
   and a transformer block (batch_dot + scalar/residual chains), with
   the analytic interior-bytes saving > 0,
2. **numeric parity** — fused predictions match the unfused pipeline
   (``default,-fuse``) at fp32 tolerances, on the reference-composition
   path AND on the real Pallas kernel path (MXNET_FUSION_INTERPRET=1),
3. **flat re-bind cost** — reshaping to an already-seen batch shape
   re-runs neither the pass pipeline nor XLA compilation.

Prints a one-line JSON summary (optionally written to argv[1]); any
violation raises, failing the CI step.
"""
import json
import os
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# FORCE a scratch tuning cache (not setdefault): the fused kernels
# consult ``fusion.blocks``, and a user's real cache must neither steer
# the parity check nor be touched by it
os.environ["MXNET_TUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="fuse_smoke_"), "tuning.json")
os.environ["MXNET_TUNE_FINGERPRINT"] = "fuse_smoke"

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import graph_pass  # noqa: E402
from mxnet_tpu.config import set_flag  # noqa: E402
from mxnet_tpu.io import NDArrayIter  # noqa: E402
from mxnet_tpu.observability import metrics as M  # noqa: E402
from mxnet_tpu.observability import set_enabled  # noqa: E402


def _resnet_toy():
    from mxnet_tpu.models import get_resnet

    sym = get_resnet(num_classes=10, num_layers=8, image_shape=(3, 16, 16))
    return sym, (2, 3, 16, 16)


def _transformer_block():
    T, D = 8, 16
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
        name="softmax"), (4, T, D)


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


def _predict(builder, spec, args, auxs, x, dshape, interpret=0):
    graph_pass.set_passes(spec)
    set_flag("MXNET_FUSION_INTERPRET", interpret)
    try:
        sym, _ = builder()
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[("data", dshape)], for_training=False)
        mod.init_params(mx.init.Uniform(0.1))
        mod.set_params(args, auxs)
        out = mod.predict(NDArrayIter(x, None, batch_size=x.shape[0]))
        return mod, out.asnumpy()
    finally:
        set_flag("MXNET_FUSION_INTERPRET", None)
        graph_pass.set_passes(None)


def _fuse_summary():
    for rep in reversed(graph_pass.recent_reports()):
        if "fuse" in rep:
            return rep["fuse"]
    return {"regions": [], "saved_bytes": 0}


def check_regions_and_parity():
    out = {}
    for name, builder in (("resnet_toy", _resnet_toy),
                          ("transformer_block", _transformer_block)):
        _sym, dshape, args, auxs, x = _materialize(builder)
        _m0, ref = _predict(builder, "default,-fuse", args, auxs, x, dshape)  # graftlint: disable=G001 — 2-model smoke comparison, host fetch is the point
        graph_pass.reset_stats()
        _m1, fused = _predict(builder, "default", args, auxs, x, dshape)  # graftlint: disable=G001 — 2-model smoke comparison, host fetch is the point
        summary = _fuse_summary()
        n_regions = len(summary["regions"])
        saved = summary["saved_bytes"]
        if n_regions < 1:
            raise AssertionError("%s: no fused regions carved" % name)
        if saved <= 0:
            raise AssertionError("%s: no interior bytes saved" % name)
        np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-6,
                                   err_msg="%s fused-vs-unfused" % name)
        # the real Pallas kernel path (interpret mode on CPU)
        _m2, kern = _predict(builder, "default", args, auxs, x, dshape,  # graftlint: disable=G001 — 2-model smoke comparison, host fetch is the point
                             interpret=1)
        np.testing.assert_allclose(kern, ref, rtol=2e-4, atol=1e-5,
                                   err_msg="%s kernel-vs-unfused" % name)
        out[name] = {"regions": n_regions, "saved_bytes": saved}
    return out


def check_rebind_flat():
    set_enabled(True)
    try:
        builder = _transformer_block
        _sym, dshape, args, auxs, x = _materialize(builder)
        graph_pass.set_passes("default")
        try:
            sym, _ = builder()
            mod = mx.mod.Module(sym, context=mx.cpu())
            mod.bind(data_shapes=[("data", dshape)], for_training=False)
            mod.init_params(mx.init.Uniform(0.1))
            mod.set_params(args, auxs)
            mod.predict(NDArrayIter(x, None, batch_size=x.shape[0]))
            runs0 = graph_pass.stats()["pipeline_runs"]
            small = x[:2]
            for _ in range(2):
                mod.reshape([("data", small.shape)])
                mod.predict(NDArrayIter(small, None, batch_size=2))
                mod.reshape([("data", x.shape)])
                mod.predict(NDArrayIter(x, None, batch_size=x.shape[0]))
            assert graph_pass.stats()["pipeline_runs"] == runs0, \
                "re-binds re-ran the pass pipeline under fuse"
            c1 = M.get_value("jit.compile_count", 0)
            mod.reshape([("data", small.shape)])
            mod.predict(NDArrayIter(small, None, batch_size=2))
            c2 = M.get_value("jit.compile_count", 0)
            assert c2 == c1, "a shape seen before recompiled (fused)"
        finally:
            graph_pass.set_passes(None)
    finally:
        set_enabled(False)
    return {"compile_flat": True}


def main(out_path=None):
    summary = {}
    summary["parity"] = check_regions_and_parity()
    summary["rebind"] = check_rebind_flat()
    summary["ok"] = True
    line = json.dumps(summary, sort_keys=True)
    print(line)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))

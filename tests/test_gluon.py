"""Gluon Block/Parameter/Trainer/nn (reference:
tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn


def test_parameter():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier", ctx=[mx.cpu(0), mx.cpu(1)])
    assert len(p.list_data()) == 2
    assert len(p.list_grad()) == 2
    assert p.data(mx.cpu(1)).context == mx.cpu(1)
    assert p.data(mx.cpu(0)).shape == (10, 10)
    assert p.var().name == "weight"

    p.reset_ctx(ctx=[mx.cpu(1), mx.cpu(2)])
    assert set(c.device_id for c in p.list_ctx()) == {1, 2}


def test_paramdict(tmp_path):
    params = gluon.ParameterDict("net_")
    params.get("weight", shape=(10, 10))
    assert list(params.keys()) == ["net_weight"]
    params.initialize(ctx=mx.cpu())
    fname = str(tmp_path / "test.params")
    params.save(fname)
    params.load(fname, mx.cpu())


def test_dense_deferred_init():
    net = nn.Dense(8)
    net.initialize()
    # shape unknown until first forward
    with pytest.raises(gluon.DeferredInitializationError):
        net.weight.data()
    out = net(mx.nd.ones((4, 3)))
    assert out.shape == (4, 8)
    assert net.weight.shape == (8, 3)


def test_hybridize_consistency():
    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.rand(3, 10).astype(np.float32))
    y_imp = net(x).asnumpy()
    net.hybridize()
    y_hyb = net(x).asnumpy()
    np.testing.assert_allclose(y_imp, y_hyb, rtol=1e-5, atol=1e-6)


def test_hybrid_autograd_matches_imperative():
    np.random.seed(0)

    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(8, activation="tanh"), nn.Dense(2))
        return net

    x = mx.nd.array(np.random.rand(4, 5).astype(np.float32))
    grads = []
    for hybrid in (False, True):
        net = build()
        net.collect_params().initialize(mx.init.One())
        if hybrid:
            net.hybridize()
        with mx.autograd.record():
            y = net(x).sum()
        y.backward()
        grads.append(net[0].weight.grad().asnumpy())
    np.testing.assert_allclose(grads[0], grads[1], rtol=1e-4, atol=1e-5)


def test_trainer_converges():
    np.random.seed(0)
    x = np.random.uniform(-1, 1, (256, 10)).astype(np.float32)
    w = np.random.uniform(-1, 1, (10,))
    y = (x @ w > 0).astype(np.float32)

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for epoch in range(15):
        with mx.autograd.record():
            out = net(mx.nd.array(x))
            loss = loss_fn(out, mx.nd.array(y))
        loss.backward()
        trainer.step(x.shape[0])
    preds = net(mx.nd.array(x)).asnumpy().argmax(axis=1)
    assert (preds == y).mean() > 0.9


def test_conv_bn_pool_block():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.MaxPool2D(),
                nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(3))
    net.initialize()
    out = net(mx.nd.ones((2, 1, 8, 8)))
    assert out.shape == (2, 3)


def test_block_save_load(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(5), nn.Dense(3))
    net.initialize(mx.init.Uniform(0.1))
    x = mx.nd.ones((1, 4))
    y1 = net(x).asnumpy()
    fname = str(tmp_path / "net.params")
    net.save_params(fname)

    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(5), nn.Dense(3))
    net2.load_params(fname, ctx=mx.cpu())
    np.testing.assert_allclose(net2(x).asnumpy(), y1, rtol=1e-6)


def test_embedding_block():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    out = emb(mx.nd.array([1, 2, 5]))
    assert out.shape == (3, 4)


def test_lambda_blocks():
    net = nn.Sequential()
    net.add(nn.HybridLambda("exp"))
    net.add(nn.Lambda(lambda x: x * 2))
    out = net(mx.nd.zeros((2, 2)))
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 2), 2.0), rtol=1e-6)


def test_model_zoo_forward():
    for name, shape in [("resnet18_v1", (1, 3, 32, 32)),
                        ("resnet18_v2", (1, 3, 32, 32)),
                        ("mobilenet0_25", (1, 3, 32, 32)),
                        ("squeezenet1_1", (1, 3, 64, 64))]:
        net = gluon.model_zoo.get_model(name, classes=10)
        net.initialize(mx.init.Xavier())
        out = net(mx.nd.ones(shape))
        assert out.shape == (1, 10), name


def test_symbol_block():
    data = mx.sym.Variable("data")
    out_sym = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    blk = gluon.SymbolBlock(out_sym, data)
    blk.collect_params().initialize(mx.init.One())
    out = blk(mx.nd.ones((2, 3)))
    assert out.shape == (2, 4)
    # One() pattern-dispatches *_bias to zero (reference Initializer.__call__)
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 4), 3.0), rtol=1e-5)


def test_split_and_load():
    arrs = gluon.utils.split_and_load(np.arange(8).reshape(8, 1),
                                      [mx.cpu(0), mx.cpu(1)])
    assert len(arrs) == 2
    assert arrs[0].shape == (4, 1)
    assert arrs[1].context == mx.cpu(1)


def test_clip_global_norm():
    arrs = [mx.nd.ones((3,)) * 3, mx.nd.ones((2,)) * 4]
    norm = gluon.utils.clip_global_norm(arrs, 1.0)
    total = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrs))
    assert abs(total - 1.0) < 1e-5
    assert norm > 1.0


def test_model_zoo_densenet_inception():
    # model_zoo tail (reference: model_zoo/vision/densenet.py, inception.py)
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.densenet121(classes=7)
    net.initialize(mx.init.Xavier())
    out = net(mx.nd.array(np.random.RandomState(0).rand(
        1, 3, 224, 224).astype(np.float32)))
    assert out.shape == (1, 7)
    net2 = vision.inception_v3(classes=5)
    net2.initialize(mx.init.Xavier())
    out2 = net2(mx.nd.array(np.random.RandomState(1).rand(
        1, 3, 299, 299).astype(np.float32)))
    assert out2.shape == (1, 5)
    assert np.isfinite(out2.asnumpy()).all()
    # registry surface
    assert "densenet121" in vision._models and "inception_v3" in vision._models


def test_trainer_fused_step_matches_unfused():
    """Trainer's fused local update (ALL params in one compiled program)
    is numerically identical to the per-param eager path, and optimizer
    state survives save/load across it."""
    import numpy as np

    def build(fuse):
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, activation="relu", in_units=8))
        net.add(mx.gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.initializer.Xavier())
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9,
                               "wd": 1e-3},
                              kvstore=None, fuse_step=fuse)
        return net, tr

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(8, 8).astype("float32"))
    y = mx.nd.array(rng.randint(0, 4, 8).astype("float32"))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    nets = {fuse: build(fuse) for fuse in (False, True)}

    # force identical weights across the two nets
    vals = [v.data().asnumpy() for v in
            nets[False][0].collect_params().values()]
    for net, _tr in nets.values():
        for p, w in zip(net.collect_params().values(), vals):
            p.set_data(mx.nd.array(w))

    from mxnet_tpu import autograd

    for step in range(3):
        outs = {}
        for fuse, (net, tr) in nets.items():
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(8)
            outs[fuse] = [p.data().asnumpy()
                          for p in net.collect_params().values()]
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), step

    # states roundtrip through save/load with fusing on
    import tempfile
    net, tr = nets[True]
    with tempfile.NamedTemporaryFile() as f:
        tr.save_states(f.name)
        tr.load_states(f.name)
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(8)  # still works after the roundtrip


def test_trainer_fused_step_dynamic_optimizers():
    """VERDICT r4 item 2: Adam (t-dependent bias correction) and
    SGD+MultiFactorScheduler fuse WITH fusion actually engaged — the
    per-step lr enters the compiled program as a traced scalar, so the
    schedule/bias correction stays dynamic and matches the eager path."""
    import numpy as np

    from mxnet_tpu import autograd

    def build(fuse, optimizer, opt_params):
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, activation="relu", in_units=8))
        net.add(mx.gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.initializer.Xavier())
        tr = mx.gluon.Trainer(net.collect_params(), optimizer,
                              dict(opt_params), kvstore=None,
                              fuse_step=fuse)
        return net, tr

    rng = np.random.RandomState(3)
    x = mx.nd.array(rng.randn(8, 8).astype("float32"))
    y = mx.nd.array(rng.randint(0, 4, 8).astype("float32"))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    configs = [
        ("adam", {"learning_rate": 0.01, "wd": 1e-3}),
        ("sgd", {"learning_rate": 0.2, "momentum": 0.9,
                 "lr_scheduler": mx.lr_scheduler.MultiFactorScheduler(
                     step=[2, 4], factor=0.1)}),
        ("rmsprop", {"learning_rate": 0.01}),
        # python-scalar-math optimizers: traced lr must ride through the
        # NDArray scalar dispatch (round-5 review found NAG/AdaGrad broke)
        ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
        ("adagrad", {"learning_rate": 0.05}),
        ("adadelta", {}),
        ("ftrl", {"learning_rate": 0.05}),
    ]
    for name, params in configs:
        nets = {fuse: build(fuse, name, params) for fuse in (False, True)}
        assert nets[True][1]._can_fuse(), name  # fusion actually engages
        vals = [v.data().asnumpy() for v in
                nets[False][0].collect_params().values()]
        for net, _tr in nets.values():
            for p, w in zip(net.collect_params().values(), vals):
                p.set_data(mx.nd.array(w))
        for step in range(6):
            outs = {}
            for fuse, (net, tr) in nets.items():
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                tr.step(8)
                outs[fuse] = [p.data().asnumpy()
                              for p in net.collect_params().values()]
            for a, b in zip(outs[False], outs[True]):
                np.testing.assert_allclose(
                    a, b, rtol=2e-5, atol=1e-6,
                    err_msg="%s step %d" % (name, step))


def test_trainer_fused_lr_change_no_recompile():
    """set_learning_rate and scheduler decay do NOT rebuild the fused
    program (lr is a traced input, not a baked constant)."""
    import numpy as np

    from mxnet_tpu import autograd

    net = mx.gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.initializer.Xavier())
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1}, kvstore=None)
    x = mx.nd.array(np.random.RandomState(0).randn(4, 8).astype("float32"))
    for lr in (0.1, 0.05, 0.01):
        tr.set_learning_rate(lr)
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        tr.step(4)
    # one signature, one compiled fn across all three lrs
    assert tr._fused is not None
    assert tr._fused[0] == tr._fused_signature()


def test_model_zoo_parameter_counts():
    """Exact parameter counts for the zoo architectures (the published
    gluon model-zoo numbers; reference model_zoo/vision/*). A wrong
    kernel/width/stage layout changes the count, so this pins the
    architectures without needing pretrained weights."""
    expected = {
        "resnet18_v1": 11699112,
        "resnet50_v1": 25629032,
        "resnet50_v2": 25595060,
        "alexnet": 61100840,
        "vgg16": 138357544,
        "squeezenet1_0": 1248424,
        "mobilenet1_0": 4253864,
        "densenet121": 8062504,
        "inception_v3": 23869000,
    }
    for name, want in expected.items():
        net = gluon.model_zoo.get_model(name, classes=1000)
        size = 299 if "inception" in name else 224
        net.initialize(mx.init.Xavier())
        net(mx.nd.ones((1, 3, size, size)))   # materialize deferred shapes
        got = sum(int(np.prod(p.shape))
                  for p in net.collect_params().values())
        assert got == want, (name, got, want)


def test_bench_gluon_config_engages_fusion():
    """A hybridized zoo net + Trainer(kvstore='local') on one device must
    take the FUSED update path, not the per-param dispatch path (one
    dispatch per parameter; PERF_NOTES round 4)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    net = resnet18_v1(classes=10)
    net.initialize()
    net.hybridize()
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.05}, kvstore="local")
    x = mx.nd.array(np.random.RandomState(0)
                    .rand(2, 3, 32, 32).astype(np.float32))
    y = mx.nd.array(np.array([1.0, 3.0], np.float32))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(2)
    assert tr._kvstore is None          # single-device local -> no kv
    assert tr._can_fuse()
    assert tr._fused is not None        # the fused program actually ran

    # the same setup drives compile_step (whole-step fusion);
    # guard that this exact setup compiles and runs it
    step = tr.compile_step(net, loss_fn)
    step(x, y)
    assert step.compile_count == 1


def test_gluon_nd_conv_pool_blocks():
    """1-D/3-D conv, transpose-conv and pool blocks (reference
    conv_layers.py surface — Conv3DTranspose was missing r5)."""
    import torch
    import torch.nn.functional as F

    rng = np.random.RandomState(4)
    x1 = rng.randn(2, 3, 12).astype(np.float32)
    c1 = nn.Conv1D(5, 3, strides=2, padding=1, in_channels=3)
    c1.initialize(mx.init.Xavier())
    out1 = c1(mx.nd.array(x1))
    want1 = F.conv1d(torch.tensor(x1),
                     torch.tensor(c1.weight.data().asnumpy()),
                     torch.tensor(c1.bias.data().asnumpy()),
                     stride=2, padding=1).numpy()
    np.testing.assert_allclose(out1.asnumpy(), want1, rtol=1e-4,
                               atol=1e-5)

    x3 = rng.randn(1, 2, 4, 5, 6).astype(np.float32)
    t3 = nn.Conv3DTranspose(3, (2, 2, 2), strides=(2, 2, 2),
                            in_channels=2)
    t3.initialize(mx.init.Xavier())
    out3 = t3(mx.nd.array(x3))
    want3 = F.conv_transpose3d(
        torch.tensor(x3), torch.tensor(t3.weight.data().asnumpy()),
        torch.tensor(t3.bias.data().asnumpy()), stride=2).numpy()
    np.testing.assert_allclose(out3.asnumpy(), want3, rtol=1e-4,
                               atol=1e-5)

    p3 = nn.MaxPool3D(pool_size=2, strides=2)
    outp = p3(mx.nd.array(x3))
    wantp = F.max_pool3d(torch.tensor(x3), 2, 2).numpy()
    np.testing.assert_allclose(outp.asnumpy(), wantp, rtol=1e-5)


def test_compile_step_matches_eager():
    """Trainer.compile_step (whole fwd+bwd+update as ONE program) matches
    the eager record/backward/step path: weights, loss values, and BN
    moving stats, across SGD-momentum and Adam+MultiFactorScheduler."""
    import numpy as np

    from mxnet_tpu import autograd

    def build(opt_name, opt_params):
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, in_units=8))
        net.add(mx.gluon.nn.BatchNorm())
        net.add(mx.gluon.nn.Activation("relu"))
        net.add(mx.gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.initializer.Xavier())
        net.hybridize()
        net(mx.nd.zeros((2, 8)))  # materialize deferred-shape params (BN)
        tr = mx.gluon.Trainer(net.collect_params(), opt_name,
                              dict(opt_params), kvstore=None)
        return net, tr

    rng = np.random.RandomState(7)
    x = mx.nd.array(rng.randn(8, 8).astype("float32"))
    y = mx.nd.array(rng.randint(0, 4, 8).astype("float32"))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    sched = mx.lr_scheduler.MultiFactorScheduler(step=[2, 4], factor=0.5)
    for opt_name, opt_params in (
            ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3}),
            ("adam", {"learning_rate": 0.01, "lr_scheduler": sched})):
        eager_net, eager_tr = build(opt_name, opt_params)
        fused_net, fused_tr = build(opt_name, opt_params)
        for pe, pf in zip(eager_net.collect_params().values(),
                          fused_net.collect_params().values()):
            pf.set_data(mx.nd.array(pe.data().asnumpy()))

        step = fused_tr.compile_step(fused_net, loss_fn)
        for it in range(5):
            with autograd.record():
                loss_e = loss_fn(eager_net(x), y)
            loss_e.backward()
            eager_tr.step(8)
            loss_f = step(x, y)
            np.testing.assert_allclose(loss_f.asnumpy(), loss_e.asnumpy(),
                                       rtol=1e-5, atol=1e-6)
        for (ne, pe), (nf, pf) in zip(
                sorted(eager_net.collect_params().items()),
                sorted(fused_net.collect_params().items())):
            np.testing.assert_allclose(
                pf.data().asnumpy(), pe.data().asnumpy(),
                rtol=2e-5, atol=2e-6,
                err_msg="%s/%s diverged under %s" % (ne, nf, opt_name))
        # BN moving stats must have moved off init AND match
        bn_moved = any("running_mean" in n and
                       np.abs(p.data().asnumpy()).max() > 0
                       for n, p in fused_net.collect_params().items())
        assert bn_moved, "fused step did not update BN moving stats"
        # the scheduler's lr changes must NOT have recompiled the program
        assert step.compile_count == 1, \
            "compile_step recompiled %d times" % step.compile_count


def test_compile_step_rng_ops():
    """Dropout inside a compiled step draws fresh randomness per call."""
    import numpy as np

    from mxnet_tpu import autograd  # noqa: F401

    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(32, in_units=8))
    net.add(mx.gluon.nn.Dropout(0.5))
    net.add(mx.gluon.nn.Dense(4, in_units=32))
    net.initialize()
    net.hybridize()
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.0}, kvstore=None)
    step = tr.compile_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
    rng = np.random.RandomState(3)
    x = mx.nd.array(rng.randn(8, 8).astype("float32"))
    y = mx.nd.array(rng.randint(0, 4, 8).astype("float32"))
    losses = {tuple(step(x, y).asnumpy().tolist()) for _ in range(4)}
    assert len(losses) > 1, "dropout mask appears frozen across steps"


def test_compile_step_frozen_params():
    """grad_req='null' params must survive the fused step intact (the
    donation set excludes them) and remain usable by later steps and
    eager forwards."""
    import numpy as np

    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(16, in_units=8))
    net.add(mx.gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    frozen = list(net.collect_params().values())[0]
    frozen.grad_req = "null"
    before = frozen.data().asnumpy().copy()

    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1}, kvstore=None)
    step = tr.compile_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
    rng = np.random.RandomState(11)
    x = mx.nd.array(rng.randn(8, 8).astype("float32"))
    y = mx.nd.array(rng.randint(0, 4, 8).astype("float32"))
    step(x, y)
    step(x, y)  # second step reads the frozen buffer again
    np.testing.assert_array_equal(frozen.data().asnumpy(), before)
    net(x).asnumpy()  # eager forward still works


def test_compile_step_rejects_kvstore():
    """compile_step is a local fused path; kvstore-backed trainers must
    be rejected loudly, not silently update locally."""
    import pytest as _pytest

    net = mx.gluon.nn.Dense(4, in_units=8)
    net.initialize()
    net.hybridize()
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1}, kvstore="device")
    tr._init_kvstore()
    if tr._kvstore is None:  # single-device local resolves to no store
        import mxnet_tpu.kvstore as kvs
        tr._kvstore = kvs.create("local")
    step = tr.compile_step(net, mx.gluon.loss.SoftmaxCrossEntropyLoss())
    x = mx.nd.ones((4, 8))
    y = mx.nd.zeros((4,))
    with _pytest.raises(ValueError, match="kvstore"):
        step(x, y)

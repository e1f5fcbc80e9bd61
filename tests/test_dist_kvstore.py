"""Distributed KVStore fake-cluster test — the reference's
tests/nightly/dist_sync_kvstore.py pattern: N local processes (here wired by
jax.distributed over the CPU backend instead of ps-lite ZMQ), asserting
dist_sync push/pull semantics and sync-SGD parity with single-process.

These workers create SEVERAL dist stores per process on purpose: a second
``mx.kv.create("dist_sync")`` must reuse the live client rather than re-run
``initialize()`` after computations have executed ("must be called before
any JAX computations"). ``_ensure_distributed`` guards on
``jax.distributed.is_initialized()``; the kv2/kv3/kv4/kv5 creates below are
the regression."""
import os
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from launch import launch_local  # noqa: E402

_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %(repo)r)
    import numpy as np
    import mxnet_tpu as mx

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == %(n)d, (rank, nw)
    shape = (3, 2)

    # push/pull: sum across workers (dist_sync accumulate semantics)
    kv.init("w", mx.nd.ones(shape))
    kv.push("w", mx.nd.ones(shape) * (rank + 1))
    out = mx.nd.zeros(shape)
    kv.pull("w", out=out)
    expect = sum(r + 1 for r in range(nw))
    assert np.allclose(out.asnumpy(), expect), (rank, out.asnumpy())

    # updater path: sync-SGD parity with the single-process result
    kv2 = mx.kv.create("dist_sync")
    kv2.init("p", mx.nd.ones(shape))
    opt = mx.optimizer.create("sgd", learning_rate=0.1, rescale_grad=1.0)
    kv2.set_optimizer(opt)
    kv2.push("p", mx.nd.ones(shape) * (rank + 1))
    got = mx.nd.zeros(shape)
    kv2.pull("p", out=got)
    # merged grad = sum(rank+1); sgd: w - lr*merged
    expect_w = 1.0 - 0.1 * expect
    assert np.allclose(got.asnumpy(), expect_w, atol=1e-6), (
        rank, got.asnumpy(), expect_w)

    kv._barrier()

    # row-sparse push stays sparse on the wire: disjoint rows per worker
    from mxnet_tpu.ndarray import sparse as sp
    kv3 = mx.kv.create("dist_sync")
    kv3.init("e", mx.nd.zeros((6, 2)))
    g = np.zeros((6, 2), np.float32)
    g[rank] = rank + 1          # worker r touches row r
    g[5] = 0.5                  # and everyone touches row 5
    kv3.push("e", sp.row_sparse_array(g))
    out3 = mx.nd.zeros((6, 2))
    kv3.pull("e", out=out3)
    exp3 = np.zeros((6, 2), np.float32)
    for r in range(nw):
        exp3[r] = r + 1
    exp3[5] = 0.5 * nw
    assert np.allclose(out3.asnumpy(), exp3), (rank, out3.asnumpy())

    # dist_lenet pattern (tests/nightly/dist_lenet.py): multi-step MLP
    # training sharded across workers must match the serial reference
    rng = np.random.RandomState(42)
    X = rng.rand(8 * nw, 5).astype(np.float32)
    Y = (X[:, 0] > 0.5).astype(np.float32)
    W0 = rng.randn(2, 5).astype(np.float32) * 0.1

    def grads(w, xs, ys):
        # linear softmax: analytic gradient, deterministic
        logits = xs @ w.T
        p = np.exp(logits - logits.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        onehot = np.eye(2, dtype=np.float32)[ys.astype(int)]
        return ((p - onehot).T @ xs) / len(xs)

    kv4 = mx.kv.create("dist_sync")
    kv4.init("w", mx.nd.array(W0))
    opt4 = mx.optimizer.create("sgd", learning_rate=0.5, rescale_grad=1.0)
    kv4.set_optimizer(opt4)
    shard = slice(rank * 8, (rank + 1) * 8)
    w_ref = W0.copy()
    wbuf = mx.nd.zeros(W0.shape)
    for step in range(10):
        kv4.pull("w", out=wbuf)
        w_cur = wbuf.asnumpy()
        kv4.push("w", mx.nd.array(grads(w_cur, X[shard], Y[shard])))
        # serial reference: sum of shard gradients at the same weights
        gsum = sum(grads(w_ref, X[r * 8:(r + 1) * 8], Y[r * 8:(r + 1) * 8])
                   for r in range(nw))
        w_ref = w_ref - 0.5 * gsum
    kv4.pull("w", out=wbuf)
    assert np.allclose(wbuf.asnumpy(), w_ref, rtol=1e-5, atol=1e-6), (
        rank, np.abs(wbuf.asnumpy() - w_ref).max())

    # 2-bit compressed dist push: packed codes on the wire
    kv5 = mx.kv.create("dist_sync")
    kv5.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv5.init("c", mx.nd.zeros((2, 3)))
    gc = np.full((2, 3), 0.6, np.float32) * (1 if rank %% 2 == 0 else -1)
    kv5.push("c", mx.nd.array(gc))
    outc = mx.nd.zeros((2, 3))
    kv5.pull("c", out=outc)
    n_pos = (nw + 1) // 2
    n_neg = nw - n_pos
    expc = 0.5 * (n_pos - n_neg)
    assert np.allclose(outc.asnumpy(), expc, atol=1e-6), (
        rank, outc.asnumpy(), expc)

    kv._barrier()
    print("WORKER_OK", rank)
""")


@pytest.mark.parametrize("n", [2, 3])
def test_dist_sync_fake_cluster(n):
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = _WORKER % {"repo": repo, "n": n}
    procs = launch_local(n, [sys.executable, "-c", script])
    outputs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outputs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, "worker %d failed:\n%s" % (i, out)
        assert "WORKER_OK" in out


def test_dist_async_exists():
    # dist_async is the PS path now — covered in tests/test_dist_async.py
    kv = mx.kv.create("dist_async")
    try:
        assert kv.type == "dist_async"
    finally:
        kv.close()


def test_gradient_compression_2bit_local():
    # reference invariants (tests/nightly/dist_sync_kvstore.py compression
    # section): quantized pushes are in {0, +-threshold} and the error
    # feedback residual recovers dropped mass on later pushes
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", mx.nd.zeros((2, 2)))
    g = np.array([[0.3, 0.6], [-0.7, 0.1]], np.float32)
    kv.push("w", mx.nd.array(g))
    out = mx.nd.zeros((2, 2))
    kv.pull("w", out=out)
    # first push: 0.3->0 (residual), 0.6->+0.5, -0.7->-0.5, 0.1->0
    np.testing.assert_allclose(out.asnumpy(),
                               [[0.0, 0.5], [-0.5, 0.0]], atol=1e-6)
    kv.push("w", mx.nd.array(g))
    kv.pull("w", out=out)
    # residuals (0.3,0.1,-0.2,0.1) + g: 0.6->0.5, 0.7->0.5, -0.9->-0.5, 0.2->0
    np.testing.assert_allclose(out.asnumpy(),
                               [[0.5, 0.5], [-0.5, 0.0]], atol=1e-6)


def test_gradient_compression_unknown_type_raises():
    kv = mx.kv.create("local")
    with pytest.raises(mx.MXNetError):
        kv.set_gradient_compression({"type": "8bit"})


def test_dist_without_launcher_raises():
    env_backup = {k: os.environ.pop(k) for k in
                  ("MXTPU_COORDINATOR", "MXTPU_NUM_WORKERS",
                   "MXTPU_WORKER_ID") if k in os.environ}
    try:
        with pytest.raises(mx.MXNetError):
            mx.kv.create("dist_sync")
    finally:
        os.environ.update(env_backup)

"""Ring attention + multi-axis transformer parallelism tests (new TPU-first
capability beyond the reference — SURVEY.md §2.3 lists sequence/tensor/
expert parallelism as absent upstream; task requirement: long-context and
distributed are first-class)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _qkv(B=2, H=4, T=32, D=8, seed=0):
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, H, T, D).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_ring_attention_matches_dense(causal, n_shards):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    mesh = Mesh(np.array(jax.devices("cpu")[:n_shards]), ("sp",))
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_gradients():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("sp",))
    q, k, v = _qkv(seed=1)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_ring_attention_with_head_sharding():
    # tp x sp: each tensor-parallel shard rides its own sequence ring
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("tp", "sp"))
    q, k, v = _qkv(H=4, seed=2)
    out = ring_attention(q, k, v, mesh, causal=True, head_axis="tp")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_transformer_multi_axis_training():
    # one compiled step over a dp x tp x sp x ep mesh; loss must drop
    import jax

    from mxnet_tpu.parallel import TransformerParallel
    from mxnet_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "tp": 2, "sp": 2, "ep": 2},
                     devices=jax.devices("cpu")[:8])
    tr = TransformerParallel(mesh, vocab=32, d_model=16, n_heads=4,
                             n_layers=2, d_ff=32, n_experts=2)
    params = tr.init()
    r = np.random.RandomState(0)
    toks = r.randint(0, 32, (2, 16)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    tok_s, tgt_s = tr.shard_batch(toks, tgts)
    step = tr.step_fn(lr=0.5)
    losses = []
    for _ in range(30):
        params, loss = step(params, tok_s, tgt_s)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_transformer_dp_parity():
    # the same step on a dp=4 mesh reproduces the single-device losses
    import jax

    from mxnet_tpu.parallel import TransformerParallel
    from mxnet_tpu.parallel.mesh import make_mesh

    r = np.random.RandomState(3)
    toks = r.randint(0, 16, (4, 8)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)

    def run(mesh_axes, n_dev):
        mesh = make_mesh(mesh_axes, devices=jax.devices("cpu")[:n_dev])
        tr = TransformerParallel(mesh, vocab=16, d_model=8, n_heads=2,
                                 n_layers=1, d_ff=16, n_experts=2)
        params = tr.init()
        tok_s, tgt_s = tr.shard_batch(toks, tgts)
        step = tr.step_fn(lr=0.2)
        out = []
        for _ in range(5):
            params, loss = step(params, tok_s, tgt_s)
            out.append(float(loss))
        return out

    single = run({"dp": 1}, 1)
    multi = run({"dp": 4}, 4)
    np.testing.assert_allclose(single, multi, rtol=2e-3)


def test_ring_attention_with_batch_sharding():
    # dp x sp: batch rows stay sharded through the ring (no all-gather)
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "sp"))
    q, k, v = _qkv(B=4, seed=4)
    out = ring_attention(q, k, v, mesh, causal=True, batch_axis="dp")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_group2ctx_via_bind():
    # bind() (not just simple_bind) must honor group2ctx
    from tests.test_model_parallel import _int_net, _int_fill

    net = _int_net()
    g2c = {"stage1": mx.Context("cpu", 0), "stage2": mx.Context("cpu", 1)}
    args = {n: mx.nd.zeros(s) for n, s in
            zip(net.list_arguments(), net.infer_shape(data=(2, 5))[0])}
    ex = net.bind(mx.cpu(0), args=args, group2ctx=g2c)
    assert ex._ctx_map and len(ex._ctx_map) == 2


def test_transformer_step_fn_lr_not_stale():
    import jax

    from mxnet_tpu.parallel import TransformerParallel
    from mxnet_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    tr = TransformerParallel(mesh, vocab=8, d_model=8, n_heads=2,
                             n_layers=1, d_ff=8, n_experts=1)
    assert tr.step_fn(lr=0.1) is tr.step_fn(lr=0.1)
    assert tr.step_fn(lr=0.1) is not tr.step_fn(lr=0.01)


def test_pipeline_parallel_gpipe():
    # pp axis: GPipe microbatch schedule == sequential stage application
    # (fwd and grads); tolerances cover CPU fastmath-vs-compiled drift
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline import pipeline_apply

    S = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:S]), ("pp",))
    r = np.random.RandomState(0)
    W = jnp.asarray(r.randn(S, 6, 6).astype(np.float32) * 0.3)
    b = jnp.asarray(r.randn(S, 6).astype(np.float32) * 0.1)

    def stage(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    x = jnp.asarray(r.randn(8, 6).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        out = pipeline_apply(stage, {"w": W, "b": b}, x, mesh,
                             n_microbatches=4)
        ref = x
        for i in range(S):
            ref = jnp.tanh(ref @ W[i] + b[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

        g_pipe = jax.grad(lambda W: jnp.sum(pipeline_apply(
            stage, {"w": W, "b": b}, x, mesh, n_microbatches=4) ** 2))(W)

        def seq(W):
            h = x
            for i in range(S):
                h = jnp.tanh(h @ W[i] + b[i])
            return jnp.sum(h ** 2)

        g_seq = jax.grad(seq)(W)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               rtol=1e-3, atol=2e-4)


def test_pipeline_stage_count_mismatch_raises():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline import pipeline_apply

    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("pp",))
    W = jnp.zeros((4, 3, 3), jnp.float32)  # 4 stages on a pp=2 mesh
    with pytest.raises(ValueError):
        pipeline_apply(lambda p, x: x @ p, W,
                       jnp.zeros((4, 3), jnp.float32), mesh)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret(causal):
    # the Pallas kernel in interpreter mode vs the dense oracle, compared
    # under full matmul precision (CPU fastmath otherwise dominates)
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention_reference, flash_attention

    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(2, 2, 64, 16).astype(np.float32))
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_attention_ragged_seq_picks_divisor_blocks():
    # block sizes are bounds: T=48 with bound 32 runs with block 24/16
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention_reference, flash_attention

    r = np.random.RandomState(5)
    q, k, v = (jnp.asarray(r.randn(1, 2, 48, 16).astype(np.float32))
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              block_k=32, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_attention_prime_seq_falls_back():
    # prime T has no usable divisor blocks; the XLA formula takes over
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention_reference, flash_attention

    r = np.random.RandomState(6)
    q, k, v = (jnp.asarray(r.randn(1, 1, 127, 8).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_transformer_parallel_checkpoint_resume(tmp_path, dtype):
    """tp/ep-sharded parameters checkpoint whole and reload onto the
    mesh with identical continued training (sharded-state resume) —
    bfloat16 included, which .npz cannot name (first met on the chip:
    the bf16 LM's checkpoint -> Generator handoff, PR 21)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)

    from mxnet_tpu.parallel import TransformerParallel
    from mxnet_tpu.parallel.mesh import make_mesh

    r = np.random.RandomState(0)
    toks = r.randint(0, 16, (2, 8)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    mesh = make_mesh({"dp": 1, "tp": 2, "ep": 2},
                     devices=jax.devices("cpu")[:4])
    tr = TransformerParallel(mesh, vocab=16, d_model=8, n_heads=2,
                             n_layers=1, d_ff=16, n_experts=2, dtype=dtype)
    params = tr.init(seed=1)
    tok_s, tgt_s = tr.shard_batch(toks, tgts)
    step = tr.step_fn(lr=0.2)
    for _ in range(2):
        params, _ = step(params, tok_s, tgt_s)
    path = str(tmp_path / "tp_ckpt")
    tr.save_checkpoint(params, path)
    for _ in range(2):
        params, loss_ref = step(params, tok_s, tgt_s)

    tr2 = TransformerParallel(mesh, vocab=16, d_model=8, n_heads=2,
                              n_layers=1, d_ff=16, n_experts=2, dtype=dtype)
    resumed = tr2.load_checkpoint(path)
    assert resumed["l0_wq"].dtype == dtype
    # shardings restored, not just values
    assert resumed["l0_wq"].sharding.spec == params["l0_wq"].sharding.spec
    step2 = tr2.step_fn(lr=0.2)
    for _ in range(2):
        resumed, loss2 = step2(resumed, tok_s, tgt_s)
    assert float(loss2) == float(loss_ref)
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(resumed[k]))

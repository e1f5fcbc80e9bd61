"""Flash-attention backward kernel tests (parallel/flash_attention.py).

The training-side contract of the long-context path: the vjp runs tiled
recompute Pallas kernels (dq pass + dk/dv pass) from O(T) residuals —
gradient parity vs the dense reference across causal/non-causal,
fp32/bf16, block-fallback shapes; plus the memory regression guard that
no T x T tensor survives the forward."""
import numpy as np
import pytest

from mxnet_tpu import config


def _qkv(B=2, H=2, T=64, D=16, dtype=np.float32, seed=0):
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, H, T, D).astype(np.float32))
                 .astype(dtype) for _ in range(3))


def _grads(fn, q, k, v):
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_dense_fp32(causal):
    import jax
    import functools

    from mxnet_tpu.parallel import attention_reference, flash_attention

    q, k, v = _qkv()
    flash = functools.partial(flash_attention, causal=causal, block_q=16,
                              block_k=16, block_q_bwd=16, block_k_bwd=16,
                              interpret=True)
    ref = functools.partial(attention_reference, causal=causal)
    with jax.default_matmul_precision("highest"):
        gf = _grads(flash, q, k, v)
        gr = _grads(ref, q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg="d%s causal=%s" % (name, causal))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_dense_bf16(causal):
    import jax
    import jax.numpy as jnp
    import functools

    from mxnet_tpu.parallel import attention_reference, flash_attention

    q, k, v = _qkv(dtype=jnp.bfloat16, seed=1)
    flash = functools.partial(flash_attention, causal=causal, block_q=16,
                              block_k=16, block_q_bwd=16, block_k_bwd=16,
                              interpret=True)
    ref = functools.partial(attention_reference, causal=causal)
    with jax.default_matmul_precision("highest"):
        gf = _grads(flash, q, k, v)
        gr = _grads(ref, q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        # bf16 inputs: compare against the dense grads at bf16 resolution
        tol = 2e-2 * max(1.0, float(np.abs(b).max()))
        assert float(np.abs(a - b).max()) < tol, \
            "d%s causal=%s: %s" % (name, causal, float(np.abs(a - b).max()))


def test_flash_bwd_uneven_blocks():
    # bwd block bounds pick divisors independently of the fwd's
    import jax
    import functools

    from mxnet_tpu.parallel import attention_reference, flash_attention

    q, k, v = _qkv(B=1, T=48, seed=2)
    flash = functools.partial(flash_attention, causal=True, block_q=32,
                              block_k=32, block_q_bwd=24, block_k_bwd=16,
                              interpret=True)
    with jax.default_matmul_precision("highest"):
        gf = _grads(flash, q, k, v)
        gr = _grads(functools.partial(attention_reference, causal=True),
                    q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_bwd_prime_seq_fallback_grads():
    # prime-ish T routes the whole op through the dense fallback; grads
    # must still match the reference there
    import jax
    import functools

    from mxnet_tpu.parallel import attention_reference, flash_attention

    q, k, v = _qkv(B=1, H=1, T=127, D=8, seed=3)
    with jax.default_matmul_precision("highest"):
        gf = _grads(functools.partial(flash_attention, causal=True,
                                      interpret=True), q, k, v)
        gr = _grads(functools.partial(attention_reference, causal=True),
                    q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_flash_fwd_residuals_are_linear_in_T():
    """Memory regression guard: the saved residuals are O(T) per head —
    no T x T tensor may survive the forward (that was the dense-autodiff
    vjp's footprint, and the whole point of the backward kernels)."""
    import jax

    from mxnet_tpu.parallel import flash_attention

    T = 64
    q, k, v = _qkv(T=T)
    _, vjp_fn = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16,
                                        block_k=16, interpret=True),
        q, k, v)
    leaves = jax.tree_util.tree_leaves(vjp_fn)
    assert leaves, "vjp carried no residuals?"
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        assert not (len(shape) >= 2 and shape[-1] == T and shape[-2] == T), \
            "T x T residual leaked into the vjp: %s" % (shape,)
    # and the residual footprint is exactly the O(T) set: q, k, v, o
    # (4 x B*H*T*D) + lse (B*H*T)
    B, H, D = q.shape[0], q.shape[1], q.shape[3]
    n_elem = sum(int(np.prod(l.shape)) for l in leaves)
    assert n_elem <= 4 * B * H * T * D + B * H * T + T, n_elem


def test_flash_bwd_lse_cotangent():
    # return_lse output is differentiable too (the ring merge needs it)
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import flash_attention
    from mxnet_tpu.parallel.flash_attention import _dense_with_lse

    q, k, v = _qkv(seed=4)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, block_q_bwd=16,
                                   block_k_bwd=16, interpret=True,
                                   return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        out, lse = _dense_with_lse(q, k, v, causal=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    with jax.default_matmul_precision("highest"):
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg="d" + name)


def test_flash_bwd_config_escape_hatch():
    # MXNET_FLASH_ATTENTION_BWD=0 restores the dense-autodiff vjp and
    # still produces correct gradients
    import jax
    import functools

    from mxnet_tpu.parallel import attention_reference, flash_attention

    q, k, v = _qkv(seed=5)
    config.set_flag("MXNET_FLASH_ATTENTION_BWD", 0)
    try:
        with jax.default_matmul_precision("highest"):
            gf = _grads(functools.partial(flash_attention, causal=True,
                                          block_q=16, block_k=16,
                                          interpret=True), q, k, v)
            gr = _grads(functools.partial(attention_reference,
                                          causal=True), q, k, v)
    finally:
        config.set_flag("MXNET_FLASH_ATTENTION_BWD", None)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_ring_attention_flash_flag_force():
    # MXNET_RING_ATTENTION_FLASH=2 forces the kernel on any backend,
    # switching on interpret mode off-TPU (the documented contract)
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    n = min(2, len(jax.devices("cpu")))
    if n < 2:
        pytest.skip("needs >= 2 cpu devices")
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("sp",))
    q, k, v = _qkv(B=1, H=2, T=16, D=8, seed=7)
    config.set_flag("MXNET_RING_ATTENTION_FLASH", 2)
    try:
        out = ring_attention(q, k, v, mesh, causal=True)
    finally:
        config.set_flag("MXNET_RING_ATTENTION_FLASH", None)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_path(causal):
    # the ring inherits the kernels: per-step local attention is the
    # Pallas kernel, partial results merge via lse — fwd and grads match
    # the dense oracle
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import attention_reference, ring_attention

    n = min(4, len(jax.devices("cpu")))
    if n < 2:
        pytest.skip("needs >= 2 cpu devices")
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("sp",))
    q, k, v = _qkv(B=2, H=4, T=32, D=8, seed=6)
    # under jit, as the transformer step runs it: the eager shard_map
    # interpreter executes the same ring op by op and takes ~7x as long
    with jax.default_matmul_precision("highest"):
        def ring(q, k, v):
            return ring_attention(q, k, v, mesh, causal=causal,
                                  use_flash=True, interpret=True)

        out = jax.jit(ring)(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

        def ring_loss(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(attention_reference(q, k, v,
                                               causal=causal) ** 2)

        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)

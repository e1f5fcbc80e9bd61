"""Flash-attention backward kernel tests (parallel/flash_attention.py).

The training-side contract of the long-context path: the vjp runs tiled
recompute Pallas kernels from O(T) residuals — ONE fused pass (dk, dv and
the whole head's dq) where the shape fits the VMEM budget, else a dk/dv
pass and a dq pass. Gradient parity vs the dense reference on both paths
across causal/non-causal, fp32/bf16, block-fallback shapes — T = 64 in
16-wide tiles, so mask-free interior tiles, tiles the diagonal crosses
and dead tiles all occur; plus the memory regression guard that no T x T
tensor survives the forward."""
import functools
import importlib

import numpy as np
import pytest

from mxnet_tpu import config

# the package re-exports the function under the module's own name
flash_module = importlib.import_module("mxnet_tpu.parallel.flash_attention")


@pytest.fixture(params=["fused", "two_pass"])
def bwd_path(request, monkeypatch):
    """Both backwards: the static rule picks the fused pass at these
    sizes; a budget of nothing sends the same call down the two passes."""
    if request.param == "two_pass":
        monkeypatch.setattr(flash_module, "_FUSED_BWD_VMEM_BUDGET", 0)
    return request.param


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


def _flash(causal, bq_bwd=16, bk_bwd=16, fwd=16):
    from mxnet_tpu.parallel import flash_attention

    return functools.partial(flash_attention, causal=causal, block_q=fwd,
                             block_k=fwd, block_q_bwd=bq_bwd,
                             block_k_bwd=bk_bwd, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_dense_fp32(causal, bwd_path):
    import jax

    from mxnet_tpu.parallel import attention_reference

    q, k, v = _qkv()
    ref = functools.partial(attention_reference, causal=causal)
    with jax.default_matmul_precision("highest"):
        gf = _grads(_flash(causal), q, k, v)
        gr = _grads(ref, q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg="d%s causal=%s" % (name, causal))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_dense_bf16(causal, bwd_path):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention_reference

    q, k, v = _qkv(dtype=jnp.bfloat16, seed=1)
    ref = functools.partial(attention_reference, causal=causal)
    with jax.default_matmul_precision("highest"):
        gf = _grads(_flash(causal), q, k, v)
        gr = _grads(ref, q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        assert a.dtype == jnp.bfloat16
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        # bf16 inputs: compare against the dense grads at bf16 resolution
        tol = 2e-2 * max(1.0, float(np.abs(b).max()))
        assert float(np.abs(a - b).max()) < tol, \
            "d%s causal=%s: %s" % (name, causal, float(np.abs(a - b).max()))


@pytest.mark.parametrize("bq_bwd,bk_bwd", [(24, 16), (16, 24), (12, 48)])
def test_flash_bwd_uneven_blocks(bq_bwd, bk_bwd, bwd_path):
    # bwd block bounds pick divisors independently of the fwd's, and of
    # each other: the diagonal crosses tiles off their corners
    import jax

    from mxnet_tpu.parallel import attention_reference

    q, k, v = _qkv(B=1, T=48, seed=2)
    with jax.default_matmul_precision("highest"):
        gf = _grads(_flash(True, bq_bwd, bk_bwd, fwd=32), q, k, v)
        gr = _grads(functools.partial(attention_reference, causal=True),
                    q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,causal", [("float32", True),
                                          ("float32", False),
                                          ("bfloat16", True)])
def test_fused_and_two_pass_backward_agree(dtype, causal, monkeypatch):
    # one algorithm on both paths: the same tiles in the same order, so
    # the gradients agree to float32 rounding (bf16: to one rounding of
    # the result)
    import jax
    import jax.numpy as jnp

    q, k, v = _qkv(dtype=jnp.dtype(dtype), seed=8)
    flash = _flash(causal, 16, 32)
    with jax.default_matmul_precision("highest"):
        fused = _grads(flash, q, k, v)
        monkeypatch.setattr(flash_module, "_FUSED_BWD_VMEM_BUDGET", 0)
        two_pass = _grads(flash, q, k, v)
    tol = 1e-6 if dtype == "float32" else 1e-2
    for name, a, b in zip("qkv", fused, two_pass):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg="d" + name)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense_at_lane_width_tiles(causal):
    # 512-wide tiles as the chip runs them: the forward's statistics 128
    # lanes wide, its 128-row sub-chunks and the backward's 128-key ones
    # stopping at the diagonal inside a tile that sits on it
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import flash_attention
    from mxnet_tpu.parallel.flash_attention import _dense_with_lse

    q, k, v = _qkv(B=1, H=1, T=1024, D=8, seed=10)

    def loss(attend):
        def fn(q, k, v):
            out, lse = attend(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
        return fn

    flash = functools.partial(flash_attention, causal=causal, block_q=512,
                              block_k=512, block_q_bwd=512, block_k_bwd=512,
                              interpret=True, return_lse=True)
    dense = functools.partial(_dense_with_lse, causal=causal)
    with jax.default_matmul_precision("highest"):
        gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg="d" + name)


def test_flash_bwd_selection_is_static_and_counted(monkeypatch):
    # the path is chosen from shapes while tracing: one trace serves
    # every call, and each traced backward counts once on its own counter
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import observability as obs

    traces = []

    def loss(q, k, v):
        traces.append(1)
        return jnp.sum(_flash(True)(q, k, v) ** 2)

    q, k, v = _qkv(seed=9)
    obs.set_enabled(True)
    obs.reset_metrics()
    try:
        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        first = step(q, k, v)
        second = step(q, k, v)
        assert len(traces) == 1
        assert obs.metrics.get_value("flash_attention.bwd_fused") == 1
        assert obs.metrics.get_value("flash_attention.bwd_two_pass",
                                     0) == 0
        monkeypatch.setattr(flash_module, "_FUSED_BWD_VMEM_BUDGET", 0)
        third = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        assert obs.metrics.get_value("flash_attention.bwd_fused") == 1
        assert obs.metrics.get_value("flash_attention.bwd_two_pass") == 1
    finally:
        obs.reset_metrics()
        obs.set_enabled(False)
    for a, b, c in zip(first, second, third):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("T,D,Dv,dtype,causal,fused", [
    (2048, 128, 128, "bfloat16", True, True),    # lm_train_t2048_b2
    (8192, 192, 128, "bfloat16", True, True),    # sarvam_train_t8192_b1
    (8192, 128, 128, "bfloat16", True, True),    # lm_train_t8192_b1 (queued)
    (8192, 128, 128, "bfloat16", False, True),   # a ring step of lm_train_sp4
    (4096, 128, 128, "float32", True, True),
    (32768, 128, 128, "bfloat16", True, False),  # 44.0 MiB > 40: two passes
])
def test_default_tiles_at_the_cells_shapes(T, D, Dv, dtype, causal, fused,
                                           monkeypatch):
    # the constants the ledger's numbers rest on: with no bound named, the
    # compiled call resolves 2048/2048 forward, 1024/1024 backward and the
    # backward the VMEM rule picks — read off the kernel builders' own
    # arguments while the public function is traced (nothing runs)
    import jax
    import jax.numpy as jnp

    seen = {}

    def spy(name):
        build = getattr(flash_module, name)

        def wrapper(*args):
            seen[name] = args
            return build(*args)

        monkeypatch.setattr(flash_module, name, wrapper)

    spy("_forward_call")
    spy("_backward_call")

    def loss(q, k, v):
        return jnp.sum(flash_module.flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    q, v = (jax.ShapeDtypeStruct((1, 1, T, w), jnp.dtype(dtype))
            for w in (D, Dv))
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert seen["_forward_call"][2:4] == (2048, 2048)
    assert seen["_backward_call"][2:5] == (1024, 1024, fused)
    assert seen["_forward_call"][0] is seen["_backward_call"][0] is causal
    assert flash_module._bwd_is_fused(
        T, D, 1024, 1024, jnp.dtype(dtype).itemsize, Dv=Dv) is fused


def test_tuning_cache_cannot_move_the_flash_kernels(monkeypatch):
    # the kernels' tiles come from the caller or this file's constants:
    # neither a tuning-cache entry under the old tunables' names and key
    # nor the old flags in the environment change the lowered program
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import autotune
    from mxnet_tpu.autotune import cache

    def lowered():
        def loss(q, k, v):
            return jnp.sum(flash_module.flash_attention(
                q, k, v, causal=True, interpret=True) ** 2)

        q = jax.ShapeDtypeStruct((1, 2, 128, 16), jnp.float32)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text()

    clean = lowered()
    for op in ("flash_attention.fwd", "flash_attention.bwd"):
        autotune.record(op, ("T128", "D16", "causal"),
                        {"block_q": 32, "block_k": 64}, dtype="float32",
                        persist=False)
    # the five flags PR 30 removed (spelled in parts: the names are gone
    # from the tree, and a grep for them should stay empty)
    for flag, value in (("BLOCK_Q", 32), ("BLOCK_K", 32), ("BWD_BLOCK_Q", 32),
                        ("BWD_BLOCK_K", 32), ("ATTENTION_BWD", 0)):
        monkeypatch.setenv("MXNET_FLASH_" + flag, str(value))
    cache.reset_stats()
    try:
        assert lowered() == clean
        assert cache.stats()["hits"] == cache.stats()["misses"] == 0
    finally:
        cache.reset()


def test_vmem_bytes_counts_the_narrower_v_and_the_fused_dq():
    count = flash_module.flash_vmem_bytes
    # a 128-wide v beside 192-wide q/k: 64 columns fewer in do, v, dv
    # (double-buffered, bf16) and in dv's fp32 accumulator
    assert (count(1024, 1024, 192, 2, backward=True)
            - count(1024, 1024, 192, 2, backward=True, Dv=128)
            == 2 * (1024 + 2 * 1024) * 64 * 2 + 1024 * 64 * 4)
    # the fused backward holds the whole head's fp32 dq and its resident
    # output block (double-buffered like every tile) on top of the tiles
    assert (count(512, 512, 128, 2, backward=True, T=2048)
            - count(512, 512, 128, 2, backward=True)
            == 2048 * 128 * 4 + 2 * 2048 * 128 * 2)


@pytest.mark.parametrize("D,Dv,itemsize,last", [
    (128, None, 2, 27648),    # 39.0 MiB; 28,672 would take 40.02
    (192, 128, 2, 17408),     # latent attention's heads
    (128, None, 4, 16384),
])
def test_vmem_bytes_boundary_of_the_fused_backward(D, Dv, itemsize, last):
    # the last T the static rule fuses at the default 1024/1024 tiles,
    # and the next multiple of the tile declined
    fused = flash_module._bwd_is_fused
    assert fused(last, D, 1024, 1024, itemsize, Dv=Dv)
    assert not fused(last + 1024, D, 1024, 1024, itemsize, Dv=Dv)
    assert (flash_module.flash_vmem_bytes(
        1024, 1024, D, itemsize, backward=True, T=last, Dv=Dv)
        <= flash_module._FUSED_BWD_VMEM_BUDGET < flash_module._VMEM_LIMIT)


def test_fused_backward_rule_follows_the_vmem_budget():
    # (T, D) against the budget, nothing else: the cell's shape and the
    # longest local length the repo's queue names fuse, a head whose
    # fp32 dq alone outgrows the budget does not
    fused = flash_module._bwd_is_fused
    assert fused(2048, 128, 512, 512, 2)
    assert fused(8192, 128, 512, 512, 2)
    assert fused(8192, 128, 1024, 1024, 4)
    assert not fused(131072, 128, 512, 512, 2)


def test_flash_bwd_prime_seq_fallback_grads():
    # prime-ish T routes the whole op through the dense fallback; grads
    # must still match the reference there
    import jax

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


def test_flash_bwd_lse_cotangent(bwd_path):
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

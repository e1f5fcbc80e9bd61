"""Rotary positions inside the flash kernels (``flash_attention``'s
``rope`` operand, parallel/flash_attention.py): q and k arrive unrotated,
the kernels rotate them tile by tile, and the backward writes the
gradients of the UNROTATED q and k. Against the same kernels fed q and k
rotated in XLA (``rotated``, ``lm_layers.apply_rope`` on the range), in
the Pallas interpreter on float32: the output and all three gradients,
through the fused and the two-pass backward, at the three forms the
models take — every channel of a 128-wide head under groups of 8, the
first 64 of 128 scaled by an ``attention_factor`` under a window, and
channels 128-191 of a 192-wide head over a 128-wide v (latent attention).
Then the counter ``flash_attention.rotary`` in a traced step of each LM
family: one a rotated layer in the two that rotate, none elsewhere."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu.observability as obs
from mxnet_tpu.parallel import lm_layers, make_mesh
from mxnet_tpu.parallel.transformer import TransformerParallel

fa = importlib.import_module("mxnet_tpu.parallel.flash_attention")
F32 = jnp.float32
T = 64


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# name: (D, Dv, group, rotated channels, their first, window, rotary block)
CASES = {
    "full_grouped": (128, 128, 8, 128, 0, None, {"theta": 10000}),
    "partial_window": (128, 128, 1, 64, 0, 23, {
        "theta": 500000, "factor": 64, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 16,
        "attention_factor": 1.4158883083359672}),
    "latent": (192, 128, 1, 64, 128, None, {"theta": 10000}),
}


@pytest.mark.parametrize("two_pass", [False, True], ids=["fused", "two_pass"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rotation_in_the_kernels_against_the_rotation_before_them(
        case, two_pass, monkeypatch):
    D, Dv, group, rot, start, window, block = CASES[case]
    if two_pass:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_BUDGET", 0)
        monkeypatch.setattr(fa, "_GROUPED_FUSED_BWD_VMEM_BUDGET", 0)
    ks = jax.random.split(jax.random.PRNGKey(D + rot + group), 4)
    q = jax.random.normal(ks[0], (1, group, T, D), F32)
    k = jax.random.normal(ks[1], (1, 1, T, D), F32)
    v = jax.random.normal(ks[2], (1, 1, T, Dv), F32)
    do = jax.random.normal(ks[3], (1, group, T, Dv), F32)
    cos, sin = lm_layers.rope_tables(T, rot, block)
    rope = (cos, sin, start)
    tiles = dict(block_q=16, block_k=16, block_q_bwd=16, block_k_bwd=16)
    scale = 1.1 / math.sqrt(D)

    def in_kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  window=window, interpret=True, rope=rope,
                                  **tiles)

    def before_them(q, k, v):
        return fa.flash_attention(fa.rotated(q, rope), fa.rotated(k, rope),
                                  v, causal=True, scale=scale, window=window,
                                  interpret=True, **tiles)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                        (0, 1, 2))(q, k, v)

    assert rel(in_kernels(q, k, v), before_them(q, k, v)) < 2e-6
    for g, w, what in zip(grads(in_kernels), grads(before_them),
                          ("dq", "dk", "dv")):
        assert g.shape == w.shape, what
        assert rel(g, w) < 2e-6, what
    # the rotation is no identity here: the unrotated operands differ
    assert rel(fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  window=window, interpret=True, **tiles),
               before_them(q, k, v)) > 1e-2


def test_tables_that_do_not_fit_the_heads_are_refused():
    q = jnp.zeros((1, 2, T, 16), F32)
    cos, sin = lm_layers.rope_tables(T, 16, {"theta": 10000})
    with pytest.raises(ValueError, match="rotary tables"):
        fa.flash_attention(q, q, q, causal=True, interpret=True,
                           rope=(cos, sin, 8))
    with pytest.raises(ValueError, match="rotary tables"):
        fa.flash_attention(q, q, q, causal=True, interpret=True,
                           rope=(cos[:T // 2], sin[:T // 2]))


def test_the_vmem_count_holds_the_tables_and_the_rotated_tiles():
    """Both routed cells' backwards stay fused with the rotation's VMEM
    counted: laguna's layers under the grouped budget, sarvam's 192-wide
    heads under the plain one."""
    plain = fa.flash_vmem_bytes(1024, 1024, 192, 2, backward=True, T=8192,
                                Dv=128)
    rotary = fa.flash_vmem_bytes(1024, 1024, 192, 2, backward=True, T=8192,
                                 Dv=128, rope_half=32)
    # cos and sin tiles at q and at k, lanes padded to 128, two buffers;
    # the rotated q and k; four float32 (1024, 192) intermediates
    assert rotary - plain == (2 * 2 * 2048 * 128 * 4 + 2048 * 192 * 2
                              + 4 * 1024 * 192 * 4)
    assert fa._bwd_is_fused(8192, 192, 1024, 1024, 2, Dv=128, rope_half=32)
    assert fa._bwd_is_fused(8192, 128, 1024, 1024, 2, group=6, rope_half=32)
    assert fa._bwd_is_fused(8192, 128, 512, 512, 2, group=8, rope_half=64)


def _laguna():
    from test_gqa_layers import small_cfg
    return TransformerParallel.from_config(_one_chip(), small_cfg(5),
                                           remat=True)


def _sarvam():
    from test_lm_layers import small_cfg
    return TransformerParallel.from_config(_one_chip(), small_cfg(5),
                                           remat=True)


def _phi4flash():
    from test_sambay_layers import tiny
    return TransformerParallel.from_config(_one_chip(), tiny(), remat=True)


def _first_lm():
    return TransformerParallel(_one_chip(), vocab=64, d_model=32, n_heads=4,
                               n_layers=2, d_ff=64, n_experts=2)


def _one_chip():
    return make_mesh({"dp": 1}, devices=jax.devices()[:1])


@pytest.mark.parametrize("family,rotated_calls", [
    (_laguna, 5), (_sarvam, 5), (_phi4flash, 0), (_first_lm, 0)],
    ids=["laguna", "sarvam", "phi4flash", "first_lm"])
def test_a_traced_step_counts_its_rotated_flash_calls(
        family, rotated_calls, monkeypatch):
    """Traced as on the chip (the flash path is the TPU's, T = 128): one
    count a layer whose flash call takes the tables, however often the
    recomputed layers run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs.set_enabled(True)
    model = family()
    params = model.init(3)
    tokens = jnp.zeros((2, 128), jnp.int32)
    before = obs.metrics.get_value("flash_attention.rotary", 0)
    jax.make_jaxpr(jax.grad(model.loss_fn))(params, tokens, tokens)
    assert (obs.metrics.get_value("flash_attention.rotary", 0) - before
            == rotated_calls)

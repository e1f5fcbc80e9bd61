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
Each row is rotated once a call and held in VMEM (the forward's k, the
fused backward's q): the cases take groups of 3 and unequal q and k tiles,
so that rows are held off the diagonal. Then the counters
``flash_attention.rotary`` and ``flash_attention.rotary_held`` in a traced
step of each LM family: one a rotated layer in the two that rotate, none
elsewhere, and none held where the backward takes two passes."""
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


YARN = {"theta": 500000, "factor": 64, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 16,
        "attention_factor": 1.4158883083359672}
#: the kernels' tiles where a case names none: four q and k blocks
SQUARE = dict(block_q=16, block_k=16, block_q_bwd=16, block_k_bwd=16)
# name: (D, Dv, group, rotated channels, their first, window, rotary block,
# tiles). The held rows are filled at the first step that sees them: off
# the diagonal where the forward's q and k tiles differ — a q block that
# is the first to see two k blocks, or none
CASES = {
    "full_grouped": (128, 128, 8, 128, 0, None, {"theta": 10000}, SQUARE),
    "partial_window": (128, 128, 1, 64, 0, 23, YARN, SQUARE),
    "latent": (192, 128, 1, 64, 128, None, {"theta": 10000}, SQUARE),
    "group3_wide_q": (128, 128, 3, 128, 0, None, {"theta": 10000}, dict(
        block_q=32, block_k=16, block_q_bwd=16, block_k_bwd=32)),
    "group3_wide_k": (128, 128, 3, 64, 0, None, YARN, dict(
        block_q=16, block_k=32, block_q_bwd=32, block_k_bwd=16)),
    "group3_window": (128, 128, 3, 128, 0, 23, {"theta": 10000}, dict(
        block_q=32, block_k=16, block_q_bwd=16, block_k_bwd=16)),
    "latent_group3": (192, 128, 3, 64, 128, 40, {"theta": 10000}, dict(
        block_q=16, block_k=32, block_q_bwd=16, block_k_bwd=16)),
}


@pytest.mark.parametrize("two_pass", [False, True], ids=["fused", "two_pass"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rotation_in_the_kernels_against_the_rotation_before_them(
        case, two_pass, monkeypatch):
    D, Dv, group, rot, start, window, block, tiles = CASES[case]
    if two_pass:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_BUDGET", 0)
        monkeypatch.setattr(fa, "_GROUPED_FUSED_BWD_VMEM_BUDGET", 0)
    ks = jax.random.split(jax.random.PRNGKey(D + rot + group), 4)
    # two k/v heads: the second group's rows are held anew
    q = jax.random.normal(ks[0], (1, 2 * group, T, D), F32)
    k = jax.random.normal(ks[1], (1, 2, T, D), F32)
    v = jax.random.normal(ks[2], (1, 2, T, Dv), F32)
    do = jax.random.normal(ks[3], (1, 2 * group, T, Dv), F32)
    cos, sin = lm_layers.rope_tables(T, rot, block)
    rope = (cos, sin, start)
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
    counted — the group's rotated q held whole beside dq: laguna's layers
    under the grouped budget, sarvam's 192-wide heads under the plain one
    — and the forwards, which hold a head's rotated k, fit the scoped
    limit."""
    plain = fa.flash_vmem_bytes(1024, 1024, 192, 2, backward=True, T=8192,
                                Dv=128)
    rotary = fa.flash_vmem_bytes(1024, 1024, 192, 2, backward=True, T=8192,
                                 Dv=128, rope_half=32)
    # cos and sin tiles at q and at k, lanes padded to 128, two buffers;
    # the rotated k tile and the head's 8192 rotated q rows held; four
    # float32 (1024, 192) intermediates
    assert rotary - plain == (2 * 2 * 2048 * 128 * 4
                              + (1024 + 8192) * 192 * 2
                              + 4 * 1024 * 192 * 4)
    # the group's q: laguna's full layer holds 6 * 8192 rows
    grouped = fa.flash_vmem_bytes(1024, 1024, 128, 2, backward=True,
                                  T=6 * 8192, rope_half=32)
    assert grouped - fa.flash_vmem_bytes(
        1024, 1024, 128, 2, backward=True, T=6 * 8192) == (
            2 * 2 * 2048 * 128 * 4 + (1024 + 6 * 8192) * 128 * 2
            + 4 * 1024 * 128 * 4)
    # the two-pass dk/dv pass still rotates a q and a k tile
    assert fa.flash_vmem_bytes(1024, 1024, 192, 2, backward=True, Dv=128,
                               rope_half=32) - fa.flash_vmem_bytes(
        1024, 1024, 192, 2, backward=True, Dv=128) == (
            2 * 2 * 2048 * 128 * 4 + 2048 * 192 * 2 + 4 * 1024 * 192 * 4)
    assert fa._bwd_is_fused(8192, 192, 1024, 1024, 2, Dv=128, rope_half=32)
    assert fa._bwd_is_fused(8192, 128, 1024, 1024, 2, group=6, rope_half=32)
    assert fa._bwd_is_fused(8192, 128, 512, 512, 2, group=8, rope_half=64)
    # the forward: a q tile rotated and the head's (8192, D) k held
    for bq, D, Dv, half, limit in ((2048, 192, 128, 32, fa._VMEM_LIMIT),
                                   (2048, 128, 128, 32,
                                    fa._GROUPED_VMEM_LIMIT),
                                   (512, 128, 128, 64,
                                    fa._GROUPED_VMEM_LIMIT)):
        fwd = fa.flash_vmem_bytes(bq, bq, D, 2, T=8192, Dv=Dv,
                                  rope_half=half)
        assert fwd - fa.flash_vmem_bytes(bq, bq, D, 2, Dv=Dv) == (
            2 * 2 * 2 * bq * 128 * 4 + (bq + 8192) * D * 2
            + 4 * bq * D * 4)
        assert fwd < limit


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


@pytest.mark.parametrize("family,rotated_calls,held_calls,two_pass", [
    (_laguna, 5, 5, False), (_sarvam, 5, 5, False), (_phi4flash, 0, 0, False),
    (_first_lm, 0, 0, False), (_laguna, 5, 0, True), (_sarvam, 5, 0, True)],
    ids=["laguna", "sarvam", "phi4flash", "first_lm", "laguna_two_pass",
         "sarvam_two_pass"])
def test_a_traced_step_counts_its_rotated_flash_calls(
        family, rotated_calls, held_calls, two_pass, monkeypatch):
    """Traced as on the chip (the flash path is the TPU's, T = 128): one
    count a layer whose flash call takes the tables, however often the
    recomputed layers run — and ``flash_attention.rotary_held`` one a
    call whose backward is the fused pass, which holds the rotated rows
    as the forward does; a call that falls to the two passes counts
    none."""
    if two_pass:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_BUDGET", 0)
        monkeypatch.setattr(fa, "_GROUPED_FUSED_BWD_VMEM_BUDGET", 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs.set_enabled(True)
    model = family()
    params = model.init(3)
    tokens = jnp.zeros((2, 128), jnp.int32)
    names = ("flash_attention.rotary", "flash_attention.rotary_held")
    before = [obs.metrics.get_value(n, 0) for n in names]
    jax.make_jaxpr(jax.grad(model.loss_fn))(params, tokens, tokens)
    assert [obs.metrics.get_value(n, 0) - b for n, b in zip(
        names, before)] == [rotated_calls, held_calls]

"""Grouped-query, windowed flash attention (parallel/flash_attention.py)
against the dense formula: out, lse and all three gradients, in the
Pallas interpreter on float32. T = 64 in 16-wide tiles (or unequal ones),
so tiles the diagonal crosses, tiles the band's far edge crosses, tiles
between the two, and dead tiles on both sides all occur."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu.observability as obs

fa = importlib.import_module("mxnet_tpu.parallel.flash_attention")
F32 = jnp.float32
T = 64


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _operands(group, T=T, D=16, Dv=16, B=2, Hkv=2):
    ks = jax.random.split(jax.random.PRNGKey(7 * group + T), 5)
    q = jax.random.normal(ks[0], (B, Hkv * group, T, D), F32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), F32)
    v = jax.random.normal(ks[2], (B, Hkv, T, Dv), F32)
    do = jax.random.normal(ks[3], (B, Hkv * group, T, Dv), F32)
    dlse = jax.random.normal(ks[4], (B, Hkv * group, T), F32)
    return q, k, v, do, dlse


def _value_and_grads(fn, q, k, v, do, dlse):
    def total(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * do) + jnp.sum(lse * dlse), (out, lse)
    return jax.value_and_grad(total, (0, 1, 2), has_aux=True)(q, k, v)


def _check(group, window, tiles, two_pass, monkeypatch, T=T):
    if two_pass:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_BUDGET", 0)
        monkeypatch.setattr(fa, "_GROUPED_FUSED_BWD_VMEM_BUDGET", 0)
    q, k, v, do, dlse = _operands(group, T)
    scale = 1.1 / math.sqrt(q.shape[-1])
    bq, bk = tiles
    (_, (out, lse)), grads = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, scale=scale, window=window, block_q=bq,
            block_k=bk, block_q_bwd=bq, block_k_bwd=bk, interpret=True,
            return_lse=True), q, k, v, do, dlse)
    (_, (want_out, want_lse)), want = _value_and_grads(
        lambda q, k, v: fa._dense_with_lse(
            q, k, v, causal=True, scale=scale, window=window),
        q, k, v, do, dlse)
    assert out.shape == want_out.shape and rel(out, want_out) < 2e-5
    assert rel(lse, want_lse) < 2e-5
    for got, ref, what in zip(grads, want, ("dq", "dk", "dv")):
        assert got.shape == ref.shape, what
        assert rel(got, ref) < 5e-5, what


# window: none; smaller than T and a multiple of the tile; not a multiple
# of the tile; one position; equal to T; larger than T
WINDOWS = [None, 32, 23, 1, T, 3 * T]


@pytest.mark.parametrize("two_pass", [False, True], ids=["fused", "two_pass"])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("group", [1, 6, 8])
def test_grouped_windowed_flash_against_the_dense_formula(
        group, window, two_pass, monkeypatch):
    _check(group, window, (16, 16), two_pass, monkeypatch)


@pytest.mark.parametrize("two_pass", [False, True], ids=["fused", "two_pass"])
@pytest.mark.parametrize("tiles", [(32, 16), (16, 32), (8, 64), (64, 64)])
def test_a_window_under_unequal_tiles(tiles, two_pass, monkeypatch):
    """q and k tiles of different heights, and one tile a head: the band's
    live range is read off both tile heights."""
    _check(3, 20, tiles, two_pass, monkeypatch)


def test_the_dense_oracle_masks_what_the_equations_say():
    """``i - window < j <= i`` written out, with query head ``h`` on k/v
    head ``h // group``."""
    q, k, v, _, _ = _operands(3, T=12, B=1)
    out, lse = fa._dense_with_lse(q, k, v, causal=True, scale=0.25, window=5)
    i, j = np.arange(12)[:, None], np.arange(12)[None, :]
    mask = (j <= i) & (j > i - 5)
    for h in range(6):
        s = np.asarray(q[0, h]) @ np.asarray(k[0, h // 3]).T * 0.25
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        want = (p / p.sum(1, keepdims=True)) @ np.asarray(v[0, h // 3])
        assert rel(out[0, h], want) < 1e-5
        assert rel(lse[0, h], np.log(p.sum(1)) + s.max(1)) < 1e-5


@pytest.mark.parametrize("length", [60, 37])
def test_a_length_the_kernels_decline_lowers_the_dense_formula(length):
    """No 16-tile divides 60 eight times short of the bound, and 37 is
    prime: the same mask and the same k/v head, through XLA."""
    q, k, v, do, dlse = _operands(2, T=length)
    got = fa.flash_attention(q, k, v, causal=True, window=9, block_q=48,
                             block_k=48, interpret=True)
    want, _ = fa._dense_with_lse(q, k, v, causal=True, window=9)
    assert rel(got, want) < 1e-6


def test_the_counters_count_windowed_calls_and_group_sizes():
    obs.set_enabled(True)
    read = lambda n: obs.metrics.get_value("flash_attention." + n, 0)
    before = read("windowed"), read("kv_group")
    q, k, v, _, _ = _operands(6)
    kw = dict(causal=True, block_q=16, block_k=16, interpret=True)
    fa.flash_attention(q, k, v, window=8, **kw)
    fa.flash_attention(q, k, v, **kw)                   # grouped, no window
    fa.flash_attention(q, k, v, window=T, **kw)         # the causal mask
    fa.flash_attention(q[:, :2], k, v, window=8, **kw)  # windowed, group 1
    assert read("windowed") - before[0] == 2
    assert read("kv_group") - before[1] == 18


def test_the_kernels_of_a_windowed_call_hold_window_in_their_names(
        monkeypatch):
    q, k, v, do, _ = _operands(2)
    kw = dict(causal=True, block_q=16, block_k=16, block_q_bwd=16,
              block_k_bwd=16, interpret=True)

    def names(window, budget=None):
        if budget is not None:
            monkeypatch.setattr(fa, "_GROUPED_FUSED_BWD_VMEM_BUDGET", budget)
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            fa.flash_attention(q, k, v, window=window, **kw) * do)))(q))
        return {w for w in ("flash_attention_window_fwd",
                            "flash_attention_window_bwd_dqkv",
                            "flash_attention_window_bwd_dkv",
                            "flash_attention_window_bwd_dq",
                            "flash_attention_fwd", "flash_attention_bwd_dqkv")
                if w in text}

    assert names(None) == {"flash_attention_fwd", "flash_attention_bwd_dqkv"}
    assert names(8) == {"flash_attention_window_fwd",
                        "flash_attention_window_bwd_dqkv",
                        "flash_attention_window_bwd_dq"}    # a substring
    assert names(8, 0) == {"flash_attention_window_fwd",
                           "flash_attention_window_bwd_dkv",
                           "flash_attention_window_bwd_dq"}


def test_a_window_without_causal_and_uneven_heads_are_refused():
    q, k, v, _, _ = _operands(3)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.flash_attention(q, k, v, window=8, interpret=True)
    with pytest.raises(ValueError, match="query heads over"):
        fa.flash_attention(q[:, :5], k, v, causal=True, interpret=True)


def test_vmem_count_of_a_grouped_fused_backward():
    """The fused pass plans for the whole group's dq, ``group * T`` rows,
    under the grouped calls' own budget: fused at the cell's 6 and 8 heads
    a group, two passes where a group's dq outgrows it."""
    one = fa.flash_vmem_bytes(1024, 1024, 128, 2, backward=True, T=8192)
    six = fa.flash_vmem_bytes(1024, 1024, 128, 2, backward=True, T=6 * 8192)
    assert six - one == 5 * 8192 * 128 * (2 * 2 + 4)
    assert fa._bwd_is_fused(8192, 128, 1024, 1024, 2)
    assert not fa._bwd_is_fused(6 * 8192, 128, 1024, 1024, 2)
    assert fa._bwd_is_fused(8192, 128, 1024, 1024, 2, group=6)
    assert fa._bwd_is_fused(8192, 128, 512, 512, 2, group=8)
    assert not fa._bwd_is_fused(8192, 128, 1024, 1024, 2, group=16)
    assert fa._GROUPED_FUSED_BWD_VMEM_BUDGET < fa._GROUPED_VMEM_LIMIT


@pytest.mark.parametrize("tiles,window,of_q,want", [
    ((512, 512), 512, True, 2), ((512, 512), 512, False, 2),
    ((256, 256), 512, True, 3), ((1024, 512), 512, True, 3),
    ((1024, 512), 512, False, 2), ((128, 128), 512, True, 5),
    ((512, 512), 514, True, 3), ((2048, 2048), 512, True, 2)])
def test_a_windowed_grid_walks_the_band_alone(tiles, window, of_q, want):
    """The inner grid axis of a windowed pass is as long as the widest
    band of live blocks, not as the sequence: 2 of 16 at the cell's 512."""
    assert fa._band_blocks(8192, *tiles, window, of_q) == want

"""The gated short-convolution kernel pair (parallel/short_conv.py) in the
Pallas interpreter against the plain formula written as a shifted sum:
``y``, ``d bcx`` and ``dw``, over one row tile, several, a length that is
no multiple of the tile bound, a length with no legal tile (the formula
itself), more than one sequence and more than one channel tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.observability import metrics as obs
from mxnet_tpu.parallel.short_conv import short_conv, short_conv_xla


def plain(bcx, w):
    """``y_t = C_t * sum_s M_ts B_s x_s`` with the causal band ``M_ts =
    w_k`` where ``s = t - K + 1 + k``, as one dense (T, T) matrix a
    channel."""
    d = bcx.shape[-1] // 3
    K, T = w.shape[0], bcx.shape[1]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    lag = np.arange(T)[:, None] - np.arange(T)[None, :]    # t - s
    band = sum(jnp.asarray(lag == K - 1 - k, w.dtype)[..., None] * w[k]
               for k in range(K))                          # (T, T, d)
    return c * jnp.einsum("tsd,bsd->btd", band, b * x,
                          precision="highest")


def value_and_grads(fn, bcx, w, weight):
    return jax.value_and_grad(
        lambda a, b: jnp.sum(fn(a, b).astype(jnp.float32) * weight),
        argnums=(0, 1))(bcx, w)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


#: (sequences, length, channels, row bound, channel bound, kernel runs):
#: one tile; four, the look-back and look-ahead crossing three edges; 80
#: rows under a bound of 32 (tiles of 16); two channel tiles; 50 rows (no
#: tile of 16 divides it: the formula itself)
CASES = {
    "one_tile": (1, 32, 128, 32, 128, True),
    "four_tiles": (2, 64, 128, 16, 128, True),
    "not_a_multiple_of_the_bound": (2, 80, 128, 32, 128, True),
    "two_channel_tiles": (2, 48, 256, 16, 128, True),
    "no_legal_tile": (2, 50, 128, 32, 128, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_the_plain_formula(case):
    B, T, d, rows, channels, runs = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(T + d), 3)
    f32 = jnp.float32
    bcx = jax.random.normal(keys[0], (B, T, 3 * d), f32)
    w = jax.random.normal(keys[1], (3, d), f32)
    weight = jax.random.normal(keys[2], (B, T, d), f32)
    obs.set_enabled(True)
    before = obs.get_value("short_conv.kernel", 0)
    kernel = lambda a, b: short_conv(a, b, rows=rows, channels=channels,
                                     interpret=True)
    got_y, (got_dbcx, got_dw) = value_and_grads(kernel, bcx, w, weight)
    assert obs.get_value("short_conv.kernel", 0) - before == int(runs)
    want_y, (want_dbcx, want_dw) = value_and_grads(plain, bcx, w, weight)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5)
    assert rel(kernel(bcx, w), plain(bcx, w)) < 1e-6
    assert rel(got_dbcx, want_dbcx) < 1e-6
    assert rel(got_dw, want_dw) < 1e-6
    # the formula the kernels fall back to, and the CPU path takes
    assert rel(short_conv_xla(bcx, w), plain(bcx, w)) < 1e-6


def test_bfloat16_operands_keep_their_type_and_sum_in_float32():
    B, T, d = 2, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    bcx = jax.random.normal(keys[0], (B, T, 3 * d)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], (3, d)).astype(jnp.bfloat16)
    kernel = lambda a, b: short_conv(a, b, rows=16, channels=128,
                                     interpret=True)
    y, vjp = jax.vjp(kernel, bcx, w)
    dbcx, dw = vjp(jnp.ones_like(y))
    assert (y.dtype, dbcx.dtype, dw.dtype) == (jnp.bfloat16,) * 3
    f32 = lambda a: a.astype(jnp.float32)
    want, vjp32 = jax.vjp(plain, f32(bcx), f32(w))
    want_dbcx, want_dw = vjp32(jnp.ones_like(want))
    assert rel(f32(y), want) < 1e-2
    assert rel(f32(dbcx), want_dbcx) < 1e-2
    assert rel(f32(dw), want_dw) < 1e-2

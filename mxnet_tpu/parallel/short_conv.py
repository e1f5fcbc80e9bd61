"""Gated short convolution (the token mixer of an LFM2 ``conv`` layer) as a
Pallas TPU kernel pair, forward and backward.

The in-projection's output ``bcx`` (B, T, 3d) holds three gates side by
side, ``[B | C | x~]``, and with a depthwise causal filter ``w`` (K, d)

    z_t = B_t * x~_t
    y_t = C_t * sum_{k < K} w_k * z_{t - K + 1 + k}      (z before 0 is 0)

Written in XLA the three gates, the look-back of ``K - 1`` rows and the
weight gradient's sum over (B, T) would each make their own passes over
HBM; here one pass reads ``bcx`` and writes ``y``, and one pass reads
``bcx`` and ``dy`` and writes ``d bcx`` (one (B, T, 3d) array, which the
in-projection's backward reads as it is) and ``dw``. The kernels read B,
C and x~ as three block views of ``bcx`` (index maps over its last
axis): no gate is copied out. The grid runs over (channel tile, sequence,
row tile), the rows in order; each step carries the last rows of its
``z`` to the next in VMEM (the look-back), and the backward reads the
first rows of the next tile's ``C`` and ``dy`` (the look-ahead of ``dc =
dy * C``, which ``dz_t = sum_k w_k dc_{t + K - 1 - k}`` needs) as a block
of ``_EDGE`` rows. The backward's grid has a fourth axis of three steps
over which the same inputs stay in VMEM and ``d bcx``'s three thirds are
written in turn. Arithmetic is float32 inside; ``dw`` is summed in float32
over every (b, t). docs/short_conv.md has the derivation and the bytes.

Off the TPU, and at a shape with no legal tile, :func:`short_conv_xla`
computes the same formula in ``jax.numpy``; ``interpret=True`` runs the
kernels in the Pallas interpreter.
"""
from __future__ import annotations

import functools

from ..observability import counter
from .pallas_common import LANES, aligned_block, pallas_call

__all__ = ["short_conv", "short_conv_xla"]

#: rows of the sequence a grid step holds (the v5e sweep: docs/short_conv.md)
_ROWS = 512
#: channels a grid step holds
_CHANNELS = 1024
#: rows at each edge of a tile that the shifts across its edges touch: the
#: bfloat16 tile's height. The forward carries the last ``_EDGE`` rows of a
#: tile's ``z`` to the next (the look-back); the backward also reads the
#: first ``_EDGE`` rows of the next tile's ``C`` and ``dy`` (the look-ahead)
_EDGE = 16
_VMEM_LIMIT = 64 * 2 ** 20


def _compiler_params(n_axes):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (n_axes - 1),
        vmem_limit_bytes=_VMEM_LIMIT)


# --- the filter along the rows -----------------------------------------
def _back(z, w_ref):
    """``sum_s w_{K-1-s} z_{t-s}`` down the rows of ``z`` by sublane rolls,
    which wrap around: the first ``K - 1`` rows are wrong, and
    :func:`_back_head` gives the first ``_EDGE`` right."""
    from jax.experimental.pallas import tpu as pltpu

    K = w_ref.shape[0]
    out = w_ref[K - 1] * z
    for s in range(1, K):
        out = out + w_ref[K - 1 - s] * pltpu.roll(z, s, 0)
    return out


def _back_head(z, before, w_ref):
    """The first ``_EDGE`` rows of :func:`_back`, with ``before`` (the
    ``_EDGE`` rows that came before the tile) in place of the wrap."""
    import jax.numpy as jnp

    return _back(jnp.concatenate([before, z[:_EDGE]], 0), w_ref)[_EDGE:]


def _ahead(dc, w_ref):
    """``sum_s w_{K-1-s} dc_{t+s}`` up the rows: the last ``K - 1`` rows
    are wrong, and :func:`_ahead_tail` gives the last ``_EDGE`` right."""
    from jax.experimental.pallas import tpu as pltpu

    K, rows = w_ref.shape[0], dc.shape[0]
    out = w_ref[K - 1] * dc
    for s in range(1, K):
        out = out + w_ref[K - 1 - s] * pltpu.roll(dc, rows - s, 0)
    return out


def _ahead_tail(dc, after, w_ref):
    """The last ``_EDGE`` rows of :func:`_ahead`, with ``after`` (the
    ``_EDGE`` rows that come after the tile) in place of the wrap."""
    import jax.numpy as jnp

    return _ahead(jnp.concatenate([dc[-_EDGE:], after], 0), w_ref)[:_EDGE]


# --- the kernels ---------------------------------------------------------
def _fwd_kernel(b_ref, c_ref, x_ref, w_ref, y_ref, back_scr):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        back_scr[...] = jnp.zeros_like(back_scr)  # graftlint: disable=G003 — a Pallas kernel writes its refs

    z = b_ref[0].astype(f32) * x_ref[0].astype(f32)
    y_ref[0] = (c_ref[0].astype(f32) * _back(z, w_ref)).astype(y_ref.dtype)
    y_ref[0, :_EDGE] = (c_ref[0, :_EDGE].astype(f32)
                        * _back_head(z, back_scr[...], w_ref)).astype(
                            y_ref.dtype)
    back_scr[...] = z[-_EDGE:]


def _bwd_kernel(b_ref, c_ref, x_ref, c_next_ref, w_ref, dy_ref, dy_next_ref,
                d_ref, dw_ref, back_scr, dc_scr, dx_scr):
    """Step (channel tile, b, row tile, third): at third 0 everything is
    computed and ``dB`` written, ``dC`` and ``dx~`` wait in VMEM for
    thirds 1 and 2."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    K = w_ref.shape[0]
    t, third = pl.program_id(2), pl.program_id(3)

    @pl.when((pl.program_id(1) == 0) & (t == 0) & (third == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when((t == 0) & (third == 0))
    def _():
        back_scr[...] = jnp.zeros_like(back_scr)  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when(third == 0)
    def _():
        bb, xx = b_ref[0].astype(f32), x_ref[0].astype(f32)
        z = bb * xx
        before = back_scr[...]
        dy = dy_ref[0].astype(f32)
        # dC = dy * conv(z), its first rows with the carried look-back
        dc_scr[...] = (dy * _back(z, w_ref)).astype(dc_scr.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs
        dc_scr[:_EDGE] = (dy[:_EDGE] * _back_head(z, before, w_ref)).astype(  # graftlint: disable=G003 — a Pallas kernel writes its refs
            dc_scr.dtype)
        # dz = the filter turned round on dc = dy * C, its last rows with
        # the next tile's first (nothing after the last tile)
        dc = dy * c_ref[0].astype(f32)
        last = t == pl.num_programs(2) - 1
        after = jnp.where(last, 0.0, dy_next_ref[0].astype(f32)
                          * c_next_ref[0].astype(f32))
        dz = _ahead(dc, w_ref)
        dz_tail = _ahead_tail(dc, after, w_ref)
        d_ref[0] = (dz * xx).astype(d_ref.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs
        d_ref[0, -_EDGE:] = (dz_tail * xx[-_EDGE:]).astype(d_ref.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs
        dx_scr[...] = (dz * bb).astype(dx_scr.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs
        dx_scr[-_EDGE:] = (dz_tail * bb[-_EDGE:]).astype(dx_scr.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs
        # dw_k = sum_t dc_t z_{t-K+1+k}: the wrapped rows from the head
        head = jnp.concatenate([before, z[:_EDGE]], 0)
        for k in range(K):
            s = K - 1 - k
            lagged = pltpu.roll(z, s, 0) if s else z
            lagged_head = (pltpu.roll(head, s, 0) if s else head)[_EDGE:]
            dw_ref[k] += (jnp.sum((dc * lagged)[_EDGE:], axis=0, keepdims=True)  # graftlint: disable=G003 — a Pallas kernel writes its refs
                          + jnp.sum(dc[:_EDGE] * lagged_head, axis=0,
                                    keepdims=True))
        back_scr[...] = z[-_EDGE:]  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when(third == 1)
    def _():
        d_ref[0] = dc_scr[...]  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when(third == 2)
    def _():
        d_ref[0] = dx_scr[...]  # graftlint: disable=G003 — a Pallas kernel writes its refs


@functools.lru_cache(maxsize=None)
def _calls(shape, taps, rows, channels, dtype, interpret):
    """(forward, backward) ``pallas_call``s for ``bcx`` of ``shape`` (B, T,
    3d) and ``taps`` (K) filter rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, three_d = shape
    d = three_d // 3
    nC, nT = d // channels, T // rows
    f32 = jnp.float32

    def gate(j):
        """Gate ``j`` (0 B, 1 C, 2 x~) of ``bcx``, a row tile."""
        return pl.BlockSpec((1, rows, channels),
                            lambda c, b, t, *_: (b, t, j * nC + c))

    def next_rows(j):
        """The first ``_EDGE`` rows after the tile, of gate ``j`` (of
        ``dy`` for j None); the last tile reads its own last rows, which
        the kernel multiplies by 0."""
        per = rows // _EDGE
        last = T // _EDGE - 1
        return pl.BlockSpec(
            (1, _EDGE, channels),
            lambda c, b, t, *_: (b, jnp.minimum((t + 1) * per, last),
                                 (j or 0) * nC + c))

    taps_spec = pl.BlockSpec((taps, 1, channels),
                             lambda c, b, t, *_: (0, 0, c))
    forward = pallas_call(
        _fwd_kernel, grid=(nC, B, nT),
        in_specs=[gate(0), gate(1), gate(2), taps_spec],
        out_specs=pl.BlockSpec((1, rows, channels),
                               lambda c, b, t: (b, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), dtype),
        scratch_shapes=[pltpu.VMEM((_EDGE, channels), f32)],
        compiler_params=_compiler_params(3), interpret=interpret,
        name="short_conv_fwd")
    backward = pallas_call(
        _bwd_kernel, grid=(nC, B, nT, 3),
        in_specs=[gate(0), gate(1), gate(2), next_rows(1), taps_spec,
                  gate(0), next_rows(None)],
        out_specs=[pl.BlockSpec((1, rows, channels),
                                lambda c, b, t, j: (b, t, j * nC + c)),
                   taps_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, three_d), dtype),
                   jax.ShapeDtypeStruct((taps, 1, d), f32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, channels), f32),
                        pltpu.VMEM((rows, channels), dtype),
                        pltpu.VMEM((rows, channels), dtype)],
        compiler_params=_compiler_params(4), interpret=interpret,
        name="short_conv_bwd")
    return forward, backward


# --- the entry -----------------------------------------------------------
def short_conv(bcx, w, rows=None, channels=None, interpret=None):
    """``y`` (B, T, d) of the module's formula, in ``bcx``'s type, for
    ``bcx`` (B, T, 3d) laid out ``[B | C | x~]`` and ``w`` (K, d);
    differentiable in both. ``rows`` and ``channels`` bound the kernels'
    tiles (the module's constants by default); ``interpret`` runs the
    kernels in the Pallas interpreter (None: never, and a call off the TPU
    takes :func:`short_conv_xla`)."""
    import jax
    import jax.numpy as jnp

    Bsz, T, three_d = bcx.shape
    d = three_d // 3
    K = w.shape[0]
    if interpret is None and jax.default_backend() != "tpu":
        return short_conv_xla(bcx, w)
    tile = aligned_block(T, int(rows or _ROWS), _EDGE)
    block = aligned_block(d, int(channels or _CHANNELS),
                          1 if interpret else LANES)
    if tile is None or block is None or tile % _EDGE or K - 1 > _EDGE:
        return short_conv_xla(bcx, w)
    counter("short_conv.kernel").inc()
    forward, backward = _calls((Bsz, T, three_d), K, tile, block,
                               jnp.dtype(bcx.dtype), bool(interpret))

    def taps(w):
        return w.astype(jnp.float32).reshape(K, 1, d)

    @jax.custom_vjp
    def conv(bcx, w):
        return forward(bcx, bcx, bcx, taps(w))

    def fwd(bcx, w):
        return conv(bcx, w), (bcx, w)

    def bwd(res, dy):
        bcx, w = res
        dy = dy.astype(bcx.dtype)
        dbcx, dw = backward(bcx, bcx, bcx, bcx, taps(w), dy, dy)
        return dbcx, dw.reshape(K, d).astype(w.dtype)

    conv.defvjp(fwd, bwd)
    return conv(bcx, w)


def short_conv_xla(bcx, w):
    """The same formula in plain ``jax.numpy``, float32 inside: what a
    call off the TPU lowers, and any shape the kernels have no tile for."""
    import jax.numpy as jnp

    T, d = bcx.shape[1], bcx.shape[2] // 3
    K = w.shape[0]
    f32 = jnp.float32
    b, c, x = (bcx[..., j * d:(j + 1) * d].astype(f32) for j in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(f32)
    conv = sum(w[k] * z[:, k:k + T] for k in range(K))
    return (c * conv).astype(bcx.dtype)

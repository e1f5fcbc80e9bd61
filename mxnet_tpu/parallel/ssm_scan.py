"""Selective scan (the recurrence of a Mamba-1 state-space layer) as a
Pallas TPU kernel pair, forward and backward.

For each sequence and channel ``e`` a state of ``N`` floats follows

    h_t = exp(delta_t[e] * A[e]) * h_{t-1} + (delta_t[e] * u_t[e]) * B_t
    y_t[e] = h_t . C_t + D[e] * u_t[e],            h_0 = 0

with ``u``, ``delta`` (B, T, E), ``A`` (E, N), ``B_t``, ``C_t`` (B, T, N) and
``D`` (E,). Written out for every token the states are ``T * E * N`` floats
a sequence (2.7 GB at 8192 x 5120 x 16), so neither pass holds them in HBM:
the kernels walk the sequence in chunks of ``_CHUNK`` steps with the state
of one block of ``_CHANNELS`` channels in VMEM (``N`` on the sublanes, the
channels on the lanes), the grid running over (sequence, channel block,
chunk). The forward keeps the state at each chunk's start; the backward
takes the chunks last to first, makes a chunk's states again from its
start into VMEM, and walks it in reverse with the adjoint state in
registers. ``delta``, ``exp``, the state and the sum over ``N`` are float32
whatever the operands' type. docs/ssm_scan.md has the derivation and the
VMEM count.

``B_t`` and ``C_t`` reach the kernels as (N, ``_UNROLL``) tiles, one a run
of ``_UNROLL`` steps, so that step ``j`` of a run reads column ``j`` at a
static lane: the inner loop is unrolled that far. Any length works (the
sequence is padded with ``delta = 0``, which leaves the state as it is);
a channel count with no TPU-legal block, and every call off the TPU, takes
:func:`ssm_scan_xla`, the same recurrence as a chunked ``lax.scan`` whose
chunks are recomputed in the backward pass.
"""
from __future__ import annotations

import functools

from ..observability import counter
from .pallas_common import LANES, aligned_block, pallas_call

__all__ = ["ssm_scan", "ssm_scan_xla"]

#: steps a grid step walks; the forward keeps one state a chunk and channel
_CHUNK = 256
#: channels a grid step holds (the state tile is N x _CHANNELS float32)
_CHANNELS = 1280
#: steps of the inner loop written out, and the width of a B/C tile
_UNROLL = 16
_VMEM_LIMIT = 48 * 2 ** 20


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# --- the kernels ---------------------------------------------------------
def _fwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, y_ref, hs_ref,
                h_scr, x_scr, y_scr, *, unroll):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[0, 0] = h_scr[...]           # the state this chunk starts from
    a = a_ref[...]
    u = u_ref[0].astype(jnp.float32)
    x_scr[...] = dt_ref[0] * u

    def run(c, h):
        bt, ct = bt_ref[0, c], ct_ref[0, c]
        for j in range(unroll):
            i = c * unroll + j
            decay = jnp.exp(dt_ref[0, pl.ds(i, 1), :] * a)
            h = decay * h + bt[:, j:j + 1] * x_scr[pl.ds(i, 1), :]
            y_scr[pl.ds(i, 1), :] = jnp.sum(h * ct[:, j:j + 1], axis=0,  # graftlint: disable=G003 — a Pallas kernel writes its refs
                                            keepdims=True)
        return h

    h_scr[...] = jax.lax.fori_loop(0, x_scr.shape[0] // unroll, run,
                                   h_scr[...])
    y_ref[0] = (y_scr[...] + d_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, dy_ref, hs_ref,
                du_ref, ddt_ref, da_ref, dbt_ref, dct_ref,
                g_scr, h_scr, x_scr, dy_scr, gb_scr, ddt_scr, *, unroll):
    """One chunk, its steps last to first. ``g`` is the adjoint of the
    state: what later steps (and later chunks, through ``g_scr``) make of
    a change in ``h_t``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = a_ref[...]
    n, _ = a.shape
    u = u_ref[0].astype(jnp.float32)
    x_scr[...] = dt_ref[0] * u
    dy_scr[...] = dy_ref[0].astype(jnp.float32)
    n_runs = x_scr.shape[0] // unroll

    # the chunk's states again, from the state the forward kept:
    # h_scr[i + 1] is the state after step i, h_scr[0] the one before
    h_scr[0] = hs_ref[0, 0]

    def again(c, h):
        bt = bt_ref[0, c]
        for j in range(unroll):
            i = c * unroll + j
            decay = jnp.exp(dt_ref[0, pl.ds(i, 1), :] * a)
            h = decay * h + bt[:, j:j + 1] * x_scr[pl.ds(i, 1), :]
            h_scr[i + 1] = h  # graftlint: disable=G003 — a Pallas kernel writes its refs
        return h

    jax.lax.fori_loop(0, n_runs, again, hs_ref[0, 0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, unroll), 1)

    def back(k, carry):
        g, da = carry
        c = n_runs - 1 - k
        bt, ct = bt_ref[0, c], ct_ref[0, c]
        dbt, dct = jnp.zeros_like(bt), jnp.zeros_like(ct)
        for j in reversed(range(unroll)):
            i = c * unroll + j
            dt = dt_ref[0, pl.ds(i, 1), :]
            dy = dy_scr[pl.ds(i, 1), :]
            decay = jnp.exp(dt * a)
            g = g + ct[:, j:j + 1] * dy
            dct = jnp.where(lane == j, jnp.sum(
                h_scr[i + 1] * dy, axis=1, keepdims=True), dct)
            dbt = jnp.where(lane == j, jnp.sum(
                g * x_scr[pl.ds(i, 1), :], axis=1, keepdims=True), dbt)
            gb_scr[pl.ds(i, 1), :] = jnp.sum(g * bt[:, j:j + 1], axis=0,  # graftlint: disable=G003 — a Pallas kernel writes its refs
                                             keepdims=True)
            through_decay = g * h_scr[i] * decay
            da = da + through_decay * dt
            ddt_scr[pl.ds(i, 1), :] = jnp.sum(through_decay * a, axis=0,  # graftlint: disable=G003 — a Pallas kernel writes its refs
                                              keepdims=True)
            g = decay * g
        dbt_ref[0, 0, c] = dbt  # graftlint: disable=G003 — a Pallas kernel writes its refs
        dct_ref[0, 0, c] = dct  # graftlint: disable=G003 — a Pallas kernel writes its refs
        return g, da

    g, da = jax.lax.fori_loop(0, n_runs, back,
                              (g_scr[...], jnp.zeros_like(a)))
    g_scr[...] = g
    da_ref[0] += da
    gb = gb_scr[...]
    du_ref[0] = (gb * dt_ref[0] + d_ref[...] * dy_scr[...]).astype(
        du_ref.dtype)
    ddt_ref[0] = (ddt_scr[...] + gb * u).astype(ddt_ref.dtype)


@functools.lru_cache(maxsize=None)
def _calls(shape, n_state, chunk, channels, unroll, dtype, interpret):
    """(forward, backward) ``pallas_call``s for padded operands of
    ``shape`` (B, T, E)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, E = shape
    N, nT, nE, runs = n_state, T // chunk, E // channels, chunk // unroll
    grid = (B, nE, nT)
    f32 = jnp.float32

    def specs(when):
        """Block specs with the chunk index a function of the grid's."""
        rows = pl.BlockSpec((1, chunk, channels),
                            lambda b, e, t: (b, when(t), e))
        tiles = pl.BlockSpec((1, runs, N, unroll),
                             lambda b, e, t: (b, when(t), 0, 0))
        state = pl.BlockSpec((1, 1, N, channels),
                             lambda b, e, t: (b, when(t), 0, e))
        a = pl.BlockSpec((N, channels), lambda b, e, t: (0, e))
        d = pl.BlockSpec((1, channels), lambda b, e, t: (0, e))
        return rows, tiles, state, a, d

    rows, tiles, state, a, d = specs(lambda t: t)
    forward = pallas_call(
        functools.partial(_fwd_kernel, unroll=unroll),
        grid=grid, in_specs=[rows, rows, a, tiles, tiles, d],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, E), dtype),
                   jax.ShapeDtypeStruct((B, nT, N, E), f32)],
        scratch_shapes=[pltpu.VMEM((N, channels), f32),
                        pltpu.VMEM((chunk, channels), f32),
                        pltpu.VMEM((chunk, channels), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_scan_fwd")

    rows, tiles, state, a, d = specs(lambda t: nT - 1 - t)
    backward = pallas_call(
        functools.partial(_bwd_kernel, unroll=unroll),
        grid=grid, in_specs=[rows, rows, a, tiles, tiles, d, rows, state],
        out_specs=[
            rows, rows,
            pl.BlockSpec((1, N, channels), lambda b, e, t: (b, 0, e)),
            pl.BlockSpec((1, 1, runs, N, unroll),
                         lambda b, e, t: (b, e, nT - 1 - t, 0, 0)),
            pl.BlockSpec((1, 1, runs, N, unroll),
                         lambda b, e, t: (b, e, nT - 1 - t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, E), dtype),
                   jax.ShapeDtypeStruct((B, T, E), f32),
                   jax.ShapeDtypeStruct((B, N, E), f32),
                   jax.ShapeDtypeStruct((B, nE, T // unroll, N, unroll), f32),
                   jax.ShapeDtypeStruct((B, nE, T // unroll, N, unroll), f32)],
        scratch_shapes=[pltpu.VMEM((N, channels), f32),
                        pltpu.VMEM((chunk + 1, N, channels), f32)]
        + [pltpu.VMEM((chunk, channels), f32)] * 4,
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_scan_bwd")
    return forward, backward


def _tiles(m, unroll):
    """(B, T, N) -> (B, T / unroll, N, unroll) float32: one (N, unroll)
    tile a run of the inner loop."""
    import jax.numpy as jnp

    B, T, N = m.shape
    return m.astype(jnp.float32).reshape(
        B, T // unroll, unroll, N).transpose(0, 1, 3, 2)


def _rows(tiles):
    B, runs, N, unroll = tiles.shape
    return tiles.transpose(0, 1, 3, 2).reshape(B, runs * unroll, N)


# --- the entry -----------------------------------------------------------
def ssm_scan(u, delta, A, Bm, Cm, D, chunk=None, channels=None,
             interpret=None):
    """``y`` (B, T, E) of the recurrence in the module's note, in ``u``'s
    type; differentiable in all six operands. ``chunk`` and ``channels``
    bound the kernels' tiles (the module's constants by default);
    ``interpret`` runs the kernels in the Pallas interpreter (None: never,
    and a call off the TPU takes :func:`ssm_scan_xla`)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    Bsz, T, E = u.shape
    N = A.shape[1]
    chunk = int(chunk or _CHUNK)
    if interpret is None and jax.default_backend() != "tpu":
        return ssm_scan_xla(u, delta, A, Bm, Cm, D, chunk)
    unroll = min(_UNROLL, chunk)
    block = aligned_block(E, int(channels or _CHANNELS),
                          1 if interpret else LANES)
    if block is None or chunk % unroll or (not interpret and chunk % 8):
        return ssm_scan_xla(u, delta, A, Bm, Cm, D, chunk)
    counter("ssm_scan.kernel").inc()
    pad = -T % chunk

    def padded(x):      # delta = 0 past the end: the state stays
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    forward, backward = _calls((Bsz, T + pad, E), N, chunk, block, unroll,
                               jnp.dtype(u.dtype), bool(interpret))
    f32 = jnp.float32

    @jax.custom_vjp
    def scan(u, delta, A, Bm, Cm, D):
        return run(u, delta, A, Bm, Cm, D)[0]

    def run(u, delta, A, Bm, Cm, D):
        operands = (padded(u), padded(delta.astype(f32)), A.astype(f32).T,
                    _tiles(padded(Bm), unroll), _tiles(padded(Cm), unroll),
                    D.astype(f32)[None, :])
        y, starts = forward(*operands)
        # named for a recomputing caller's policy: with this and ``y`` kept
        # the backward pass does not run the forward kernel again
        return y[:, :T], (operands, checkpoint_name(starts, "ssm_starts"))

    def fwd(u, delta, A, Bm, Cm, D):
        y, kept = run(u, delta, A, Bm, Cm, D)
        return y, (kept, u, delta, A, Bm, Cm, D)

    def bwd(res, dy):
        (operands, starts), u, delta, A, Bm, Cm, D = res
        du, ddt, da, dbt, dct = backward(*operands, padded(dy.astype(
            u.dtype)), starts)
        dD = jnp.einsum("bte,bte->e", dy, u, preferred_element_type=f32)
        return (du[:, :T], ddt[:, :T].astype(delta.dtype),
                da.sum(0).T.astype(A.dtype),
                _rows(dbt.sum(1))[:, :T].astype(Bm.dtype),
                _rows(dct.sum(1))[:, :T].astype(Cm.dtype),
                dD.astype(D.dtype))

    scan.defvjp(fwd, bwd)
    return scan(u, delta, A, Bm, Cm, D)


def ssm_scan_xla(u, delta, A, Bm, Cm, D, chunk=None):
    """The same recurrence in plain ``jax.numpy``: a ``lax.scan`` over
    chunks of ``chunk`` steps, each a ``lax.scan`` over its steps that the
    backward pass runs again from the chunk's first state, so that no more
    than one chunk's states are alive. What a call off the TPU lowers, and
    any shape the kernels have no legal block for."""
    import jax
    import jax.numpy as jnp

    Bsz, T, E = u.shape
    chunk = min(int(chunk or _CHUNK), T)
    pad = -T % chunk
    f32 = jnp.float32
    a = A.astype(f32)

    def by_chunk(x):    # (B, T, .) -> (chunks, chunk, B, .), padded
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return x.reshape(Bsz, -1, chunk, x.shape[-1]).transpose(1, 2, 0, 3)

    def step(h, xs):
        u_t, dt, b_t, c_t = xs                       # (B, E), (B, N)
        h = (jnp.exp(dt[..., None] * a) * h
             + (dt * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def one_chunk(h, xs):
        return jax.lax.scan(step, h, xs)

    _, y = jax.lax.scan(one_chunk, jnp.zeros((Bsz, E, a.shape[1]), f32),
                        tuple(by_chunk(x) for x in (u, delta, Bm, Cm)))
    y = y.reshape(-1, Bsz, E).transpose(1, 0, 2)[:, :T]
    return (y + D.astype(f32) * u.astype(f32)).astype(u.dtype)

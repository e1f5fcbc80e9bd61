"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the long-context path: computes softmax(QK^T)V in VMEM-sized
blocks with an online-softmax accumulator, so the T x T score matrix never
touches HBM (HBM traffic drops from O(T^2) to O(T * d) — exactly the class
of fix PERF_NOTES.md shows this chip needs). Composes with
:mod:`ring_attention`: the ring shards the sequence ACROSS chips while this
kernel blocks it WITHIN a chip.

Training is O(T) in memory end to end: the forward saves only
(q, k, v, o, lse) — lse is the per-row logsumexp of the scaled scores —
and the backward recomputes block scores on the fly in two tiled passes:

- a dq pass gridded over q blocks (k blocks as the innermost,
  sequential axis), and
- a dk/dv pass gridded over k blocks (q blocks innermost),

each accumulating in fp32 VMEM scratch and honoring the same causal
dead-block skipping as the forward. No pass ever materializes a T x T
tensor in HBM.

Standard flash-attention recurrence (Dao et al. 2022, public algorithm);
the kernel implementation is original. Falls back to the XLA reference
implementation when the sequence length has no TPU-legal block (a static
rule, ``_pick_block``), and to XLA autodiff of the dense formula for the
backward when ``MXNET_FLASH_ATTENTION_BWD=0`` (see config.py for the
knobs). Every kernel traces through :func:`.pallas_common.pallas_call`
(x64 scoped off) and moves its per-row softmax statistics (lse, delta)
as lane-dense (1, bq) row blocks of (B*H, 1, T) arrays, a block shape
the TPU lowering accepts where (1, bq) of a 2-d (B*H, T) array is not.
"""
from __future__ import annotations

import functools

import numpy as np

from ..autotune import cost_model as _tune_cost
from ..autotune.registry import declare as _declare_tunable
from ..config import get_flag
from .pallas_common import LANES, aligned_block, pallas_call

__all__ = ["flash_attention", "paged_decode_attention",
           "paged_verify_attention"]


def _block_space(ctx):
    """Candidate block bounds at this shape: powers of two up to
    min(T, 2048) — bounds, not exact sizes (the largest divisor of T at
    or below the bound is what actually runs)."""
    T = int(ctx.get("T", 2048))
    vals = [b for b in (128, 256, 512, 1024, 2048) if b <= T]
    return tuple(vals) if vals else (T,)


# the knob + search-space declaration lives AT the call site (ISSUE 6):
# the tuner sweeps per-call overrides below, no env mutation involved
_declare_tunable(
    "flash_attention.fwd",
    space=lambda ctx: {"block_q": _block_space(ctx),
                       "block_k": _block_space(ctx)},
    default=lambda ctx: {"block_q": get_flag("MXNET_FLASH_BLOCK_Q"),
                         "block_k": get_flag("MXNET_FLASH_BLOCK_K")},
    cost=_tune_cost.flash_fwd_cost,
    doc="Forward kernel q/k block upper bounds (config defaults from "
        "the round-5 on-chip sweep at T=4096).")
_declare_tunable(
    "flash_attention.bwd",
    space=lambda ctx: {"block_q": _block_space(ctx),
                       "block_k": _block_space(ctx)},
    default=lambda ctx: {"block_q": get_flag("MXNET_FLASH_BWD_BLOCK_Q"),
                         "block_k": get_flag("MXNET_FLASH_BWD_BLOCK_K")},
    cost=_tune_cost.flash_bwd_cost,
    doc="Backward (dq + dk/dv recompute passes) block upper bounds — "
        "more live tiles per grid step than the forward.")


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _tuned_block(value):
    """Positive-int coercion of a tuning-cache value; a corrupt or
    hand-edited entry degrades to the config default, never a crash."""
    try:
        value = int(value)
    except (TypeError, ValueError):
        return None
    return value if value > 0 else None


def _pick_block(T, bound, interpret):
    """Sequence tile under ``bound``, or None (the caller lowers the dense
    formula). Compiled, a tile is the whole sequence or a multiple of 128
    dividing it — it is the lane dimension of the lse/delta row blocks
    and of the score tile; the interpreter takes any divisor. Prime-ish T
    (only tiny divisors) declines either way: ``aligned_block``."""
    return aligned_block(T, bound, 1 if interpret else LANES)


def _visible(q_idx, kv_idx, bq, bk, transposed=False):
    """Causal visibility (query position >= key position) of one score
    tile: (bq, bk), or (bk, bq) when ``transposed``."""
    import jax
    import jax.numpy as jnp

    shape, q_dim = ((bk, bq), 1) if transposed else ((bq, bk), 0)
    q_pos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                   1 - q_dim)
    return q_pos >= k_pos


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_k, seq_len):
    """One (batch*head, q_block, k_block) forward grid step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: blocks entirely above the diagonal contribute nothing —
    # skip their MXU work (half the grid for long sequences)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    # a block is live unless it lies entirely above the causal diagonal:
    # last query position >= first key position
    live = ((q_idx + 1) * bq - 1 >= kv_idx * bk) if causal else (kv_idx >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_visible(q_idx, kv_idx, bq, bk), s, -jnp.inf)
        m_prev = m_ref[...]                       # (bq, 1)
        block_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0,
                         jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kv_idx == (seq_len // block_k) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        # the O(T) softmax residual: lse = m + log(l). -inf rows (fully
        # masked — only reachable through ring blocks above the causal
        # diagonal) stay -inf: -inf + log(eps) = -inf. The (bq, 1) column
        # leaves as a lane-dense (1, bq) row of a (B*H, 1, T) array
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0, 0] = lse[:, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, causal, block_k, seq_len):
    """dq pass: grid (batch*head, q_block, k_block); k is the sequential
    axis, dq accumulates in fp32 scratch across it.

    Recomputes the (bq, bk) tile of p and ds from the residuals: p =
    exp(s - lse) is the EXACT softmax (no renormalization needed — lse
    is the forward's true row logsumexp), ds = p * (do.v^T - delta) with
    delta = rowsum(do * o) (+ any lse cotangent, folded into delta by
    the caller). lse/delta arrive as lane-dense (1, bq) rows of
    (B*H, 1, T) arrays and turn into columns here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    live = ((q_idx + 1) * bq - 1 >= kv_idx * bk) if causal else (kv_idx >= 0)

    @pl.when(live)
    def _compute():
        qs, k, v, do = _bwd_operands(q_ref, k_ref, v_ref, do_ref, scale)
        lse = jnp.expand_dims(lse_ref[0, 0], -1)            # (bq, 1)
        delta = jnp.expand_dims(delta_ref[0, 0], -1)
        s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_visible(q_idx, kv_idx, bq, bk), s, -jnp.inf)
        ds = _softmax_grad_tile(s, lse, delta, do, v, transposed=False)[1]
        # ds/dq_i = scale * sum_j ds_ij k_j
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kv_idx == (seq_len // block_k) - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_operands(q_ref, k_ref, v_ref, do_ref, scale):
    """fp32 (scale * q, k, v, do) tiles of one backward grid step."""
    import jax.numpy as jnp

    return (q_ref[0].astype(jnp.float32) * scale,           # (bq, d)
            k_ref[0].astype(jnp.float32),                   # (bk, d)
            v_ref[0].astype(jnp.float32),
            do_ref[0].astype(jnp.float32))


def _softmax_grad_tile(s, lse, delta, do, v, transposed):
    """(p, ds) of one masked score tile ``s`` — (bq, bk) with lse/delta
    as (bq, 1) columns, or (bk, bq) with (1, bq) rows when ``transposed``
    — shared by both backward passes."""
    import jax
    import jax.numpy as jnp

    # fully-masked rows have lse = -inf; exp(s - 0) would explode, so
    # zero them explicitly (s is -inf there too, but -inf - -inf is nan)
    lse_safe = jnp.where(jnp.isneginf(lse), 0.0, lse)
    p = jnp.exp(s - lse_safe)
    p = jnp.where(jnp.isneginf(s) | jnp.isneginf(lse), 0.0, p)
    lhs, rhs = (v, do) if transposed else (do, v)
    dp = jax.lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, block_q, seq_len):
    """dk/dv pass: grid (batch*head, k_block, q_block); q is the
    sequential axis, dk and dv accumulate in fp32 scratch across it.

    Works on the TRANSPOSED (bk, bq) score tile s^T = k.q^T, so dv =
    p^T.do and dk = ds^T.q are plain row-major matmuls (no transposed-LHS
    contraction for Mosaic to transpose) and lse/delta broadcast along
    sublanes straight from their (1, bq) rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(1)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    live = ((q_idx + 1) * bq - 1 >= kv_idx * bk) if causal else (q_idx >= 0)

    @pl.when(live)
    def _compute():
        qs, k, v, do = _bwd_operands(q_ref, k_ref, v_ref, do_ref, scale)
        lse = lse_ref[0]                                    # (1, bq)
        delta = delta_ref[0]
        s_t = jax.lax.dot_general(k, qs, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if causal:
            s_t = jnp.where(_visible(q_idx, kv_idx, bq, bk, transposed=True),
                            s_t, -jnp.inf)
        p_t, ds_t = _softmax_grad_tile(s_t, lse, delta, do, v,
                                       transposed=True)
        # dv_j = sum_i p_ij do_i ; dk_j = sum_i ds_ij (scale q_i) — qs is
        # already scaled, so no extra factor here
        dv_acc[...] += jax.lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds_t, qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_idx == (seq_len // block_q) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, block_q_bwd=None, block_k_bwd=None,
                    interpret=False, return_lse=False):
    """Blocked attention; q/k/v: (batch, heads, T, d).

    Block arguments are upper bounds; the largest TPU-legal tiles at or
    below them are used (``_pick_block``: the whole sequence or a
    multiple of 128 dividing T when compiled, any divisor interpreted; a
    T with no such tile lowers the dense XLA formula). Unset bounds
    resolve through the autotuner
    first — a persistent per-device tuning-cache entry for this
    (shape-bucket, dtype) wins (docs/autotune.md; a miss with
    MXNET_TUNE=1 outside a trace runs the measured sweep on the spot) —
    then fall back to config.py (MXNET_FLASH_BLOCK_Q/K for the forward,
    MXNET_FLASH_BWD_BLOCK_Q/K for the backward; forward defaults from an
    on-chip sweep at T=4096, v5e, round 5: 1024/1024 measures 2.49 ms vs
    2.67 ms for 512/512 and 35.5 ms for the dense XLA formula).
    Differentiable: the vjp runs the
    tiled recompute backward kernels above (dense XLA autodiff of the
    reference formula when MXNET_FLASH_ATTENTION_BWD=0).

    With ``return_lse`` the per-row logsumexp of the scaled scores is
    returned alongside the output, shape (batch, heads, T) fp32 — the
    streaming-combine hook :mod:`ring_attention` uses to merge per-ring-
    step partial results (gradients flow through both outputs).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    # block resolution: explicit per-call override > tuning-cache entry
    # for this (device, shape-bucket, dtype) > config.py flag. The cache
    # consult is one dict probe at trace time; a miss under MXNET_TUNE=1
    # (outside any jax trace) runs the measured sweep right here.
    tuned_fwd = tuned_bwd = None
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        from .. import autotune

        key = autotune.flash_shape_key(T, D, causal)
        ctx = {"T": T, "D": D, "B": B, "H": H, "causal": causal,
               "dtype": str(q.dtype), "dtype_bytes": q.dtype.itemsize,
               "interpret": interpret or None}
        if block_q is None or block_k is None:
            tuned_fwd = autotune.lookup_or_tune(
                "flash_attention.fwd", key, dtype=str(q.dtype), ctx=ctx)
        if block_q_bwd is None or block_k_bwd is None:
            tuned_bwd = autotune.lookup_or_tune(
                "flash_attention.bwd", key, dtype=str(q.dtype), ctx=ctx)
    # corrupt/hand-edited entries (including non-dict values) degrade to
    # the config defaults — tuning is an optimization, never a crash
    tuned_fwd = tuned_fwd if isinstance(tuned_fwd, dict) else {}
    tuned_bwd = tuned_bwd if isinstance(tuned_bwd, dict) else {}
    block_q = int(block_q or _tuned_block(tuned_fwd.get("block_q"))
                  or get_flag("MXNET_FLASH_BLOCK_Q"))
    block_k = int(block_k or _tuned_block(tuned_fwd.get("block_k"))
                  or get_flag("MXNET_FLASH_BLOCK_K"))
    block_q_bwd = int(block_q_bwd or _tuned_block(tuned_bwd.get("block_q"))
                      or get_flag("MXNET_FLASH_BWD_BLOCK_Q"))
    block_k_bwd = int(block_k_bwd or _tuned_block(tuned_bwd.get("block_k"))
                      or get_flag("MXNET_FLASH_BWD_BLOCK_K"))
    # block sizes are upper bounds; _pick_block turns each into a tile
    # the TPU lowering accepts or declines (no 128-multiple divisor
    # compiled, or prime-ish T with only tiny divisors) — a declined
    # shape lowers the XLA formula instead, a static decision.
    block_q = _pick_block(T, block_q, interpret)
    block_k = _pick_block(T, block_k, interpret)
    block_q_bwd = _pick_block(T, block_q_bwd, interpret)
    block_k_bwd = _pick_block(T, block_k_bwd, interpret)
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        out, lse = _dense_with_lse(q, k, v, causal=causal, scale=scale)
        return (out, lse) if return_lse else out

    def _flash_fwd_impl(q, k, v):
        qf = q.reshape(B * H, T, D)
        kf = k.reshape(B * H, T, D)
        vf = v.reshape(B * H, T, D)
        grid = (B * H, T // block_q, T // block_k)
        kernel = functools.partial(_kernel, scale=scale, causal=causal,
                                   block_k=block_k, seq_len=T)
        out, lse = pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
            compiler_params=_compiler_params(),
            name="flash_attention_fwd",
        )(qf, kf, vf)
        return out.reshape(B, H, T, D), lse.reshape(B, H, T)

    def _flash_bwd_impl(q, k, v, o, lse, do, dlse):
        bq, bk = block_q_bwd, block_k_bwd
        qf, kf, vf, dof = (a.reshape(B * H, T, D) for a in (q, k, v, do))
        # delta_i = rowsum(do_i * o_i); an lse cotangent adds
        # glse_i * p_ij to ds_ij, which folds in as delta - glse
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        # per-row residuals ride as lane-dense rows of (B*H, 1, T)
        # arrays — a (1, bq) block of a 2-d (B*H, T) array is not a legal
        # TPU block shape. The dq pass turns its row into a column
        # in-kernel; the dk/dv pass uses it as a row
        lse_row = lse.reshape(B * H, 1, T)
        delta_row = delta.reshape(B * H, 1, T)
        # dq pass grid is (b, q_idx, kv_idx): q/do/rows follow dim 1,
        # k/v follow dim 2
        q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
        k_spec = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
        row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        dq = pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_k=bk, seq_len=T),
            grid=(B * H, T // bq, T // bk),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
            compiler_params=_compiler_params(),
            name="flash_attention_bwd_dq",
        )(qf, kf, vf, dof, lse_row, delta_row)
        # dk/dv pass: grid dim 1 walks k blocks, dim 2 scans q blocks
        q_spec2 = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
        k_spec2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
        row_spec2 = pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i))
        dk, dv = pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=bq, seq_len=T),
            grid=(B * H, T // bk, T // bq),
            in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2,
                      row_spec2],
            out_specs=[k_spec2, k_spec2],
            out_shape=[jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
                       jax.ShapeDtypeStruct((B * H, T, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=interpret,
            compiler_params=_compiler_params(),
            name="flash_attention_bwd_dkv",
        )(qf, kf, vf, dof, lse_row, delta_row)
        return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D),
                dv.reshape(B, H, T, D))

    @jax.custom_vjp
    def _flash(q, k, v):
        return _flash_fwd_impl(q, k, v)

    def _fwd(q, k, v):
        out, lse = _flash_fwd_impl(q, k, v)
        # O(T)-per-head residuals — no T x T tensor survives the forward
        return (out, lse), (q, k, v, out, lse)

    def _bwd(res, g):
        q, k, v, out, lse = res
        do, dlse = g
        if not get_flag("MXNET_FLASH_ATTENTION_BWD"):
            # escape hatch: XLA autodiff of the dense formula (the
            # forward's memory win stands; backward materializes T x T)
            _, vjp = jax.vjp(
                lambda q, k, v: _dense_with_lse(q, k, v, causal=causal,
                                                scale=scale), q, k, v)
            return vjp((do, dlse))
        return _flash_bwd_impl(q, k, v, out, lse, do, dlse)

    _flash.defvjp(_fwd, _bwd)

    out, lse = _flash(q, k, v)
    return (out, lse) if return_lse else out


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None, block_tokens=None,
                           k_scale=None, v_scale=None):
    """Single-query attention against a paged KV cache — the decode step
    of the generation subsystem (serving/generation/, docs/generation.md).

    ``q``: (S, H, d) — ONE query per sequence slot (the token being
    decoded); ``k_pages``/``v_pages``: (P, page, H, d) — one layer's
    device-resident page pool; ``page_table``: (S, n_pages) int32 page
    ids mapping each slot's logical positions onto pool pages;
    ``lengths``: (S,) int32 — valid key count per slot (positions at or
    beyond a slot's length are masked, so stale/trash page contents
    never contribute; a slot with length 0 yields a zero output).

    ``k_scale``/``v_scale``: (P, page, H) fp32 — the int8 pool mode
    (ISSUE 11): pages hold symmetric-int8 quantized K/V with one scale
    per (position, head) stored alongside, and each gathered block
    dequantizes INSIDE the streaming online-softmax recurrence — the
    attention arithmetic below is fp32 either way, so int8 pages change
    HBM traffic (roughly halved vs bf16, quartered vs fp32), never the
    softmax discipline. The pool dtype is part of the program's jit
    signature, not a traced value: one compiled decode program per pool
    mode, the subsystem's compile-count contract intact.

    Deliberately XLA, not Pallas: at query length 1 there is no MXU
    tiling to win — the step is HBM-bandwidth-bound on the K/V gather,
    which XLA lowers to the same dynamic-gather DMA a hand kernel would
    issue, and a (S, H, block) score tile never approaches VMEM limits.
    What *is* kernel-shaped about it is the blocking: keys stream in
    blocks of ``block_tokens`` positions (the ``generation.decode_blocks``
    tunable; upper bound, rounded to a page multiple dividing the table)
    through the same online-softmax recurrence as the Pallas forward
    kernel above, so the gathered K/V working set is O(S * block), not
    O(S * max_seq). Everything is fixed-shape: one compiled program
    serves every batch composition (the active-slot mask lives in
    ``lengths``), which is the whole compile-count discipline of the
    decode path.
    """
    import jax
    import jax.numpy as jnp

    S, H, d = q.shape
    page = k_pages.shape[1]
    n_pages = page_table.shape[1]
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    # block bound -> whole pages per block, a divisor of the table width
    want = max(1, int(block_tokens or n_pages * page) // page)
    bp = 1
    for cand in range(min(want, n_pages), 0, -1):
        if n_pages % cand == 0:
            bp = cand
            break
    n_blocks = n_pages // bp
    blk = bp * page

    qf = q.astype(jnp.float32) * scale
    lengths = lengths.astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        tab = jax.lax.dynamic_slice_in_dim(page_table, i * bp, bp, axis=1)
        kb = k_pages[tab].reshape(S, blk, H, d).astype(jnp.float32)
        vb = v_pages[tab].reshape(S, blk, H, d).astype(jnp.float32)
        if k_scale is not None:
            kb = kb * k_scale[tab].reshape(S, blk, H)[..., None]
        if v_scale is not None:
            vb = vb * v_scale[tab].reshape(S, blk, H)[..., None]
        s = jnp.einsum("shd,sthd->sht", qf, kb)          # (S, H, blk)
        pos = i * blk + jax.lax.iota(jnp.int32, blk)
        live = pos[None, :] < lengths[:, None]            # (S, blk)
        s = jnp.where(live[:, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("sht,sthd->shd", p, vb)
        return m_new, l, acc

    m0 = jnp.full((S, H), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((S, H), jnp.float32)
    a0 = jnp.zeros((S, H, d), jnp.float32)
    if n_blocks == 1:
        _, l, acc = body(0, (m0, l0, a0))
    else:
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def paged_verify_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None, block_tokens=None,
                           k_scale=None, v_scale=None):
    """Multi-query attention against a paged KV cache — the batched-verify
    step of speculative decoding (serving/generation/, docs/generation.md).

    ``q``: (S, Q, H, d) — Q = k+1 candidate positions per sequence slot
    (the last committed token plus k draft tokens), verified in ONE
    program instead of Q sequential decode calls. ``k_pages``/
    ``v_pages``/``page_table``/``k_scale``/``v_scale`` are exactly the
    decode-path pool arguments. ``lengths``: (S,) int32 — the committed
    cache length per slot BEFORE this step's candidates; query ``qi``
    attends positions ``< lengths[s] + 1 + qi`` (its own just-scattered
    key plus every earlier candidate), the causal discipline that makes
    the verify logits bit-compatible with Q sequential decode steps.

    Same streaming online-softmax recurrence as
    :func:`paged_decode_attention` (blocks of whole pages bounded by
    ``block_tokens``), with the score tile carrying a Q axis: still
    fixed-shape, still one compiled program for every batch composition
    and accept pattern — slots past their per-step span point at the
    trash page and are masked here by ``lengths``, never contributing.
    Kept a separate function (not a Q==1 special case folded into the
    decode kernel) so the decode program's numerics and jit signature
    are untouched.
    """
    import jax
    import jax.numpy as jnp

    S, Q, H, d = q.shape
    page = k_pages.shape[1]
    n_pages = page_table.shape[1]
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    want = max(1, int(block_tokens or n_pages * page) // page)
    bp = 1
    for cand in range(min(want, n_pages), 0, -1):
        if n_pages % cand == 0:
            bp = cand
            break
    n_blocks = n_pages // bp
    blk = bp * page

    qf = q.astype(jnp.float32) * scale
    # per-(slot, query) causal limit: committed length + own position + 1
    limits = (lengths.astype(jnp.int32)[:, None]
              + jax.lax.iota(jnp.int32, Q)[None, :] + 1)       # (S, Q)

    def body(i, carry):
        m, l, acc = carry
        tab = jax.lax.dynamic_slice_in_dim(page_table, i * bp, bp, axis=1)
        kb = k_pages[tab].reshape(S, blk, H, d).astype(jnp.float32)
        vb = v_pages[tab].reshape(S, blk, H, d).astype(jnp.float32)
        if k_scale is not None:
            kb = kb * k_scale[tab].reshape(S, blk, H)[..., None]
        if v_scale is not None:
            vb = vb * v_scale[tab].reshape(S, blk, H)[..., None]
        s = jnp.einsum("sqhd,sthd->sqht", qf, kb)        # (S, Q, H, blk)
        pos = i * blk + jax.lax.iota(jnp.int32, blk)
        live = pos[None, None, :] < limits[:, :, None]   # (S, Q, blk)
        s = jnp.where(live[:, :, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("sqht,sthd->sqhd", p, vb)
        return m_new, l, acc

    m0 = jnp.full((S, Q, H), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((S, Q, H), jnp.float32)
    a0 = jnp.zeros((S, Q, H, d), jnp.float32)
    if n_blocks == 1:
        _, l, acc = body(0, (m0, l0, a0))
    else:
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _dense_with_lse(q, k, v, causal=False, scale=None):
    """XLA reference returning (out, lse) — the fallback for prime-ish T
    and the MXNET_FLASH_ATTENTION_BWD=0 escape hatch."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision="highest").astype(jnp.float32) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    w = jnp.exp(scores - jnp.where(jnp.isneginf(lse), 0.0, lse)[..., None])
    w = jnp.where(jnp.isneginf(scores), 0.0, w)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(q.dtype), v,
                     precision="highest")
    return out, lse

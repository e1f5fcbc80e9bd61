"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the long-context path: computes softmax(QK^T)V in VMEM-sized
blocks with an online-softmax accumulator, so the T x T score matrix never
touches HBM (HBM traffic drops from O(T^2) to O(T * d) — exactly the class
of fix PERF_NOTES.md shows this chip needs). Composes with
:mod:`ring_attention`: the ring shards the sequence ACROSS chips while this
kernel blocks it WITHIN a chip.

Training is O(T) in memory end to end: the forward saves only
(q, k, v, o, lse) — lse is the per-row logsumexp of the scaled scores —
and the backward recomputes block scores on the fly, each tile once:

- ONE fused pass gridded over k blocks (q blocks innermost) computes s, p,
  dp and ds of a tile and accumulates dk, dv (per k block) and dq (the
  whole head's (T, D), in fp32 VMEM scratch) from them — 5 matmuls and
  one exp pass a tile; a static rule on the shape (``_bwd_is_fused``:
  the dq scratch beside the tiles against a VMEM budget) selects it;
- beyond that budget, two passes — the same dk/dv pass without dq, and a
  dq pass gridded over q blocks (k blocks innermost) — 7 matmuls a tile.

Every pass accumulates in fp32 VMEM scratch and follows the causal
diagonal the same way: tiles above it are skipped (their index maps name
the neighbouring live block, so they cost no DMA), tiles it crosses are
masked — and a square tile ON it is worked through in sub-chunks that
stop at the diagonal, so its dead part is not computed — and tiles below
it run mask-free. Dots take their operands in the
input's dtype (bf16 in, fp32 accumulation; fp32 inputs keep fp32 dots)
with p and ds cast down for the second dot of each pair; scores, softmax
statistics, lse, delta and all accumulators stay fp32. No pass ever
materializes a T x T tensor in HBM.

Standard flash-attention recurrence (Dao et al. 2022, public algorithm);
the kernel implementation is original. Falls back to the XLA reference
implementation when the sequence length has no TPU-legal block (a static
rule, ``_pick_block``). Tile bounds are the caller's arguments or the
constants below — no flag, no tuning cache. Every kernel traces through
:func:`.pallas_common.pallas_call`
(x64 scoped off) and moves its per-row softmax statistics (lse, delta)
as lane-dense (1, bq) row blocks of (B*H, 1, T) arrays, a block shape
the TPU lowering accepts where (1, bq) of a 2-d (B*H, T) array is not.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .pallas_common import LANES, aligned_block, pallas_call

__all__ = ["flash_attention", "paged_decode_attention",
           "paged_verify_attention"]


#: q and k tile upper bounds where the caller names none: one winner at
#: every length on the v5e (chip sweep, PERF.md §6 PR 27), so constants
_FWD_BLOCK = 2048
_BWD_BLOCK = 1024
#: VMEM the fused backward may plan for (``flash_vmem_bytes``: the whole
#: head's fp32 dq (T, D), its resident (1, T, D) output block and the
#: step's tiles). A shape over it runs the two-pass kernels — the static
#: rule that picks the backward, decided from shapes at trace time.
_FUSED_BWD_VMEM_BUDGET = 40 * 2 ** 20
#: scoped VMEM handed to Mosaic for every kernel here (its default is
#: 16 MiB of the v5e's 128 MiB; a 2048-wide forward tile needs 18)
_VMEM_LIMIT = 64 * 2 ** 20
#: the same two for a call with fewer k/v heads than query heads: its fused
#: backward holds the whole group's dq, ``group * T`` rows, and is still
#: the faster one at 6 and 8 heads a group and T 8192 (24.7 against 36.5 ms
#: a full layer; chip sweep, PERF.md §6 PR 33) — the v5e has 128 MiB
_GROUPED_FUSED_BWD_VMEM_BUDGET = 96 * 2 ** 20
_GROUPED_VMEM_LIMIT = 112 * 2 ** 20
#: rows of q a forward tile is worked through at a time, and keys a
#: backward tile ON the diagonal is: the grain at which a tile follows
#: the diagonal (chip sweep, PERF.md §6 PR 27)
_FWD_SUB_ROWS = 256
_BWD_SUB_KEYS = 128
#: the same bounds under a window: a tile wider than the window computes
#: mostly dead scores, so the band is walked in tiles of about its width
#: (chip sweep at window 512, T 8192, PERF.md §6 PR 33)
_WINDOW_FWD_BLOCK = 512
_WINDOW_BWD_BLOCK = 512

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _compiler_params(semantics=("parallel", "parallel", "arbitrary"),
                     grouped=False):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_GROUPED_VMEM_LIMIT if grouped else _VMEM_LIMIT)


def flash_vmem_bytes(bq, bk, D, dtype_bytes, backward=False, T=None,
                     Dv=None):
    """Live VMEM of one grid step (input and output tiles double-buffered
    by the pipeline, fp32 accumulators single-buffered). With ``T``, the
    fused backward: the whole head's fp32 dq scratch (T, D) and its
    resident (1, T, D) output block on top of the dk/dv pass's tiles.
    ``D`` is the q/k width; ``Dv`` the v/o width where it differs."""
    db = dtype_bytes
    Dv = D if Dv is None else Dv
    if not backward:
        tiles = (bq * D * db            # q
                 + bk * (D + Dv) * db   # k, v
                 + bq * Dv * db)        # out
        scratch = bq * Dv * 4 + 2 * bq * 4     # acc, m, l (fp32)
    else:
        # the dk/dv pass (the dq pass holds one accumulator fewer)
        tiles = (bq * (D + Dv) * db         # q, do
                 + 2 * bk * (D + Dv) * db   # k, v, dk, dv
                 + 2 * bq * 4)              # lse, delta rows
        scratch = bk * (D + Dv) * 4         # dk_acc, dv_acc
        if T is not None:
            tiles += T * D * db       # dq out
            scratch += T * D * 4      # dq_acc
    # score/probability intermediates, fp32: the backward's (bq, bk) s^T
    # and dp^T; the forward works its tile through _FWD_SUB_ROWS rows at
    # a time
    inter = (bq if backward else min(bq, _FWD_SUB_ROWS)) * bk * 4 * 2
    return 2 * tiles + scratch + inter


def _pick_block(T, bound, interpret):
    """Sequence tile under ``bound``, or None (the caller lowers the dense
    formula). Compiled, a tile is the whole sequence or a multiple of 128
    dividing it — it is the lane dimension of the lse/delta row blocks
    and of the score tile; the interpreter takes any divisor. Prime-ish T
    (only tiny divisors) declines either way: ``aligned_block``."""
    return aligned_block(T, bound, 1 if interpret else LANES)


def _bwd_is_fused(T, D, bq, bk, itemsize, Dv=None, group=1):
    """The static rule that picks the backward: one fused pass while the
    whole head's dq — of ``group`` query heads to a k/v head, the whole
    group's — fits the VMEM budget beside the tiles, else two. ``D`` is
    the q/k width, ``Dv`` the v/o width where it differs."""
    return flash_vmem_bytes(
        bq, bk, D, itemsize, backward=True, T=group * T, Dv=Dv) <= (
        _FUSED_BWD_VMEM_BUDGET if group == 1
        else _GROUPED_FUSED_BWD_VMEM_BUDGET)


def _last_live_k(q_idx, bq, bk):
    """Last k block a causal q block sees (its last row's own key)."""
    return ((q_idx + 1) * bq - 1) // bk


def _first_live_q(kv_idx, bq, bk):
    """First q block that sees a causal k block (its first key's row)."""
    return (kv_idx * bk) // bq


def _first_live_k(q_idx, bq, bk, window):
    """First k block a windowed q block sees (its first row's oldest key,
    ``window - 1`` positions back)."""
    import jax.numpy as jnp

    return jnp.maximum(q_idx * bq - (window - 1), 0) // bk


def _last_live_q(kv_idx, bq, bk, window, n_q):
    """Last q block that sees a windowed k block (the last row its last
    key is in the window of)."""
    import jax.numpy as jnp

    return jnp.minimum((kv_idx * bk + bk + window - 2) // bq, n_q - 1)


def _band_blocks(T, bq, bk, window, of_q):
    """Blocks of the inner axis a windowed pass walks for each block of
    its outer one: the most k blocks any q block sees (``of_q``: the
    forward and the dq pass) or the most q blocks that see any k block
    (the dk/dv pass). The grid's inner axis is this long, not T's
    blocks: at a window of 512 in 8,192 a dead grid step costs as much
    as a live tile's arithmetic (chip sweep, PERF.md section 6 PR 33)."""
    if of_q:
        return max(((i + 1) * bq - 1) // bk
                   - max(i * bq - (window - 1), 0) // bk + 1
                   for i in range(T // bq))
    return max(min((j * bk + bk + window - 2) // bq, T // bq - 1)
               - (j * bk) // bq + 1 for j in range(T // bk))


def _visible(q0, k0, nq, nk, transposed=False, window=None):
    """Causal visibility (query position >= key position, and with a
    ``window`` the key within the query's last ``window`` positions, the
    query's own among them) of the score tile of ``nq`` queries from
    position ``q0`` and ``nk`` keys from ``k0``: (nq, nk), or (nk, nq)
    when ``transposed``."""
    import jax
    import jax.numpy as jnp

    shape, q_dim = ((nk, nq), 1) if transposed else ((nq, nk), 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (k_pos > q_pos - window)


def _has_interior(T, bq, bk):
    """Does a causal T x T score matrix in (bq, bk) tiles hold a tile
    wholly below the diagonal (one that runs mask-free)? Static: where
    none does — one tile a head, as at T <= the bounds — the kernels
    carry no mask-free body at all."""
    return (T // bq - 1) * bq >= bk - 1


def _has_window_interior(T, bq, bk, window):
    """The same under a ``window``: is there a tile wholly between the
    diagonal and the band's far edge? (Tiles as wide as the window have
    none: every live tile is crossed by one edge or the other.)"""
    return any(k0 + bk - 1 <= q0 and k0 > q0 + bq - 1 - window
               for q0 in range(0, T, bq) for k0 in range(0, T, bk))


def _on_live_tiles(q_idx, kv_idx, bq, bk, causal, interior, tile,
                   window=None, in_range=True):
    """Run ``tile(masked)`` on this grid step's score tile if it is live.
    A causal tile is dead above the diagonal (skipped: its index maps
    are clamped, so it costs no DMA either), ``masked`` where the
    diagonal crosses it, and mask-free below — no iota, compare or
    select on interior tiles (``interior``: whether the grid has any,
    :func:`_has_interior`). Under a ``window`` the live tiles are a band:
    a tile is dead past the band's far edge too, and ``masked`` where
    either edge crosses it; ``in_range`` is whether the step's block
    exists at all (a band's last steps may point past the sequence)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if not causal:
        tile(False)
        return
    first_q, first_k = q_idx * bq, kv_idx * bk
    if window is not None:
        live = ((first_k <= first_q + bq - 1)
                & (first_k + bk - 1 > first_q - window) & in_range)
        if interior:
            clean = ((first_k + bk - 1 <= first_q)
                     & (first_k > first_q + bq - 1 - window))
            pl.when(live & clean)(lambda: tile(False))
            pl.when(live & jnp.logical_not(clean))(lambda: tile(True))
        else:
            pl.when(live)(lambda: tile(True))
        return
    crosses = first_k + bk - 1 > first_q
    if interior:
        pl.when(jnp.logical_not(crosses))(lambda: tile(False))
    pl.when(crosses & (first_k <= first_q + bq - 1))(lambda: tile(True))


def _sub_block(b, want, interpret):
    """Sub-chunk of a ``b``-long tile side: the largest divisor of ``b``
    at or under ``want`` that the lowering takes as a slice (a multiple
    of 128), else the whole side; a quarter of the side in the
    interpreter, whose tiles are small."""
    if interpret:
        return aligned_block(b, max(1, b // 4), 1) or b
    return aligned_block(b, want, LANES) or b


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, interior, sub, window=None):
    """One (batch*head, q_block, k_block) forward grid step.

    Dots take q, k, v as loaded (bf16 operands, fp32 accumulation; fp32
    inputs keep fp32 dots) and p cast to v's dtype; scores, softmax
    statistics and accumulators are fp32. The statistics are lane-dense,
    (bq, W) with W the 128 lanes (``_forward_call``): the running max m
    replicated across them, the running sum l as W partial sums reduced
    once, at the last k block — so a row costs one cross-lane reduction a
    tile (its max), and no statistic lives in a one-lane column. No row
    is ever fully masked within one call — causal or not, every query
    sees a key in its first live tile — so m is finite from that tile on
    and the -inf mask needs no guard. Under a ``window`` that no longer
    holds (the first live tile of a q block may lie wholly before a later
    row's window), so there the exponentials take a running max of 0 in
    place of -inf: the row's sums stay 0 until its first visible key."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step = pl.program_id(2)
    q_idx = pl.program_id(1)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    W = m_ref.shape[1]
    # under a window the k axis walks the q block's band alone
    kv_idx = (step if window is None
              else _first_live_k(q_idx, bq, bk, window) + step)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _tile(masked):
        # the tile is worked through ``sub`` rows of q at a time. On a
        # tile that sits ON the diagonal (bq == bk) a chunk's keys end
        # with its own last row: dead sub-blocks are not computed
        on_diagonal = masked and bq == bk and window is None
        for first in range(0, bq, sub):
            rows = slice(first, first + sub)
            nk = first + sub if on_diagonal else bk
            q, k, v = q_ref[0, rows, :], k_ref[0, :nk, :], v_ref[0, :nk, :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(_visible(q_idx * bq + first, kv_idx * bk,
                                       sub, nk, window=window), s, -jnp.inf)
            cols = [s[:, j:j + W] for j in range(0, nk, W)]
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(
                functools.reduce(jnp.maximum, cols), axis=1, keepdims=True))
            m_exp = (m_new if window is None
                     else jnp.where(jnp.isneginf(m_new), 0.0, m_new))
            ps = [jnp.exp(col - m_exp) for col in cols]
            corr = jnp.exp(m_prev - m_exp)
            l_ref[rows, :] = (l_ref[rows, :] * corr
                              + functools.reduce(jnp.add, ps))
            p = ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=1)
            acc_ref[rows, :] = (
                acc_ref[rows, :] * (corr if acc_ref.shape[1] == W
                                    else corr[:, :1])
                + jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                      preferred_element_type=jnp.float32))
            m_ref[rows, :] = m_new

    _on_live_tiles(q_idx, kv_idx, bq, bk, causal, interior, _tile, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.sum(l_ref[...], axis=1, keepdims=True)
        o_ref[0] = (acc_ref[...] * (1.0 / l)).astype(o_ref.dtype)
        # the O(T) softmax residual: lse = m + log(l). The (bq, 1) column
        # leaves as a lane-dense (1, bq) row of a (B*H, 1, T) array
        lse_ref[0, 0] = (m_ref[:, :1] + jnp.log(l))[:, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, causal, interior, window=None):
    """dq pass of the two-pass backward: grid (batch*head, q_block,
    k_block); k is the sequential axis, dq accumulates in fp32 scratch
    across it.

    Recomputes the (bq, bk) tile of p and ds from the residuals: p =
    exp(s - lse) is the EXACT softmax (no renormalization needed — lse
    is the forward's true row logsumexp), ds = p * (do.v^T - delta) with
    delta = rowsum(do * o) (+ any lse cotangent, folded into delta by
    the caller). lse/delta arrive as lane-dense (1, bq) rows of
    (B*H, 1, T) arrays and turn into columns here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step = pl.program_id(2)
    q_idx = pl.program_id(1)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    kv_idx = (step if window is None
              else _first_live_k(q_idx, bq, bk, window) + step)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile(masked):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(q_idx * bq, kv_idx * bk, bq, bk,
                                   window=window), s, -jnp.inf)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0, 0], -1))
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(delta_ref[0, 0], -1))
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _on_live_tiles(q_idx, kv_idx, bq, bk, causal, interior, _tile, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        # ds/dq_i = scale * sum_j ds_ij k_j
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *rest, scale, causal, interior, fused,
                    sub, window=None, group=1, n_q=None, n_band=None):
    """dk/dv pass — and, ``fused``, the whole backward: grid (batch*head,
    k_block, q_block); q is the sequential axis, dk and dv accumulate in
    fp32 scratch across it.

    Works on the TRANSPOSED (bk, bq) score tile s^T = k.q^T, so dv =
    p^T.do and dk = ds^T.q are plain row-major matmuls and lse/delta
    broadcast along sublanes straight from their (1, bq) rows. Fused, the
    same p and ds also feed dq += ds.k (one transposed-LHS contraction),
    accumulated over ALL k blocks in an fp32 scratch holding the whole
    head's dq (T, D) — zeroed at the head's first grid step, cast into
    the resident (1, T, D) output block at its last — so s, p, dp and ds
    are computed once: 5 matmuls and one exp pass a tile where the two
    passes spend 7 and two.

    With ``group`` query heads to a k/v head the batch axis counts k/v
    heads and the sequential axis runs over the group's heads, each
    head's ``n_q`` q blocks in turn: one k/v tile stays in VMEM for the
    whole group, and dk and dv are summed over it in the same scratch.
    Fused, the dq scratch and block then hold the group's heads one
    after another, (group * T, D). Under a ``window`` a head's part of
    the sequential axis is the ``n_band`` q blocks from the k block's
    first live one on, not all ``n_q``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if fused:
        dq_ref, dk_acc, dv_acc, dq_acc = rest
    else:
        dk_acc, dv_acc = rest
    # the sequential axis' step, and the q block of its head it stands for
    step = pl.program_id(2)
    kv_idx = pl.program_id(1)
    last_q = pl.num_programs(2) - 1
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    if window is None:
        q_idx = step if group == 1 else step % n_q
        dq_block = step     # of the (group * T, D) dq: head * n_q + q_idx
    else:
        q_idx = _first_live_q(kv_idx, bq, bk) + step % n_band
        dq_block = (step // n_band) * n_q + q_idx

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if fused:
        @pl.when((step == 0) & (kv_idx == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def _chunk(masked, keys, first):
        """Keys ``keys`` (a slice of the tile's rows) against the tile's
        queries from ``first`` on."""
        q, do = q_ref[0, first:, :], do_ref[0, first:, :]
        k = k_ref[0, keys, :]
        s_t = jax.lax.dot_general(k, q, _NT,
                                  preferred_element_type=jnp.float32) * scale
        if masked:
            s_t = jnp.where(
                _visible(q_idx * bq + first, kv_idx * bk + keys.start,
                         bq - first, keys.stop - keys.start,
                         transposed=True, window=window), s_t, -jnp.inf)
        p_t = jnp.exp(s_t - lse_ref[0, :, first:])            # (1, .) row
        dp_t = jax.lax.dot_general(v_ref[0, keys, :], do, _NT,
                                   preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta_ref[0, :, first:])).astype(q.dtype)
        # dv_j = sum_i p_ij do_i ; dk_j = scale * sum_i ds_ij q_i
        dv_acc[keys, :] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dk_acc[keys, :] += jax.lax.dot_general(
            ds_t, q, _NN, preferred_element_type=jnp.float32)
        if fused:
            rows = pl.ds(pl.multiple_of(dq_block * bq, bq) + first,
                         bq - first)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds_t, k, _TN, preferred_element_type=jnp.float32)

    def _tile(masked):
        # on a tile that sits ON the diagonal (bq == bk) a key sub-chunk
        # is seen only from its own first row on: the dead sub-blocks
        # are not computed
        if masked and bq == bk and window is None:
            for first in range(0, bk, sub):
                _chunk(True, slice(first, first + sub), first)
        else:
            _chunk(masked, slice(0, bk), 0)

    _on_live_tiles(q_idx, kv_idx, bq, bk, causal, interior, _tile, window,
                   in_range=True if window is None else q_idx < n_q)

    @pl.when(step == last_q)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if fused:
        @pl.when((step == last_q) & (kv_idx == pl.num_programs(1) - 1))
        def _finish_dq():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _kernel_name(window, part):
    """Kernel names as a trace shows them; a windowed call's hold
    ``window``, so that it is told from a full layer's."""
    return "flash_attention_%s%s" % ("window_" if window else "", part)


@functools.lru_cache(maxsize=64)
def _forward_call(causal, scale, block_q, block_k, interpret, window=None,
                  group=1):
    """The jitted forward ``pallas_call`` of one static configuration, on
    (B*H, T, D) operands. ONE function object a configuration: every
    layer of a model that calls it on the same shapes shares one trace
    and one lowering of the kernel body, where a step of 24 layers would
    lower 24 identical Mosaic bodies — set-up time, warm cache or cold.
    With ``group`` query heads to a k/v head, k and v are (B*H/group, T,
    .) and a q head's index map names its group's k/v blocks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def forward(qf, kf, vf):
        BH, T, D = qf.shape
        Dv = vf.shape[-1]       # v and o may be narrower than q and k
        # a dead causal step names the block of the last live one: no DMA
        # is issued for a block index that did not change
        def kv_map(b, i, j):
            if window is not None:      # the band's j-th block
                j = j + _first_live_k(i, block_q, block_k, window)
            if causal:
                j = jnp.minimum(j, _last_live_k(i, block_q, block_k))
            return (b if group == 1 else b // group, j, 0)

        sub = _sub_block(block_q, _FWD_SUB_ROWS, interpret)
        # width of the lane-dense statistics: the 128 lanes, which divide
        # every aligned tile and sub-chunk; an unaligned tile is the whole
        # sequence and one slice wide; the interpreter's small tiles take
        # what divides them
        lanes = (math.gcd(LANES, block_k, sub)
                 if interpret or block_k % LANES == 0 else block_k)
        interior = (_has_interior(T, block_q, block_k) if window is None
                    else _has_window_interior(T, block_q, block_k, window))
        return pallas_call(
            functools.partial(_kernel, scale=scale, causal=causal, sub=sub,
                              interior=interior, window=window),
            grid=(BH, T // block_q,
                  T // block_k if window is None else _band_blocks(
                      T, block_q, block_k, window, of_q=True)),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_map),
                pl.BlockSpec((1, block_k, Dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, T, Dv), qf.dtype),
                jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, Dv), jnp.float32),
                pltpu.VMEM((block_q, lanes), jnp.float32),
                pltpu.VMEM((block_q, lanes), jnp.float32),
            ],
            interpret=interpret,
            compiler_params=_compiler_params(grouped=group > 1),
            name=_kernel_name(window, "fwd"),
        )(qf, kf, vf)

    return jax.jit(forward)


@functools.lru_cache(maxsize=64)
def _backward_call(causal, scale, bq, bk, fused, interpret, window=None,
                   group=1):
    """The jitted backward of one static configuration — the fused pass,
    or the dk/dv and dq passes — on (B*H, T, D) operands and (B*H, 1, T)
    lse/delta rows; returns (dq, dk, dv). One function object a
    configuration, as :func:`_forward_call`. With ``group`` query heads
    to a k/v head, k, v, dk and dv are (B*H/group, T, .): the dk/dv
    pass's batch axis counts k/v heads and its sequential axis walks the
    group's heads one after another."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def backward(*operands):
        qf, kf, vf = operands[:3]
        BH, T, D = qf.shape
        Dv = vf.shape[-1]       # v, o, do and dv may be narrower
        n_q = T // bq
        interior = (_has_interior(T, bq, bk) if window is None
                    else _has_window_interior(T, bq, bk, window))
        # q blocks a head takes of the dk/dv pass's sequential axis: all
        # of them, or under a window the band of a k block
        n_band = n_q if window is None else _band_blocks(T, bq, bk, window,
                                                         of_q=False)
        # grid (b, k block, q block): k/v and their gradients follow dim
        # 1, q/do/rows dim 2 — clamped on dead causal steps, which come
        # first in the scan, to the first live q block. Under a window
        # dim 2 counts from the k block's first live q block on, clamped
        # past the band to its last. A group's heads follow one another
        # on dim 2, n_band blocks each
        def q_block(j, i):
            if group > 1:
                i = i % n_band
            if window is not None:
                return jnp.minimum(i + _first_live_q(j, bq, bk),
                                   _last_live_q(j, bq, bk, window, n_q))
            if causal:
                i = jnp.maximum(i, _first_live_q(j, bq, bk))
            return i

        def q_head(b, i):
            return b if group == 1 else b * group + i // n_band

        def q_spec(width):
            return pl.BlockSpec(
                (1, bq, width),
                lambda b, j, i: (q_head(b, i), q_block(j, i), 0))

        def k_spec(width):
            return pl.BlockSpec((1, bk, width), lambda b, j, i: (b, j, 0))

        row_spec = pl.BlockSpec(
            (1, 1, bq), lambda b, j, i: (q_head(b, i), 0, q_block(j, i)))
        out_specs = [k_spec(D), k_spec(Dv)]
        out_shape = [jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                     jax.ShapeDtypeStruct(vf.shape, vf.dtype)]
        scratch = [pltpu.VMEM((bk, D), jnp.float32),
                   pltpu.VMEM((bk, Dv), jnp.float32)]
        if fused:
            # dq is one (1, group * T, D) block per k/v head, resident
            # across both inner axes — which makes the k axis sequential
            # too
            out_specs.append(pl.BlockSpec((1, group * T, D),
                                          lambda b, j, i: (b, 0, 0)))
            out_shape.append(jax.ShapeDtypeStruct(
                (BH // group, group * T, D), qf.dtype))
            scratch.append(pltpu.VMEM((group * T, D), jnp.float32))
        outs = pallas_call(
            functools.partial(
                _bwd_dkv_kernel, scale=scale, causal=causal, fused=fused,
                interior=interior, window=window, group=group, n_q=n_q,
                n_band=n_band, sub=_sub_block(bk, _BWD_SUB_KEYS, interpret)),
            grid=(BH // group, T // bk, group * n_band),
            in_specs=[q_spec(D), k_spec(D), k_spec(Dv), q_spec(Dv),
                      row_spec, row_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=_compiler_params(
                ("parallel", "arbitrary", "arbitrary") if fused
                else ("parallel", "parallel", "arbitrary"),
                grouped=group > 1),
            name=_kernel_name(window, "bwd_dqkv" if fused else "bwd_dkv"),
        )(*operands)
        if fused:
            dk, dv, dq = outs
            return (dq if group == 1 else dq.reshape(qf.shape)), dk, dv
        dk, dv = outs
        # dq pass grid is (b, q block, k block): k/v follow dim 2,
        # clamped on dead steps to the last (first) live k block
        def k_block(i, j):
            if window is not None:      # the band's j-th block
                j = j + _first_live_k(i, bq, bk, window)
            if causal:
                j = jnp.minimum(j, _last_live_k(i, bq, bk))
            return j

        def q_spec(width):
            return pl.BlockSpec((1, bq, width), lambda b, i, j: (b, i, 0))

        def k_spec(width):
            return pl.BlockSpec(
                (1, bk, width),
                lambda b, i, j: (b if group == 1 else b // group,
                                 k_block(i, j), 0))

        row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        dq = pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              interior=interior, window=window),
            grid=(BH, T // bq, T // bk if window is None else _band_blocks(
                T, bq, bk, window, of_q=True)),
            in_specs=[q_spec(D), k_spec(D), k_spec(Dv), q_spec(Dv),
                      row_spec, row_spec],
            out_specs=q_spec(D),
            out_shape=jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
            compiler_params=_compiler_params(grouped=group > 1),
            name=_kernel_name(window, "bwd_dq"),
        )(*operands)
        return dq, dk, dv

    return jax.jit(backward)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, block_q_bwd=None, block_k_bwd=None,
                    interpret=False, return_lse=False, window=None):
    """Blocked attention; q: (batch, heads, T, d), k: (batch, kv_heads, T,
    d), v: (batch, kv_heads, T, dv) — ``dv`` may differ from ``d``
    (latent attention's 192/128), and the output then has v's width;
    nothing is padded in HBM. ``kv_heads`` divides ``heads``: query head
    ``h`` attends k/v head ``h // (heads // kv_heads)`` (grouped-query
    attention), and no copy of k or v per query head is made — the index
    maps name the group's k/v blocks, and the dk/dv pass sums over the
    group in VMEM.

    ``window`` (with ``causal``): query ``i`` sees keys ``i - window <
    j <= i``, its own among them. The kernels then walk the band of live
    tiles alone, carry ``window`` in their names, and take the window's
    tile constants; a window at or past ``T`` is the causal mask.

    Block arguments are upper bounds; the largest TPU-legal tiles at or
    below them are used (``_pick_block``: the whole sequence or a
    multiple of 128 dividing T when compiled, any divisor interpreted; a
    T with no such tile lowers the dense XLA formula with the same
    mask). An unset bound is this module's constant: 2048/2048 forward,
    1024/1024 backward (the v5e sweep of PR 27, PERF.md section 6), and
    under a window ``_WINDOW_FWD_BLOCK`` / ``_WINDOW_BWD_BLOCK``.
    Differentiable: the vjp runs the tiled recompute backward above —
    fused, or in two passes, by ``_bwd_is_fused``.

    With ``return_lse`` the per-row logsumexp of the scaled scores is
    returned alongside the output, shape (batch, heads, T) fp32 — the
    streaming-combine hook :mod:`ring_attention` uses to merge per-ring-
    step partial results (gradients flow through both outputs).
    """
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from ..observability import counter

    B, H, T, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    if k.shape[-1] != D:
        raise ValueError("flash_attention: q and k widths differ (%d, %d)"
                         % (D, k.shape[-1]))
    if H % Hkv or v.shape[1] != Hkv:
        raise ValueError("flash_attention: %d query heads over %d k and %d "
                         "v heads" % (H, Hkv, v.shape[1]))
    group = H // Hkv
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: a window needs causal=True")
        window = int(window) if int(window) < T else None
    if window is not None:
        counter("flash_attention.windowed").inc()
    if group > 1:
        counter("flash_attention.kv_group").inc(group)
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    # block sizes are upper bounds; _pick_block turns each into a tile
    # the TPU lowering accepts or declines (no 128-multiple divisor
    # compiled, or prime-ish T with only tiny divisors) — a declined
    # shape lowers the XLA formula instead, a static decision.
    fwd_bound, bwd_bound = ((_FWD_BLOCK, _BWD_BLOCK) if window is None
                            else (_WINDOW_FWD_BLOCK, _WINDOW_BWD_BLOCK))
    block_q = _pick_block(T, int(block_q or fwd_bound), interpret)
    block_k = _pick_block(T, int(block_k or fwd_bound), interpret)
    block_q_bwd = _pick_block(T, int(block_q_bwd or bwd_bound), interpret)
    block_k_bwd = _pick_block(T, int(block_k_bwd or bwd_bound), interpret)
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        out, lse = _dense_with_lse(q, k, v, causal=causal, scale=scale,
                                   window=window)
        return (out, lse) if return_lse else out
    def _flat(a):
        return a.reshape(-1, T, a.shape[-1])

    def _flash_fwd_impl(q, k, v):
        if Dv != D:
            counter("flash_attention.dqk_ne_dv").inc()
        out, lse = _forward_call(causal, scale, block_q, block_k, interpret,
                                 window, group)(*(_flat(a) for a in (q, k, v)))
        # named for a recomputing caller's policy: kept, the backward
        # pass does not run this kernel again (an identity otherwise)
        return (checkpoint_name(out.reshape(B, H, T, Dv), "flash_out"),
                checkpoint_name(lse.reshape(B, H, T), "flash_lse"))

    def _flash_bwd_impl(q, k, v, o, lse, do, dlse):
        # delta_i = rowsum(do_i * o_i); an lse cotangent adds
        # glse_i * p_ij to ds_ij, which folds in as delta - glse
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        fused = _bwd_is_fused(T, D, block_q_bwd, block_k_bwd,
                              q.dtype.itemsize, Dv=Dv, group=group)
        counter("flash_attention.bwd_fused" if fused
                else "flash_attention.bwd_two_pass").inc()
        # per-row residuals ride as lane-dense rows of (B*H, 1, T)
        # arrays — a (1, bq) block of a 2-d (B*H, T) array is not a legal
        # TPU block shape. The dq pass turns its row into a column
        # in-kernel; the dk/dv and fused passes use it as a row
        grads = _backward_call(causal, scale, block_q_bwd, block_k_bwd,
                               fused, interpret, window, group)(
            *(_flat(a) for a in (q, k, v, do)),
            lse.reshape(B * H, 1, T), delta.reshape(B * H, 1, T))
        return tuple(g.reshape(a.shape) for g, a in zip(grads, (q, k, v)))

    @jax.custom_vjp
    def _flash(q, k, v):
        return _flash_fwd_impl(q, k, v)

    def _fwd(q, k, v):
        out, lse = _flash_fwd_impl(q, k, v)
        # O(T)-per-head residuals — no T x T tensor survives the forward
        return (out, lse), (q, k, v, out, lse)

    def _bwd(res, g):
        return _flash_bwd_impl(*res, *g)

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


def _dense_with_lse(q, k, v, causal=False, scale=None, window=None):
    """XLA reference returning (out, lse) — the lowering of a T with no
    legal tile, and the tests' oracle. Fewer k/v heads than query heads
    are repeated to them; ``window`` as :func:`flash_attention` has it."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision="highest").astype(jnp.float32) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), bool), -int(window))
        scores = jnp.where(mask, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    w = jnp.exp(scores - jnp.where(jnp.isneginf(lse), 0.0, lse)[..., None])
    w = jnp.where(jnp.isneginf(scores), 0.0, w)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(q.dtype), v,
                     precision="highest")
    return out, lse

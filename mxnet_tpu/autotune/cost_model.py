"""Analytic roofline cost model — the cheap pruning half of the search
(ISSUE 6; "A Learned Performance Model for TPUs" is the graduation path,
this is the start-analytic rung ROADMAP item 2 names).

Estimates are in SECONDS and deliberately coarse: the model's only job is
to rank candidates well enough that the measured search (search.py) never
wastes a compile on a block pair that overflows VMEM or a ladder that
pads 4x, not to predict absolute times. Ceilings are the repo's own
measured numbers (PERF_NOTES.md round-5 calibration, the same basis as
tools/flops_anchor.py), not spec-sheet values.
"""
from __future__ import annotations

import math

__all__ = ["MEASURED_MATMUL_TF", "MEASURED_HBM_GBPS", "SPEC_MATMUL_TF",
           "VMEM_BYTES", "CEILINGS", "ridge_intensity",
           "roofline_seconds", "flash_fwd_cost", "flash_bwd_cost",
           "flash_vmem_bytes", "ladder_cost", "expected_padding",
           "fused_vmem_bytes", "fused_matmul_cost", "pow2_at_least"]


def pow2_at_least(n):
    """Smallest power of two >= n (shape-bucket / ladder-top rounding)."""
    p = 1
    while p < n:
        p <<= 1
    return p

# measured ceilings (PERF_NOTES.md: 8192^3 matmul scan; bf16 stream,
# round-5 recalibration) — THE one calibrated table every FLOP/ceiling
# consumer cites (ISSUE 13): tools/flops_anchor.py, tools/
# chip_calibration.py, observability/perf.py and bench_all.py's MFU
# fields all import from here, so an MFU% printed anywhere in the tree
# is always relative to the same basis.
MEASURED_MATMUL_TF = 128.6
MEASURED_HBM_GBPS = 634.0
# spec-sheet bf16 matmul peak of the chip (v5-lite datasheet) — the
# denominator of the *_spec MFU numbers (BENCH_ALL.json mfu_spec);
# measured vs spec: achieved-of-attainable vs achieved-of-advertised
SPEC_MATMUL_TF = 197.0
# per-core VMEM; Pallas tiles + double-buffered input windows must fit
VMEM_BYTES = 16 * 2 ** 20

#: the exported calibration table (single source of truth; see
#: tools/chip_calibration.py for the microbench that re-measures it)
CEILINGS = {
    "matmul_tf_s": MEASURED_MATMUL_TF,
    "hbm_gb_s": MEASURED_HBM_GBPS,
    "spec_matmul_tf_s": SPEC_MATMUL_TF,
    "vmem_bytes": VMEM_BYTES,
    "source": "PERF_NOTES.md round-5 calibration "
              "(tools/chip_calibration.py)",
}


def ridge_intensity():
    """The roofline ridge point in FLOPs/byte at the measured ceilings:
    ops whose arithmetic intensity sits below it are bandwidth-bound."""
    return (MEASURED_MATMUL_TF * 1e12) / (MEASURED_HBM_GBPS * 1e9)
_VMEM_BUDGET = int(VMEM_BYTES * 0.75)  # headroom for Mosaic's own buffers
# fixed cost per grid step (loop + DMA issue) — dominates tiny blocks
_GRID_STEP_S = 2e-7


def roofline_seconds(flops, hbm_bytes):
    """max(compute, bandwidth) time at the measured ceilings."""
    return max(flops / (MEASURED_MATMUL_TF * 1e12),
               hbm_bytes / (MEASURED_HBM_GBPS * 1e9))


def _dtype_bytes(ctx):
    return int(ctx.get("dtype_bytes", 2))  # bf16 default


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
    # and dp^T; the forward works its tile through 256 rows at a time
    inter = (bq if backward else min(bq, 256)) * bk * 4 * 2
    return 2 * tiles + scratch + inter


def _live_tiles(n_q, n_k, bq, bk, causal, grain=None):
    """Score tiles a pass computes: all, or those a causal diagonal
    leaves live (last query row of the tile >= its first key). Square
    tiles ON the diagonal are worked through in ``grain``-wide sub-chunks
    that stop at it: each counts as the stepped share it computes."""
    if not causal:
        return n_q * n_k
    live = sum(min(n_k, ((i + 1) * bq - 1) // bk + 1) for i in range(n_q))
    if grain and bq == bk:
        n = max(1, bq // grain)
        live -= n_q * (1 - (n + 1) / (2.0 * n))
    return live


def _flash_cost(ctx, bq, bk, backward):
    from ..parallel.flash_attention import (_BWD_SUB_KEYS, _FWD_SUB_ROWS,
                                            _VMEM_LIMIT, _bwd_is_fused)

    T = int(ctx["T"])
    D = int(ctx.get("D", 64))
    BH = int(ctx.get("B", 1)) * int(ctx.get("H", 1))
    causal = bool(ctx.get("causal", False))
    db = _dtype_bytes(ctx)
    bq = min(bq, T)
    bk = min(bk, T)
    # the kernels raise Mosaic's scoped-VMEM limit; keep its headroom
    fused = backward and _bwd_is_fused(T, D, bq, bk, db)
    if not fused and flash_vmem_bytes(
            bq, bk, D, db, backward=backward) > 0.75 * _VMEM_LIMIT:
        return math.inf
    n_q, n_k = -(-T // bq), -(-T // bk)
    passes = 2 if backward and not fused else 1
    steps = passes * BH * n_q * n_k
    # 2*bq*bk*D flops a matmul a live tile — forward: s, pv; fused
    # backward: s, dp, dv, dk, dq; the two passes compute s and dp twice
    matmuls = (5 if fused else 7) if backward else 2
    flops = (2 * bq * bk * D * matmuls * BH
             * _live_tiles(n_q, n_k, bq, bk, causal,
                           _BWD_SUB_KEYS if backward else _FWD_SUB_ROWS))
    # a live step moves the inner axis's two tiles (a dead one names its
    # neighbour's block: no DMA); the outer axis's tiles and the outputs
    # move once: 4 T x D tensors forward (q, k, v, o), 8 backward
    traffic = BH * D * db * (
        passes * _live_tiles(n_q, n_k, bq, bk, causal) * 2 * max(bq, bk)
        + (8 if backward else 4) * T)
    return roofline_seconds(flops, traffic) + steps * _GRID_STEP_S


def flash_fwd_cost(candidate, ctx):
    """Estimated seconds of one flash-attention forward at this block
    pair; inf when the tiles overflow VMEM."""
    return _flash_cost(ctx, int(candidate["block_q"]),
                       int(candidate["block_k"]), backward=False)


def flash_bwd_cost(candidate, ctx):
    """Estimated seconds of the backward at this block pair: the fused
    pass where the shape selects it, else the two tiled passes."""
    return _flash_cost(ctx, int(candidate["block_q"]),
                       int(candidate["block_k"]), backward=True)


# --------------------------------------------------- fused matmul regions
def fused_vmem_bytes(bm, bn, bk, dtype_bytes):
    """Live VMEM of one fused-matmul grid step: input tiles
    double-buffered by the pipeline, one fp32 accumulator, a small
    allowance for epilogue vectors/residual tiles."""
    db = dtype_bytes
    tiles = bm * bk * db + bk * bn * db      # x, w
    out = bm * bn * db                       # writeback tile
    acc = bm * bn * 4                        # fp32 accumulator (scratch)
    epilogue = bm * bn * db + bn * 4         # residual tile + one vector
    return 2 * (tiles + out + epilogue) + acc


def fused_matmul_cost(candidate, ctx):
    """Estimated seconds of one fused (M, K) x (K, N) region at this
    block triple; inf when the tiles overflow VMEM.  The traffic model
    charges exterior bytes only — the whole point of the fusion — plus
    the x re-stream across n blocks and the w re-stream across m blocks
    (the blocked-matmul reality the block sizes trade against)."""
    M = int(ctx.get("M", 1024))
    N = int(ctx.get("N", 1024))
    K = int(ctx.get("K", 1024))
    db = int(ctx.get("dtype_bytes", 4))
    bm = min(int(candidate["block_m"]), M)
    bn = min(int(candidate["block_n"]), N)
    bk = min(int(candidate["block_k"]), K)
    if fused_vmem_bytes(bm, bn, bk, db) > _VMEM_BUDGET:
        return math.inf
    n_m, n_n, n_k = -(-M // bm), -(-N // bn), -(-K // bk)
    steps = n_m * n_n * n_k
    flops = 2 * M * N * K
    # x streams once per n-block column, w once per m-block row
    traffic = (M * K * n_n + K * N * n_m + M * N) * db
    return roofline_seconds(flops, traffic) + steps * _GRID_STEP_S


# ----------------------------------------------------------- bucket ladders
def expected_padding(ladder, sizes):
    """(padded_rows / real_rows) of serving ``sizes`` under ``ladder``,
    with oversize requests chunked at the largest bucket first — the
    engine's admission behavior (serving/engine.py)."""
    ladder = sorted(set(int(b) for b in ladder))
    top = ladder[-1]
    real = alloc = 0
    for n in sizes:
        n = int(n)
        real += n
        while n > top:
            alloc += top
            n -= top
        if n:
            i = 0
            while ladder[i] < n:
                i += 1
            alloc += ladder[i]
    if not real:
        return 0.0
    return (alloc - real) / real


def ladder_cost(candidate, ctx):
    """Rank bucket ladders: expected pad-waste ratio (the per-request
    compute overhead) plus a small per-bucket compile penalty — compile
    count is len(ladder) x replicas forever (serving/buckets.py)."""
    ladder = candidate["buckets"]
    sizes = ctx.get("sizes") or (1,)
    if not ladder:
        return math.inf
    return expected_padding(ladder, sizes) + 0.02 * len(ladder)

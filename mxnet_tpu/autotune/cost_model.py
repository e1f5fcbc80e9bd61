"""Analytic roofline cost model — the cheap pruning half of the search
(ISSUE 6).

Estimates are in SECONDS and deliberately coarse: the model's only job is
to rank candidates well enough that the measured search (search.py) never
wastes a compile on a block triple that overflows VMEM or a ladder that
pads 4x, not to predict absolute times. The ceilings are the chip's
published peaks (``context.DEVICE_PEAKS``), the same basis as ``mfu_pct``
in PERF_LEDGER.jsonl.
"""
from __future__ import annotations

import math

from ..context import DEVICE_PEAKS

__all__ = ["PEAK_FLOPS_PER_S", "PEAK_HBM_BYTES_PER_S", "VMEM_BYTES",
           "CEILINGS", "ridge_intensity", "roofline_seconds", "ladder_cost",
           "expected_padding", "fused_vmem_bytes", "fused_matmul_cost",
           "pow2_at_least"]


def pow2_at_least(n):
    """Smallest power of two >= n (shape-bucket / ladder-top rounding)."""
    p = 1
    while p < n:
        p <<= 1
    return p


# the published peaks of the chip the estimates are for: what
# observability/perf.py's MFU and roofline gauges are shares of
_PEAKS = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS_PER_S = _PEAKS["bf16_flops_per_s"]
PEAK_HBM_BYTES_PER_S = _PEAKS["hbm_bytes_per_s"]
# per-core VMEM; Pallas tiles + double-buffered input windows must fit
VMEM_BYTES = 16 * 2 ** 20

#: the table as reports carry it (perf_program_cost()["ceilings"])
CEILINGS = {
    "matmul_tf_s": PEAK_FLOPS_PER_S / 1e12,
    "hbm_gb_s": PEAK_HBM_BYTES_PER_S / 1e9,
    "vmem_bytes": VMEM_BYTES,
    "source": 'mxnet_tpu.context.DEVICE_PEAKS["TPU v5 lite"] (%s)'
              % _PEAKS["source"],
}


def ridge_intensity():
    """The roofline ridge point in FLOPs/byte at the published peaks:
    ops whose arithmetic intensity sits below it are bandwidth-bound."""
    return PEAK_FLOPS_PER_S / PEAK_HBM_BYTES_PER_S


_VMEM_BUDGET = int(VMEM_BYTES * 0.75)  # headroom for Mosaic's own buffers
# fixed cost per grid step (loop + DMA issue) — dominates tiny blocks
_GRID_STEP_S = 2e-7


def roofline_seconds(flops, hbm_bytes):
    """max(compute, bandwidth) time at the published peaks."""
    return max(flops / PEAK_FLOPS_PER_S, hbm_bytes / PEAK_HBM_BYTES_PER_S)


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

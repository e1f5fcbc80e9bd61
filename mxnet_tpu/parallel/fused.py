"""Fused matmul + epilogue Pallas kernels — the fusion-region code
generator (ISSUE 15; ROADMAP open item 3).

The graph-pass layer's ``fuse`` pass (graph_pass/fuse.py) carves
single-consumer Convolution/FullyConnected/dot + epilogue chains
(bias-add, activation, residual add, per-channel rescale) into one
``_FusedRegion`` node; this module is where those regions become code.
The flash-attention playbook applied to the rest of the model: the
matmul accumulates in fp32 VMEM scratch and the ENTIRE epilogue is
applied to the accumulator before the HBM writeback, so every interior
tensor of the region — the pre-bias, pre-activation, pre-residual
values that the unfused graph writes to and re-reads from HBM — never
leaves VMEM.  Block shapes are autotuned (``fusion.blocks``,
docs/autotune.md) with the analytic VMEM/roofline pruning in
``autotune.cost_model.fused_matmul_cost``.

Two entry points:

* :func:`fused_matmul` — (M, K) x (K, N) [or the FullyConnected
  (N, K) weight layout] with a static epilogue spec; returns None when
  :func:`kernel_plan` declines the shape (no tiling the TPU lowering
  accepts) — the caller (ops/fused.py, which asks ``kernel_plan`` first
  and reports the reason) then lowers the unfused reference composition
  instead, exactly like flash attention's prime-T fallback.  The
  decision is a static function of shapes, dtype and epilogue.
* :func:`fused_batch_matmul` — the (B, M, K) x (B, K, N) batch_dot
  variant (leading batch dim rides the grid, the flash-attention B*H
  pattern).

Epilogue step grammar (static tuples, produced by the fuse pass):

``("bias",)``        next extra input, (N,)-broadcast add
``("vmul",)/("vadd",)`` next extra input, last-axis vector mul/add
                      (the int8 per-channel rescale + fp32 bias)
``("res", op)``      next extra input, full-shape elemwise add/mul
``("act", kind)``    relu / sigmoid / tanh / softrelu / softsign
``("scalar", op, v)`` *_scalar ops (the attention 1/sqrt(D) scale)
``("cast", dtype)``  dtype change — a no-op in-kernel (the accumulator
                      is fp32 and the writeback casts once)
"""
from __future__ import annotations

import functools

import numpy as np

from ..config import get_flag
from .pallas_common import LANES, SUBLANES, aligned_block, pallas_call

__all__ = ["fused_matmul", "fused_batch_matmul", "supported_act",
           "kernel_plan", "pick_blocks", "resolve_blocks",
           "fused_shape_key"]

# activations the kernel applies on the fp32 accumulator; anything else
# keeps the region on the reference composition path
_ACTS = ("relu", "sigmoid", "tanh", "softrelu", "softsign")


def supported_act(kind):
    return kind in _ACTS


def _apply_act(y, kind):
    import jax
    import jax.numpy as jnp

    if kind == "relu":
        return jnp.maximum(y, 0.0)
    if kind == "sigmoid":
        return jax.nn.sigmoid(y)
    if kind == "tanh":
        return jnp.tanh(y)
    if kind == "softrelu":
        return jax.nn.softplus(y)
    if kind == "softsign":
        return y / (1.0 + jnp.abs(y))
    raise ValueError("unsupported fused activation %r" % (kind,))


def _apply_scalar(y, op, v):
    if op == "_mul_scalar":
        return y * v
    if op == "_div_scalar":
        return y / v
    if op == "_plus_scalar":
        return y + v
    if op == "_minus_scalar":
        return y - v
    if op == "_rminus_scalar":
        return v - y
    raise ValueError("unsupported fused scalar op %r" % (op,))


def fused_shape_key(M, N, K):
    """Shape-bucket key for ``fusion.blocks`` cache entries: every dim
    rounds up to a power of two (one tuning per bucket, not per exact
    shape)."""
    from ..autotune.cost_model import pow2_at_least

    return ("M%d" % pow2_at_least(int(M)), "N%d" % pow2_at_least(int(N)),
            "K%d" % pow2_at_least(int(K)))


def _tuned_int(value):
    try:
        value = int(value)
    except (TypeError, ValueError):
        return None
    return value if value > 0 else None


def resolve_blocks(M, N, K, dtype="float32", dtype_bytes=4, block_m=None,
                   block_n=None, block_k=None):
    """Block-bound resolution: explicit per-call override > tuning-cache
    ``fusion.blocks`` entry for this (shape bucket, dtype) > config
    flags (MXNET_FUSION_BLOCK_M/N/K).  One dict probe at trace time,
    the flash-attention consult discipline."""
    tuned = None
    if None in (block_m, block_n, block_k):
        from .. import autotune

        ctx = {"M": int(M), "N": int(N), "K": int(K),
               "dtype_bytes": int(dtype_bytes)}
        tuned = autotune.lookup_or_tune(
            "fusion.blocks", fused_shape_key(M, N, K), dtype=str(dtype),
            ctx=ctx)
    tuned = tuned if isinstance(tuned, dict) else {}
    block_m = int(block_m or _tuned_int(tuned.get("block_m"))
                  or get_flag("MXNET_FUSION_BLOCK_M"))
    block_n = int(block_n or _tuned_int(tuned.get("block_n"))
                  or get_flag("MXNET_FUSION_BLOCK_N"))
    block_k = int(block_k or _tuned_int(tuned.get("block_k"))
                  or get_flag("MXNET_FUSION_BLOCK_K"))
    return block_m, block_n, block_k


def pick_blocks(M, N, K, block_m, block_n, block_k, interpret=False):
    """``((bm, bn, bk), None)`` — concrete tiles under the bounds — or
    ``(None, reason)`` when the kernel declines this shape. A static rule
    of shapes only (:func:`.pallas_common.aligned_block`): compiled, bm
    is a multiple of 8 sublanes and bn/bk of 128 lanes, or the whole
    dimension when it fits under its bound; the interpreter takes any
    divisor; a dimension with only tiny divisors (the flash-attention
    prime-T rule) declines."""
    aligns = (1, 1, 1) if interpret else (SUBLANES, LANES, LANES)
    tiles = []
    for name, n, bound, align in zip("MNK", (M, N, K),
                                     (block_m, block_n, block_k), aligns):
        b = aligned_block(n, bound, align)
        if b is None:
            return None, ("%s=%d has no divisor in [%d, %d] that is a "
                          "multiple of %d" % (name, n, -(-bound // 8),
                                              bound, align))
        tiles.append(b)
    return tuple(tiles), None


def _epilogue_extras(epilogue):
    """Which steps consume an extra input, in order."""
    return [s for s in epilogue if s[0] in ("bias", "vmul", "vadd", "res")]


def kernel_plan(M, N, K, dtype, epilogue=(), extra_shapes=(), batch=None,
                block_m=None, block_n=None, block_k=None, interpret=False):
    """THE static lowering decision of :func:`fused_matmul` (and, with
    ``batch``, :func:`fused_batch_matmul`): ``((bm, bn, bk), None)`` or
    ``(None, reason)``. A function of shapes, dtype and the epilogue
    only, so ops/fused.py decides kernel-vs-reference before it builds
    anything and the region report (``Executor.fused_regions``) can name
    the reason."""
    extra_steps = _epilogue_extras(epilogue)
    if len(extra_steps) != len(extra_shapes):
        raise ValueError("fused kernel: %d extra inputs for %d "
                         "extra-consuming steps"
                         % (len(extra_shapes), len(extra_steps)))
    for step, shape in zip(extra_steps, extra_shapes):
        if step[0] != "res" and batch is not None:
            return None, ("%s step: vector epilogues belong to the dense "
                          "conv/FC kernel, not batch_dot" % step[0])
        want = (batch or 1) * M * N if step[0] == "res" else N
        if int(np.prod(shape)) != want:
            return None, ("%s operand of shape %s does not cover the "
                          "%d-element %s" % (
                              step[0], tuple(shape), want,
                              "output" if step[0] == "res" else "last axis"))
    dtype = np.dtype(dtype)
    bounds = resolve_blocks(M, N, K, dtype=str(dtype),
                            dtype_bytes=dtype.itemsize, block_m=block_m,
                            block_n=block_n, block_k=block_k)
    return pick_blocks(M, N, K, *bounds, interpret=interpret)


def _mm_kernel(*refs, n_extras, wt, epilogue, n_k, out_dtype):
    """One (m, n, k) grid step: fp32 accumulate, epilogue on the last k
    step, single HBM writeback."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x_ref, w_ref = refs[0], refs[1]
    extra_refs = refs[2:2 + n_extras]
    o_ref = refs[2 + n_extras]
    acc_ref = refs[3 + n_extras]
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if wt:  # w block is (bn, bk): y += x . w^T
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:   # w block is (bk, bn): y += x . w
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _finish():
        y = acc_ref[...]
        ei = 0
        for step in epilogue:
            kind = step[0]
            if kind in ("bias", "vadd"):
                y = y + extra_refs[ei][...].astype(jnp.float32)
                ei += 1
            elif kind == "vmul":
                y = y * extra_refs[ei][...].astype(jnp.float32)
                ei += 1
            elif kind == "res":
                r = extra_refs[ei][...].astype(jnp.float32)
                y = y * r if step[1] == "elemwise_mul" else y + r
                ei += 1
            elif kind == "act":
                y = _apply_act(y, step[1])
            elif kind == "scalar":
                y = _apply_scalar(y, step[1], step[2])
            elif kind == "cast":
                pass  # the writeback below casts exactly once
            else:
                raise ValueError("unknown fused epilogue step %r" % (step,))
        o_ref[...] = y.astype(out_dtype)


def fused_matmul(x, w, extras=(), epilogue=(), wt=True, block_m=None,
                 block_n=None, block_k=None, out_dtype=None,
                 interpret=False):
    """act((x @ w[.T]) ... epilogue ...) in ONE kernel; x: (M, K), w:
    (N, K) when ``wt`` (the FullyConnected weight layout) else (K, N).

    ``extras`` supplies one array per extra-consuming epilogue step in
    order: N-element vectors for bias/vmul/vadd, M*N elements for res.
    Returns the (M, N) result, or **None** when :func:`kernel_plan`
    declines (the caller then lowers its unfused reference composition).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[0] if wt else w.shape[1]
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    tiles, _ = kernel_plan(
        M, N, K, x.dtype, epilogue, [e.shape for e in extras],
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)
    if tiles is None:
        return None
    bm, bn, bk = tiles

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        (pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)) if wt
         else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))),
    ]
    extra_arrays = []
    for step, arr in zip(_epilogue_extras(epilogue), extras):
        if step[0] == "res":
            extra_arrays.append(arr.reshape(M, N))
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        else:
            extra_arrays.append(arr.reshape(1, N))
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))

    kernel = functools.partial(
        _mm_kernel, n_extras=len(extra_arrays), wt=wt,
        epilogue=tuple(epilogue), n_k=K // bk, out_dtype=out_dtype)
    return pallas_call(
        kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="fused_matmul",
    )(x, w, *extra_arrays)


def _bmm_kernel(*refs, n_extras, epilogue, n_k, out_dtype):
    """Batched variant: grid (B, m, n, k), one batch row per grid slab."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x_ref, w_ref = refs[0], refs[1]
    extra_refs = refs[2:2 + n_extras]
    o_ref = refs[2 + n_extras]
    acc_ref = refs[3 + n_extras]
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _finish():
        y = acc_ref[...]
        ei = 0
        for step in epilogue:
            kind = step[0]
            if kind == "res":
                r = extra_refs[ei][0].astype(jnp.float32)
                y = y * r if step[1] == "elemwise_mul" else y + r
                ei += 1
            elif kind == "act":
                y = _apply_act(y, step[1])
            elif kind == "scalar":
                y = _apply_scalar(y, step[1], step[2])
            elif kind == "cast":
                pass
            else:
                raise ValueError("unknown batched epilogue step %r"
                                 % (step,))
        o_ref[0] = y.astype(out_dtype)


def fused_batch_matmul(x, w, extras=(), epilogue=(), block_m=None,
                       block_n=None, block_k=None, out_dtype=None,
                       interpret=False):
    """The batch_dot region: x (B, M, K) @ w (B, K, N) with a
    scalar/act/residual epilogue (vector steps belong to the dense
    conv/FC path and are declined here).  Returns (B, M, N) or None
    when :func:`kernel_plan` declines."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, M, K = x.shape
    N = w.shape[2]
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    tiles, _ = kernel_plan(
        M, N, K, x.dtype, epilogue, [e.shape for e in extras], batch=B,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)
    if tiles is None:
        return None
    bm, bn, bk = tiles

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda b, i, j, k: (b, i, k)),
        pl.BlockSpec((1, bk, bn), lambda b, i, j, k: (b, k, j)),
    ] + [pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j))
         for _ in extras]

    kernel = functools.partial(
        _bmm_kernel, n_extras=len(extras), epilogue=tuple(epilogue),
        n_k=K // bk, out_dtype=out_dtype)
    return pallas_call(
        kernel,
        grid=(B, M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "parallel",
                                 "arbitrary")),
        name="fused_batch_matmul",
    )(x, w, *[e.reshape(B, M, N) for e in extras])

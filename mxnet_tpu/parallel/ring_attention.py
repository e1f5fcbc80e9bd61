"""Ring attention — sequence/context parallelism for long sequences.

Not present in the reference (SURVEY.md §2.3 lists sequence parallelism as
absent); this is new TPU-first capability required for long-context work:
the sequence axis is sharded over a mesh axis ('sp'), each device holds a
(T/n)-length Q/K/V shard, and K/V blocks rotate around the ring with
``lax.ppermute`` while a streaming (online-softmax) accumulator combines
per-block attention — compute overlaps the ICI transfer and no device ever
materializes the full T×T score matrix (Liu et al., "Ring Attention with
Blockwise Transformers", 2023 — the public recipe; implementation here is
original).
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["ring_attention", "attention_reference"]


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain full-materialization attention (the parity oracle).

    q/k/v: (batch, heads, T, head_dim).
    """
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision="highest") * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    import jax

    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v, precision="highest")


def _merge_partials(o1, lse1, o2, lse2):
    """Combine two normalized partial attention results via their row
    logsumexps (associative — the streaming-softmax merge)."""
    import jax.numpy as jnp

    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w1 = jnp.where(jnp.isneginf(lse1), 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(jnp.isneginf(lse2), 0.0, jnp.exp(lse2 - m_safe))
    l = w1 + w2
    o = ((o1.astype(jnp.float32) * w1[..., None]
          + o2.astype(jnp.float32) * w2[..., None])
         / jnp.maximum(l, 1e-30)[..., None])
    return o, m + jnp.log(jnp.maximum(l, 1e-30))


def _ring_attention_local_flash(q, k, v, axis_name, causal, scale,
                                interpret=False):
    """Ring body with the per-step block attention run as the Pallas
    flash kernel (parallel/flash_attention.py — forward AND backward are
    tiled kernels, so the sharded path inherits the O(T) training
    memory). The ring is unrolled (n is static): step 0 is the local
    diagonal block (causal within the shard); later steps are full
    blocks whose contribution is discarded via lse = -inf when the
    source shard is in the causal future. Gradients ride each kernel's
    custom_vjp plus the differentiable logsumexp merge."""
    import jax.numpy as jnp
    from jax import lax

    from .flash_attention import flash_attention

    from ..observability import device_scope

    n = lax.psum(1, axis_name)  # static (mesh shape is static)
    my_idx = lax.axis_index(axis_name)
    # device_scope labels land in the XPlane device trace, so
    # tools/trace_report.py can attribute ring time to per-step comms
    # (ring_comm_*) vs per-step block attention (ring_attn_step_*)
    with device_scope("ring_attn_step_0"):
        o_acc, lse_acc = flash_attention(q, k, v, causal=causal,
                                         scale=scale, interpret=interpret,
                                         return_lse=True)
    o_acc = o_acc.astype(jnp.float32)
    k_cur, v_cur = k, v
    perm = [(j, (j + 1) % n) for j in range(n)]
    for i in range(1, n):
        with device_scope("ring_comm_%d" % i):
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        with device_scope("ring_attn_step_%d" % i):
            o_b, lse_b = flash_attention(q, k_cur, v_cur, causal=False,
                                         scale=scale, interpret=interpret,
                                         return_lse=True)
        if causal:
            # src strictly before us: fully visible; after us: fully
            # masked (lse = -inf zeroes it out of the merge)
            src = (my_idx - i) % n
            lse_b = jnp.where(src < my_idx, lse_b, -jnp.inf)
        o_acc, lse_acc = _merge_partials(o_acc, lse_acc, o_b, lse_b)
    return o_acc.astype(q.dtype)


def _ring_attention_local(q, k, v, axis_name, causal, scale,
                          vary_axes=None, use_flash=False,
                          interpret=False):
    """shard_map body: q/k/v are the LOCAL sequence shards
    (batch, heads, T_local, d); returns the local output shard."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if use_flash:
        return _ring_attention_local_flash(q, k, v, axis_name, causal,
                                           scale, interpret=interpret)

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    Tl = q.shape[2]
    q32 = q.astype(jnp.float32) * scale
    # global positions of the local queries
    q_pos = my_idx * Tl + jnp.arange(Tl)

    def combine(acc, m, l, k_cur, v_cur, i):
        """Fold one K/V block into the online-softmax accumulator."""
        src = (my_idx - i) % n  # which shard this block came from
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            k_cur.astype(jnp.float32),
                            precision="highest")
        if causal:
            k_pos = src * Tl + jnp.arange(Tl)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        block_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, block_max)
        new_m_safe = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        p = jnp.exp(scores - new_m_safe[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        correction = jnp.where(jnp.isneginf(m), 0.0,
                               jnp.exp(m - new_m_safe))
        new_l = l * correction + jnp.sum(p, axis=-1)
        new_acc = (acc * correction[..., None]
                   + jnp.einsum("bhqk,bhkd->bhqd", p,
                                v_cur.astype(jnp.float32),
                                precision="highest"))
        return new_acc, new_m, new_l

    from ..observability import device_scope

    def step(carry, i):
        k_cur, v_cur, acc, m, l = carry
        with device_scope("ring_attn_step"):
            acc, m, l = combine(acc, m, l, k_cur, v_cur, i)
        # rotate K/V to the next ring position (ICI neighbor exchange)
        with device_scope("ring_comm"):
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m, l), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    # the carries become device-varying after one ring step; mark the
    # initial values varying over every sharded axis so scan carry types
    # match (with tensor parallelism the values vary over tp too)
    from .pipeline import _mark_varying

    va = tuple(vary_axes or (axis_name,))
    acc0, m0, l0 = (_mark_varying(x, va) for x in (acc0, m0, l0))
    if n > 1:
        # n-1 rotations; the final block is folded without the (wasted)
        # last neighbor exchange
        (k_l, v_l, acc, m, l), _ = lax.scan(
            step, (k, v, acc0, m0, l0), jnp.arange(n - 1))
        acc, m, l = combine(acc, m, l, k_l, v_l, n - 1)
    else:
        acc, m, l = combine(acc0, m0, l0, k, v, 0)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None,
                   head_axis=None, batch_axis=None, use_flash=None,
                   interpret=False):
    """Sequence-parallel attention over ``mesh`` axis ``axis``.

    q/k/v are GLOBAL (batch, heads, T, head_dim) arrays (or already
    sharded on the sequence dim); T must divide by the axis size. Returns
    the global attention output with the same sharding. Differentiable —
    the vjp rides the same ring in reverse (autodiff of scan+ppermute,
    or the flash kernels' custom vjp on the flash path).

    ``use_flash`` selects the per-ring-step local attention: the Pallas
    flash kernel (forward and backward both tiled — the within-chip
    blocking composes with the across-chip ring) or the dense blockwise
    XLA formula. Default (None) follows config.py's
    MXNET_RING_ATTENTION_FLASH: the kernel on TPU backends, dense
    elsewhere. ``interpret`` runs the kernel in the Pallas interpreter
    (tests on CPU).
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..config import get_flag

    if use_flash is None:
        flag = get_flag("MXNET_RING_ATTENTION_FLASH")
        use_flash = flag == 2 or (
            flag == 1 and jax.default_backend() == "tpu")
        if flag == 2 and jax.default_backend() != "tpu":
            # documented contract: 2 forces the kernel on any backend —
            # off-TPU that means the Pallas interpreter
            interpret = True

    d = q.shape[-1]
    # python float stays weakly typed (a np.float64 scalar would promote
    # the whole ring to f64 under x64)
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    # heads and batch may additionally be sharded (tensor/data
    # parallelism compose with the sequence ring: each (dp, tp) shard
    # runs its own ring over its batch rows and heads)
    spec = P(batch_axis, head_axis, axis, None)
    vary = tuple(a for a in (batch_axis, head_axis, axis) if a is not None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis,
                          causal=causal, scale=scale, vary_axes=vary,
                          use_flash=use_flash, interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call has no shard_map replication rule; the flash body
        # is per-device SPMD anyway, so skip the varying-axes check there
        check_vma=not use_flash)
    from ..observability import counter, trace_span

    # host span = the whole sharded dispatch; per-ring-step attribution
    # lives in the device trace via the device_scope labels above
    with trace_span("ring_attention", "parallel"):
        out = fn(q, k, v)
    counter("ring_attention.calls").inc()
    return out

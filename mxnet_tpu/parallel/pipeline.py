"""Pipeline parallelism: GPipe-style microbatch schedule over a 'pp' mesh
axis (beyond the reference — its nearest analog is group2ctx operator
placement without microbatching, SURVEY.md §2.3).

Each pipeline rank holds one stage's parameters (stacked and sharded on
'pp'); activations flow rank→rank with ``lax.ppermute`` while microbatches
stream in, so at steady state every rank computes a different microbatch —
the classic (M + S - 1)-tick schedule with bubble fraction (S-1)/(M+S-1).
Differentiable: jax autodiff reverses the schedule (activations re-flow
backward along the same ring).
"""
from __future__ import annotations

import functools

__all__ = ["pipeline_apply"]


def _pipeline_local(stage_params, microbatches, stage_fn, axis_name,
                    n_stages, n_micro):
    import jax
    import jax.numpy as jnp
    from jax import lax

    stage = lax.axis_index(axis_name)
    # local stage params arrive stacked with a leading length-1 shard dim
    local_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    mb_shape = microbatches.shape[1:]

    def tick(carry, t):
        cur, outputs = carry
        # stage 0 ingests microbatch t (zeros on bubble ticks)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        fresh = lax.dynamic_index_in_dim(microbatches, mb_idx, 0,
                                         keepdims=False)
        inp = jnp.where(stage == 0, fresh, cur)
        out = stage_fn(local_params, inp)
        # the final stage banks its result for microbatch t-(S-1)
        done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_ready = (stage == n_stages - 1) & (t >= n_stages - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_ready, out,
                      lax.dynamic_index_in_dim(outputs, done_idx, 0,
                                               keepdims=False)),
            done_idx, 0)
        # activations advance one rank around the ring
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        nxt = lax.ppermute(out, axis_name, perm)
        return (nxt, outputs), None

    cur0 = jnp.zeros(mb_shape, microbatches.dtype)
    outs0 = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)
    cur0, outs0 = (_mark_varying(x, (axis_name,)) for x in (cur0, outs0))
    (_, outputs), _ = lax.scan(
        tick, (cur0, outs0), jnp.arange(n_micro + n_stages - 1))
    return outputs[None]  # re-add the shard dim: (1, M, ...) per rank


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis="pp",
                   n_microbatches=None):
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` pipelined over
    mesh axis ``axis``.

    stage_fn(params_i, mb) -> mb' must be shape-preserving (classic GPipe
    homogeneous stages). ``stacked_params``: pytree whose leaves have a
    leading n_stages dim (sharded on ``axis``). ``x``: (batch, ...) global
    input; it is split into ``n_microbatches`` along the batch dim.
    Returns f_{S-1}(...f_0(x)) with the same batch layout.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                "stacked_params leading dim %d must equal the %r axis "
                "size %d" % (leaf.shape[0], axis, n_stages))
    M = n_microbatches or n_stages
    B = x.shape[0]
    assert B % M == 0, "batch must divide into microbatches"
    mbs = x.reshape((M, B // M) + x.shape[1:])
    # every rank sees the full microbatch stream; stage params sharded
    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis, n_stages=n_stages, n_micro=M),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(axis),
    )
    out = fn(stacked_params, mbs)      # (S, M, mb, ...)
    final = out[-1]                    # last rank's banked outputs
    return final.reshape((B,) + final.shape[2:])


def _mark_varying(x, axes):
    """Mark a value as device-varying over mesh axes (scan carries must
    match the varying-axes type of the loop body outputs)."""
    from jax import lax

    return lax.pcast(x, axes, to="varying")

"""Sparse mixture-of-experts layer parts: the router, the dispatch plan of
an expert-parallel rank that is told which experts it holds, and the
grouped matmul over those experts as Pallas TPU kernels.

- :func:`route` scores every token against ALL experts (sigmoid, float32),
  selects the top-k by score plus a selection-only bias, and weights the
  selected experts by their normalised scores times a scaling factor.
- :func:`plan_dispatch` lays the (token, expert) pairs whose expert lies in
  the held range out as rows sorted by expert, each expert's group padded
  to a whole number of row tiles (at least one, so every held expert's
  weight gradient is written). Shapes are static and no pair is ever
  dropped, so there are two row budgets: :func:`row_budget`, the worst
  case the routing can produce (every token's top-k inside the held
  range), and :func:`compact_row_budget`, twice what an even router sends
  the held range. The live rows are a prefix of either layout and the
  kernels skip the tiles past them.
- :func:`in_the_layout_that_fits` runs the routed block in the compact
  layout where this step's routing fits it and in the worst-case layout
  otherwise, chosen on the device from the plan's live-tile count: the
  step pays for the rows the router sent, and a routing past the compact
  budget costs time, never a pair.
- :func:`dispatch` / :func:`combine` move token rows into and out of a
  layout; each is the other's transpose. Into it is a gather by row; out
  of it is a sum over the pairs (a gather, in XLA) or over the rows (the
  ``moe_sum_to_tokens`` kernel: a selection matmul), whichever are fewer.
- :func:`gmm` is the grouped matmul ``rows[i] @ w[expert_of_tile(i)]``;
  its device events are named ``moe_gmm_fwd`` (also the input's gradient,
  with the weight read transposed) and ``moe_gmm_dw`` (the weight's
  gradient, accumulated over an expert's row tiles in fp32 VMEM scratch).

What the absent ranks' experts would add is left out: the layer's output
is this rank's partial sum (plus what every rank computes alike), and no
code stands in for the exchange.
"""
from __future__ import annotations

import functools

from .pallas_common import pallas_call

__all__ = ["route", "select", "weigh", "plan_dispatch", "dispatch",
           "combine", "gmm", "in_the_layout_that_fits", "row_budget",
           "compact_row_budget", "GMM_BLOCK_ROWS"]

#: rows of a grouped-matmul tile, and what each expert's group is padded
#: to; interpreted (tests) any multiple of 8 works
GMM_BLOCK_ROWS = 256
#: column bound of a weight tile of the forward / input-gradient kernel and
#: (k, n) bounds of the weight-gradient kernel's accumulator
_GMM_BLOCK_COLS = 1024
_GMM_DW_BLOCK = (1024, 2048)
_VMEM_LIMIT = 96 * 2 ** 20

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def route(logits, bias, top_k, scale):
    """``logits``: (N, E) float32 router outputs; ``bias``: (E,) the expert
    bias, used for the selection only (no gradient reaches it). Returns
    (idx (N, k) int32, weight (N, k) float32): the k experts with the
    largest ``sigmoid(z) + b`` and ``scale * sigmoid(z_e) / sum_topk``.

    The selection and the weights are :func:`select` and :func:`weigh`, in
    turn. A layer that is recomputed calls the two itself and keeps the
    ids by name between them, so that its backward pass weighs and plans
    from the kept ids and selects nothing again. (The two stand at the
    file's end: no line of the kernels below moves for them, and the
    kernels' serialized bodies carry their line numbers.)"""
    idx = select(logits, bias, top_k)
    return idx, weigh(logits, idx, scale)


def row_budget(n_tokens, top_k, n_held, block_rows):
    """Rows of the sorted layout: the worst case the routing can produce
    (every token's pairs inside the held range) plus each group's padding
    to whole tiles."""
    return n_tokens * min(top_k, n_held) + n_held * block_rows


#: the compact layout's room over the pairs the router sends the held range
#: in expectation. Twice: the widest held load seen at the seeded start of
#: the one cell with a routed layer is +10.6% (PERF.md, PR 28), a trained
#: router balances its load, and a routing past it costs the worst-case
#: layout's time for that layer and step, never a pair
_COMPACT_ROOM = 2


def compact_row_budget(n_tokens, top_k, n_held, n_experts, block_rows):
    """Rows of the layout the routing fits in practice: ``_COMPACT_ROOM``
    times the pairs a router that spreads its choices evenly sends the
    held experts, plus each group's padding to whole tiles; never more
    than :func:`row_budget`, and equal to it where every expert is held."""
    expected = -(-n_tokens * top_k * n_held // n_experts)
    return min(row_budget(n_tokens, top_k, n_held, block_rows),
               _COMPACT_ROOM * expected + n_held * block_rows)


def plan_dispatch(idx, held, block_rows, n_rows=None):
    """The layout of this rank's share in ``n_rows`` rows (None:
    :func:`row_budget`, which every routing fits). ``idx``:
    (N, k) global expert ids; ``held``: (lo, hi) the range of experts held.
    Returns a dict of int32 arrays: ``row_of_pair`` (N, k) (R where the
    pair's expert is not held), ``pair_of_row`` (R,) (N*k on padding and
    dead rows), per row tile ``tile_expert`` / ``tile_first`` /
    ``tile_last``, ``n_live`` (1,) the live tiles, and ``counts`` (held,)
    each held expert's pairs. The live rows are a prefix of the layout, so
    a layout of fewer rows is the worst-case one cut short: it holds every
    pair where ``n_live * block_rows <= n_rows``, which is for the caller
    to see to (:func:`in_the_layout_that_fits`); pairs past it are marked
    as not held."""
    import jax.numpy as jnp

    lo, hi = held
    n_held = hi - lo
    N, k = idx.shape
    P = N * k
    R = row_budget(N, k, n_held, block_rows) if n_rows is None else n_rows
    n_tiles = R // block_rows
    local = idx.reshape(P) - lo
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts_all = jnp.zeros(n_held + 1, jnp.int32).at[key].add(1)
    counts = counts_all[:n_held]
    group_tiles = jnp.maximum(1, -(-counts // block_rows))
    tile_end = jnp.cumsum(group_tiles)
    tile_start = tile_end - group_tiles
    first_sorted = jnp.cumsum(counts_all) - counts_all   # start in `order`
    key_sorted = key[order]
    rank = jnp.arange(P, dtype=jnp.int32) - first_sorted[key_sorted]
    row_start = jnp.concatenate(
        [tile_start * block_rows, jnp.full((1,), R, jnp.int32)])
    rows_sorted = row_start[key_sorted] + rank
    rows_sorted = jnp.where((key_sorted < n_held) & (rows_sorted < R),
                            rows_sorted, R)
    row_of_pair = jnp.zeros(P, jnp.int32).at[order].set(
        rows_sorted.astype(jnp.int32), unique_indices=True)
    pair_of_row = jnp.full(R, P, jnp.int32).at[rows_sorted].set(
        order, mode="drop", unique_indices=True)
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(tiles[:, None] >= tile_end[None, :], axis=1),
        n_held - 1).astype(jnp.int32)
    return {"row_of_pair": row_of_pair.reshape(N, k),
            "pair_of_row": pair_of_row,
            "tile_expert": tile_expert,
            "tile_first": (tiles == tile_start[tile_expert]).astype(
                jnp.int32),
            "tile_last": (tiles == tile_end[tile_expert] - 1).astype(
                jnp.int32),
            "n_live": tile_end[-1:].astype(jnp.int32),
            "counts": counts}


def _take_rows(x, index):
    """x[index] with zeros where ``index`` is out of range."""
    import jax.numpy as jnp

    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _weight_of_pairs(weight, pair):
    """weight (N, k) at the flat pair ids ``pair``; 0 at N*k (no pair)."""
    k = weight.shape[1]
    return weight.at[pair // k, pair % k].get(mode="fill", fill_value=0)


def _sum_to_tokens(rows, weight, row_of_pair, pair_of_row, block=None):
    """out[t] = sum_j weight[t, j] * rows[row_of_pair[t, j]] (absent pairs
    add nothing; ``weight`` None: ones), accumulated in float32 and rounded
    once. Summed over whichever is fewer. Where the layout has room for
    every (token, slot) pair (``N * k <= R``: the worst-case layout) each
    pair gathers its row, absent pairs a row of zeros, and XLA sums them:
    6.0 ms on the v5e at sarvam's 65,536 pairs of 4096. Else (the compact
    layout) the rows are taken in token order, a bf16 gather, and added to
    their tokens by the ``moe_sum_to_tokens`` kernel below, a matmul with a
    selection matrix a tile pair (padding and dead rows, whose contents are
    undefined, sort to the end: the gather stops after the last chunk that
    holds a live row and the kernel zeroes what follows the live rows,
    since 0 x NaN is NaN); ``block``: its (token, row) tile bounds, None
    the file's. XLA's own forms of that sum took 4.5 ms there (sorted
    segment sum; an unsorted scatter-add 5.1-5.2: PERF.md, PR 29) and 5.3
    at laguna's 40,960 rows of 2048, where this path takes 2.7-3.1 alone
    and in the step the live chunks' gather 0.53 ms, its zeros 0.26, the
    kernel 0.35-0.49, the ids' and weights' gathers 0.6 and the rounding
    0.3 (tools/moe_layout_bench.py, tools/trace_report.py; PERF.md, PR
    35). A row or token count with no legal tile takes the pairs' path
    too."""
    import jax
    import jax.numpy as jnp

    from ..observability import counter
    from .pallas_common import LANES, aligned_block

    N, k = row_of_pair.shape
    R = pair_of_row.shape[0]
    interpret = jax.default_backend() != "tpu"
    tm, tr = block or _SUM_BLOCK
    tm = aligned_block(N, tm, 1 if interpret else 16)
    tr = aligned_block(R, tr, 1 if interpret else LANES)
    if N * k <= R or tm is None or tr is None:
        if weight is None:
            weight = jnp.ones((N, k), jnp.float32)
        picked = _take_rows(rows, row_of_pair.reshape(N * k)).reshape(
            N, k, rows.shape[-1])
        return jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                          weight.astype(jnp.float32)).astype(rows.dtype)
    counter("moe.sum_to_tokens_kernel").inc()
    by_token = jnp.argsort(pair_of_row).astype(jnp.int32)
    pair = pair_of_row[by_token]            # ascending; N*k past the held
    n_held = jnp.sum(pair < N * k, dtype=jnp.int32)  # the rows before them
    weights = () if weight is None else (
        _weight_of_pairs(weight, pair).astype(jnp.float32),)
    return _sum_call(N, tm, tr, interpret)(
        _take_the_first(n_held, rows, by_token),
        (pair // k).astype(jnp.int32), n_held.reshape(1),
        *weights).astype(rows.dtype)


def _take_the_first(n, rows, index):
    """rows[index] for the first ``n`` of ``index``, a chunk of
    ``_SUM_GATHER_ROWS`` at a time (the last chunk whole) and zeros after:
    XLA's gather reads a row in 51 ns on the v5e, wanted or not, so the
    compact layout's dead half costs as much as its live one."""
    import jax
    import jax.numpy as jnp

    from .pallas_common import aligned_block

    R = index.shape[0]
    chunk = aligned_block(R, _SUM_GATHER_ROWS, 1) or R

    def take_chunk(c, taken):
        at = (c * chunk).astype(jnp.int32)
        part = rows.at[jax.lax.dynamic_slice(index, (at,), (chunk,))].get(
            mode="promise_in_bounds", unique_indices=True)
        return jax.lax.dynamic_update_slice(taken, part, (at, jnp.int32(0)))

    return jax.lax.fori_loop(0, -(-n // chunk), take_chunk,
                             jnp.zeros_like(rows))


@functools.lru_cache(maxsize=None)
def _moves():
    """(dispatch, combine): each other's transpose, built once."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def dispatch(x, row_of_pair, pair_of_row):
        return _take_rows(x, pair_of_row // row_of_pair.shape[1])

    def dispatch_fwd(x, row_of_pair, pair_of_row):
        return (dispatch(x, row_of_pair, pair_of_row),
                (row_of_pair, pair_of_row))

    def dispatch_bwd(res, d_rows):
        row_of_pair, pair_of_row = res
        return (_sum_to_tokens(d_rows, None, row_of_pair, pair_of_row),
                None, None)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(rows, weight, row_of_pair, pair_of_row):
        return _sum_to_tokens(rows, weight, row_of_pair, pair_of_row)

    def combine_fwd(rows, weight, row_of_pair, pair_of_row):
        return (_sum_to_tokens(rows, weight, row_of_pair, pair_of_row),
                (rows, weight, row_of_pair, pair_of_row))

    def combine_bwd(res, d_out):
        # both gradients from one gather of d_out by row: the weight's is
        # <rows[r], d_out[token of r]> at the pair's row (rows no pair
        # names, undefined past the live tiles, are never read)
        rows, weight, row_of_pair, pair_of_row = res
        k = row_of_pair.shape[1]
        w_row = _weight_of_pairs(weight, pair_of_row)
        by_row = _take_rows(d_out, pair_of_row // k).astype(jnp.float32)
        d_rows = (by_row * w_row[:, None].astype(jnp.float32)).astype(
            rows.dtype)
        dots = jnp.sum(rows.astype(jnp.float32) * by_row, axis=1)
        d_weight = _take_rows(dots, row_of_pair).astype(weight.dtype)
        return d_rows, d_weight, None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def dispatch(x, plan):
    """Token rows (N, d) into the sorted layout (R, d): row r holds the
    token of its pair, padding and dead rows hold zeros."""
    return _moves()[0](x, plan["row_of_pair"], plan["pair_of_row"])


def combine(rows, weight, plan):
    """(R, d) expert outputs back to tokens: ``out[t] = sum_j weight[t, j]
    * rows[row_of_pair[t, j]]`` over the pairs held here."""
    return _moves()[1](rows, weight, plan["row_of_pair"],
                       plan["pair_of_row"])


def in_the_layout_that_fits(block, compact, worst, *operands):
    """``block(plan, *operands)`` in the compact layout where this routing
    fits it (``n_live`` tiles within its rows, known from the counts before
    a row moves), else in the worst-case one: a ``lax.cond``, so the step
    runs one branch's buffers. Both are exact and neither drops a pair.
    Where the two layouts have the same rows no conditional is traced.

    ``compact`` / ``worst``: :func:`plan_dispatch` of one routing at the
    two budgets. ``block`` is differentiable in ``operands``. The backward
    is again a conditional on the same predicate, and each of its branches
    recomputes its own forward from the operands: differentiating a
    ``cond`` directly would have each branch emit zeros in the shapes of
    the other's residuals, the worst-case buffers among them."""
    import jax

    if compact["pair_of_row"].shape == worst["pair_of_row"].shape:
        return block(worst, *operands)

    def either(on_plan, fits, compact, worst, *args):
        return jax.lax.cond(fits, lambda c, w, *a: on_plan(c, *a),
                            lambda c, w, *a: on_plan(w, *a),
                            compact, worst, *args)

    def pull_back(plan, operands, d_out):
        return jax.vjp(lambda *ops: block(plan, *ops), *operands)[1](d_out)

    @jax.custom_vjp
    def run(fits, compact, worst, operands):
        return either(lambda plan, ops: block(plan, *ops),
                      fits, compact, worst, operands)

    def run_fwd(*args):
        return run(*args), args

    def run_bwd(args, d_out):
        return None, None, None, either(pull_back, *args, d_out)

    run.defvjp(run_fwd, run_bwd)
    fits = worst["n_live"][0] <= compact["tile_expert"].shape[0]
    return run(fits, compact, worst, operands)


# --- the grouped matmul ----------------------------------------------------
def _fwd_kernel(te_ref, live_ref, x_ref, w_ref, o_ref, *, transpose_rhs):
    """One (column tile, row tile) step: the row tile times its expert's
    weight tile. Tiles past the live ones do nothing (their index maps
    name the last live tile: no DMA, and no write-back of their own)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], _NT if transpose_rhs else _NN,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _dw_kernel(te_ref, first_ref, last_ref, live_ref, x_ref, dy_ref, dw_ref,
               acc_ref):
    """One (k tile, n tile, row tile) step of the weight gradient: x^T dy
    of the row tile, accumulated in fp32 over the expert's row tiles."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    live = i < live_ref[0]

    @pl.when(live & (first_ref[i] == 1))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when(live)
    def _():
        acc_ref[...] += jax.lax.dot_general(  # graftlint: disable=G003 — a Pallas kernel writes its refs
            x_ref[...], dy_ref[...], _TN, preferred_element_type=jnp.float32)

    @pl.when(live & (last_ref[i] == 1))
    def _():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)  # graftlint: disable=G003 — a Pallas kernel writes its refs


def _col_block(n, bound, interpret):
    from .pallas_common import LANES, aligned_block

    block = aligned_block(n, bound, 1 if interpret else LANES)
    if block is None:
        raise ValueError("moe.gmm: no legal tile of a %d-wide axis under %d"
                         % (n, bound))
    return block


@functools.lru_cache(maxsize=64)
def _fwd_call(transpose_rhs, block_rows, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(x, w, tile_expert, n_live):
        R, K = x.shape
        n_out = w.shape[1] if transpose_rhs else w.shape[2]
        tn = _col_block(n_out, _GMM_BLOCK_COLS, interpret)

        def row(i, live):
            return jnp.minimum(i, live[0] - 1)

        w_spec = (pl.BlockSpec((1, tn, K),
                               lambda j, i, te, live: (te[i], j, 0))
                  if transpose_rhs else
                  pl.BlockSpec((1, K, tn),
                               lambda j, i, te, live: (te[i], 0, j)))
        return pallas_call(
            functools.partial(_fwd_kernel, transpose_rhs=transpose_rhs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_out // tn, R // block_rows),
                in_specs=[
                    pl.BlockSpec((block_rows, K),
                                 lambda j, i, te, live: (row(i, live), 0)),
                    w_spec],
                out_specs=pl.BlockSpec(
                    (block_rows, tn),
                    lambda j, i, te, live: (row(i, live), j))),
            out_shape=jax.ShapeDtypeStruct((R, n_out), x.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="moe_gmm_fwd",
        )(tile_expert, n_live, x, w)

    return jax.jit(call)


@functools.lru_cache(maxsize=64)
def _dw_call(n_experts, block_rows, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(x, dy, tile_expert, tile_first, tile_last, n_live):
        R, K = x.shape
        n_out = dy.shape[1]
        tk = _col_block(K, _GMM_DW_BLOCK[0], interpret)
        tn = _col_block(n_out, _GMM_DW_BLOCK[1], interpret)

        def row(i, live):
            return jnp.minimum(i, live[0] - 1)

        return pallas_call(
            _dw_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(K // tk, n_out // tn, R // block_rows),
                in_specs=[
                    pl.BlockSpec((block_rows, tk),
                                 lambda a, b, i, te, fi, la, live:
                                 (row(i, live), a)),
                    pl.BlockSpec((block_rows, tn),
                                 lambda a, b, i, te, fi, la, live:
                                 (row(i, live), b))],
                out_specs=pl.BlockSpec(
                    (1, tk, tn),
                    lambda a, b, i, te, fi, la, live: (te[i], a, b)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((n_experts, K, n_out), x.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="moe_gmm_dw",
        )(tile_expert, tile_first, tile_last, n_live, x, dy)

    return jax.jit(call)


@functools.lru_cache(maxsize=None)
def _gmm_of(block_rows, interpret):
    """The differentiable grouped matmul of one tile height: the compiled
    kernels, or the same kernels in the Pallas interpreter."""
    import jax

    def forward(x, w, plan, transpose_rhs=False):
        return _fwd_call(transpose_rhs, block_rows, interpret)(
            x, w, plan["tile_expert"], plan["n_live"])

    def weight_grad(x, dy, plan, n_experts):
        return _dw_call(n_experts, block_rows, interpret)(
            x, dy, plan["tile_expert"], plan["tile_first"],
            plan["tile_last"], plan["n_live"])

    @jax.custom_vjp
    def gmm(x, w, plan):
        return forward(x, w, plan)

    def gmm_fwd(x, w, plan):
        return forward(x, w, plan), (x, w, plan)

    def gmm_bwd(res, dy):
        x, w, plan = res
        dx = forward(dy, w, plan, transpose_rhs=True)
        dw = weight_grad(x, dy, plan, w.shape[0])
        return dx, dw.astype(w.dtype), None

    gmm.defvjp(gmm_fwd, gmm_bwd)
    return gmm


def gmm(x, w, plan, block_rows=GMM_BLOCK_ROWS, interpret=None):
    """Grouped matmul: ``x`` (R, K) rows in the layout of ``plan``, ``w``
    (E, K, N) the held experts' weights; (R, N), row tile i times
    ``w[plan["tile_expert"][i]]``. Rows of tiles past ``plan["n_live"]``
    are not computed (their contents are undefined; :func:`combine` never
    reads them). Differentiable in ``x`` and ``w`` by the same kernels.
    ``interpret``: run the kernels in the Pallas interpreter; None does so
    wherever the backend is not a TPU (the one path off the chip)."""
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gmm_of(int(block_rows), bool(interpret))(
        x, w, {k: plan[k] for k in ("tile_expert", "tile_first",
                                    "tile_last", "n_live")})


# --- the rows -> tokens sum ---------------------------------------------------
#: (token, row) tile bounds of the rows -> tokens sum and the column bound
#: of its output tile, by tools/moe_layout_bench.py --tile on the v5e
#: (PERF.md, PR 35)
_SUM_BLOCK = (128, 256)
_SUM_BLOCK_COLS = 4096
#: rows a step of the gather before it (1024 to 4096 take the same time)
_SUM_GATHER_ROWS = 2048


def _sum_kernel(tok_ref, row_ref, first_ref, n_ref, held_ref, t_ref, *refs):
    """One (column tile, work item) step: a row tile, in token order, added
    to the token tile that owns some of its rows, as ``S @ rows`` with
    ``S[m, r] = weight[r]`` where row r is token m's and 0 elsewhere,
    accumulated in the float32 output tile over the token tile's items.
    Rows past the ``held_ref[0]`` live ones are undefined and zeroed before
    the MXU sees them (0 x NaN is NaN). bf16 rows take one MXU pass a bf16 part of the weight (ones: one
    part, exact; float32 weights: a high and a low part, 16 bits of them);
    float32 rows one at ``highest``. Items past the live ones do nothing,
    as in the kernels above."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    *w_ref, x_ref, o_ref = refs
    i = pl.program_id(1)
    live = i < n_ref[0]
    tm, tr = o_ref.shape[0], x_ref.shape[0]

    @pl.when(live & (first_ref[i] == 1))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)  # graftlint: disable=G003 — a Pallas kernel writes its refs

    @pl.when(live)
    def _():
        r = row_ref[i] * tr + jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
        x = jnp.where(r < held_ref[0], x_ref[...], jnp.zeros_like(x_ref))
        mine = (t_ref[...] - tok_ref[i] * tm
                == jax.lax.broadcasted_iota(jnp.int32, (tm, tr), 0))
        w = w_ref[0][...] if w_ref else jnp.ones((1, tr), jnp.float32)
        parts, precision = [w], jax.lax.Precision.HIGHEST
        if x.dtype == jnp.bfloat16:
            precision = None
            if w_ref:
                high = w.astype(x.dtype).astype(jnp.float32)
                parts = [high, w - high]
        for part in parts:
            o_ref[...] += jax.lax.dot_general(  # graftlint: disable=G003 — a Pallas kernel writes its refs
                jnp.where(mine, part, 0.0).astype(x.dtype), x, _NN,
                precision=precision, preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=64)
def _sum_call(n_tokens, tm, tr, interpret):
    """The kernel over ``n_tokens`` tokens in (tm, tr) tiles: (rows in
    token order (R, d); their tokens (R,), ascending and ``n_tokens`` past
    the live ones; the live rows (1,)[; their float32 weights (R,)]) ->
    (n_tokens, d) float32, for the caller to round: XLA fuses
    the rounding into what reads it and moves it out of the layouts'
    conditional, whose result then is what the segment sum's was (with a
    bf16 result the step's buffers pack 377 MB worse in
    ``sarvam_train_t8192_b1``, at the same peak of live bytes: my compiles
    for the v5e, PERF.md, PR 35)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(rows, token, n_held, *w_row):
        R, d = rows.shape
        n_tok, n_row = n_tokens // tm, R // tr
        tn = _col_block(d, _SUM_BLOCK_COLS, interpret)
        # the work list: token tile i owns the rows [start[i], start[i+1])
        # and takes an item a row tile that run meets (one where it is
        # empty, to write its zeros): n_tok + n_row - 1 items at most
        def before(ascending, values, side="left"):
            return jnp.searchsorted(ascending, values, side=side,
                                    method="compare_all").astype(jnp.int32)

        start = before(token, tm * jnp.arange(n_tok + 1, dtype=jnp.int32))
        lo = jnp.minimum(start[:-1] // tr, n_row - 1)
        hi = jnp.maximum(lo, (start[1:] - 1) // tr)
        end = jnp.cumsum(hi - lo + 1, dtype=jnp.int32)
        begin = end - (hi - lo + 1)
        item = jnp.minimum(jnp.arange(n_tok + n_row, dtype=jnp.int32),
                           end[-1] - 1)
        tok = before(end, item, "right")
        row = lo[tok] + item - begin[tok]

        def block(shape, index):
            return pl.BlockSpec(shape, lambda c, i, tok, row, *_:
                                index(c, tok[i], row[i]))

        by_row = block((1, tr), lambda c, t, r: (0, r))
        return pallas_call(
            _sum_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(d // tn, n_tok + n_row),
                in_specs=[by_row] * (1 + len(w_row)) + [
                    block((tr, tn), lambda c, t, r: (r, c))],
                out_specs=block((tm, tn), lambda c, t, r: (t, c))),
            out_shape=jax.ShapeDtypeStruct((n_tokens, d), jnp.float32),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="moe_sum_to_tokens",
        )(tok, row, (item == begin[tok]).astype(jnp.int32), end[-1:], n_held,
          token.reshape(1, R), *(w.reshape(1, R) for w in w_row), rows)

    return jax.jit(call)


# --- the router's two halves ------------------------------------------------
def select(logits, bias, top_k):
    """The selection of :func:`route` alone: idx (N, k) int32. ``bias``
    None: an architecture without an expert bias selects by score."""
    import jax
    import jax.numpy as jnp

    score = jax.lax.stop_gradient(jax.nn.sigmoid(logits.astype(jnp.float32)))
    if bias is not None:
        score = score + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(score, top_k)
    return idx.astype(jnp.int32)


def weigh(logits, idx, scale):
    """The weights :func:`route` gives the selected experts ``idx``."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(score, idx, axis=-1)
    return scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

"""Micro-benchmark of the routed layer's row movement at one cell's shapes,
on the chip: ``chiprun -- python tools/moe_layout_bench.py``.

What it decides (PERF.md, PR 29): how the pair -> token sum
``out[t] = sum_j w[t, j] * rows[row_of_pair[t, j]]`` is best written when
the sorted layout holds far fewer rows than there are (token, slot) pairs.
The forms, all float32 sums cast to the rows' dtype:

- ``pairs``: a gather over all N*k pairs and an einsum (absent pairs read a
  zero row): what the worst-case layout does.
- ``scatter``: the layout's rows, weighted, added into their tokens'
  rows (``.at[token_of_row].add``).
- ``sorted_scatter``: the rows gathered in token order first, then a
  segment sum over sorted ids: what ``moe._sum_to_tokens`` ran until PR 35.
- ``mxu``: ``moe._sum_to_tokens`` itself: the rows gathered in token order
  (bf16), then the ``moe_sum_to_tokens`` kernel, a selection matmul a
  (token tile, row tile) pair; once a ``--tile`` (``TMxTR``, several
  allowed), weighted (``combine``'s forward: two MXU passes) and with
  weights of one (``dispatch``'s backward: one). Its dead and padding rows
  are filled with NaN here, which no other form would survive. Beside it
  ``gather.by_token``, the gather before the kernel, alone: of every row,
  and of the chunks that hold live rows (what the program does).
- ``token_list``: the rows gathered in token order, each summed with the
  up to k-1 rows after it that belong to the same token, and every token
  reads the row at the head of its run.
- ``windows``: the rows gathered in token order, then one (k, d) window a
  token and a masked sum.

Beside them the row gather of ``dispatch`` at both row counts, and the
weight's gradient both ways. One JSON line a measurement; ms are medians
over ``--reps`` calls, each waited for; ``max_abs_gap_to_float32`` is
against the float32 formula at ``highest`` before any rounding to bf16.
The defaults are ``sarvam_train_t8192_b1``'s shapes;
``laguna_train_t8192_b2``'s are ``--tokens 16384 --held 32 --experts 256
--width 2048``.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tile", nargs="*", default=None, metavar="TMxTR",
                    help="(token, row) tile bounds of the mxu form to sweep;"
                         " none: the constants of moe.py")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    N, k, d = args.tokens, args.top_k, args.width
    B = moe.GMM_BLOCK_ROWS
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind}))
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    idx = jax.lax.top_k(jax.random.uniform(keys[0], (N, args.experts)), k)[1]
    idx = idx.astype(jnp.int32)
    held = (0, args.held)
    worst = moe.row_budget(N, k, args.held, B)
    compact = moe.compact_row_budget(N, k, args.held, args.experts, B)
    plan_w = jax.jit(lambda i: moe.plan_dispatch(i, held, B))(idx)
    plan_c = jax.jit(lambda i: moe.plan_dispatch(i, held, B, compact))(idx)
    live = int(plan_c["n_live"][0]) * B
    print(json.dumps({"worst_rows": worst, "compact_rows": compact,
                      "live_rows": live, "pairs": N * k,
                      "pairs_held": int(plan_c["counts"].sum()),
                      "mxu_is_the_kernel": N * k > compact}))
    assert live <= compact
    x = jax.random.normal(keys[1], (N, d), jnp.bfloat16)
    weight = jax.random.uniform(keys[2], (N, k), jnp.float32)
    d_out = jax.random.normal(keys[3], (N, d), jnp.bfloat16)

    def timed(name, fn, *a):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*a))
        ms = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ms.append((time.perf_counter() - t0) * 1e3)
        print(json.dumps({"what": name, "ms": statistics.median(ms),
                          "ms_min": min(ms)}), flush=True)
        return out

    take = moe._take_rows
    for name, plan in (("worst", plan_w), ("compact", plan_c)):
        rows = timed("dispatch_gather." + name,
                     lambda x, p: take(x, p["pair_of_row"] // k), x, plan)
        if name == "compact":
            rows_c = rows
    # rows of live tiles hold their tokens; dead tiles are zeros here (the
    # kernels leave them unwritten): nothing below may read them

    def pairs(rows, weight, plan):
        picked = take(rows, plan["row_of_pair"].reshape(N * k)).reshape(
            N, k, d)
        return jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                          weight).astype(rows.dtype)

    def row_side(plan):
        pair = plan["pair_of_row"]
        return pair // k, take(weight.reshape(N * k), pair)

    def scatter(rows, weight, plan):
        token, w_row = row_side(plan)
        out = jnp.zeros((N, d), jnp.float32).at[token].add(
            rows.astype(jnp.float32) * w_row[:, None], mode="drop")
        return out.astype(rows.dtype)

    def token_order(rows, weight, plan):
        """Rows in token order, their weights, their tokens (N past the
        held pairs), and each token's run (start, count)."""
        pair = plan["pair_of_row"]
        order = jnp.argsort(pair).astype(jnp.int32)
        pair_sorted = pair[order]
        w_sorted = take(weight.reshape(N * k), pair_sorted)
        count = jnp.sum(plan["row_of_pair"] < pair.shape[0], axis=1)
        start = jnp.cumsum(count) - count
        return (take(rows, order), w_sorted, pair_sorted // k,
                start.astype(jnp.int32), count.astype(jnp.int32))

    def sorted_scatter(rows, weight, plan):
        g, w, token, _, _ = token_order(rows, weight, plan)
        return jax.ops.segment_sum(
            jnp.where((token < N)[:, None],
                      g.astype(jnp.float32) * w[:, None], 0.0),
            token, num_segments=N, indices_are_sorted=True).astype(rows.dtype)

    def mxu(block):
        return lambda rows, weight, plan: moe._sum_to_tokens(
            rows, weight, plan["row_of_pair"], plan["pair_of_row"],
            block=block)

    def token_list(rows, weight, plan):
        g, w, token, start, count = token_order(rows, weight, plan)
        R = g.shape[0]
        g = jnp.concatenate([g, jnp.zeros((k, d), g.dtype)])
        w = jnp.concatenate([w, jnp.zeros((k,), w.dtype)])
        token = jnp.concatenate([token, jnp.full((k,), N, token.dtype)])
        total = jnp.zeros((R, d), jnp.float32)
        for j in range(k):
            same = (token[j:R + j] == token[:R]) & (token[:R] < N)
            total = total + jnp.where(
                same[:, None],
                g[j:R + j].astype(jnp.float32) * w[j:R + j, None], 0.0)
        heads = take(total.astype(rows.dtype),
                     jnp.where(count > 0, start, R))
        return heads

    def windows(rows, weight, plan):
        g, w, _, start, count = token_order(rows, weight, plan)
        g = jnp.concatenate([g, jnp.zeros((k, d), g.dtype)])
        w = jnp.concatenate([w, jnp.zeros((k,), w.dtype)])
        slot = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        mask = jnp.arange(k)[None, :] < count[:, None]
        picked = jax.vmap(lambda s: jax.lax.dynamic_slice(
            g, (s, jnp.int32(0)), (k, d)))(start)
        w_win = jnp.where(mask, w[slot], 0.0)
        return jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                          w_win).astype(rows.dtype)

    want = timed("sum.pairs.worst", pairs,
                 jnp.concatenate([rows_c, jnp.zeros((worst - compact, d),
                                                    rows_c.dtype)]),
                 weight, plan_w)
    ones = jnp.ones_like(weight)

    def exact(weight):
        with jax.default_matmul_precision("highest"):
            picked = take(rows_c, plan_c["row_of_pair"].reshape(N * k))
            return jnp.einsum("nkd,nk->nd", picked.reshape(N, k, d).astype(
                jnp.float32), weight)

    def gaps(name, got, weight):
        got = got.astype(jnp.float32)
        print(json.dumps({
            "what": name,
            "max_abs_gap_to_pairs": float(jnp.max(jnp.abs(
                got - want.astype(jnp.float32)))) if weight is not ones
            else None,
            "max_abs_gap_to_float32": float(jnp.max(jnp.abs(
                got - exact(weight)))),
            "finite": bool(jnp.all(jnp.isfinite(got)))}), flush=True)

    for name, fn in (("pairs", pairs), ("scatter", scatter),
                     ("sorted_scatter", sorted_scatter),
                     ("token_list", token_list), ("windows", windows)):
        gaps("sum.%s.compact" % name,
             timed("sum.%s.compact" % name, fn, rows_c, weight, plan_c),
             weight)

    def by_token(live_only):
        def gather(rows, plan):
            order = jnp.argsort(plan["pair_of_row"]).astype(jnp.int32)
            n = jnp.sum(plan["pair_of_row"] < N * k, dtype=jnp.int32)
            return moe._take_the_first(n if live_only else rows.shape[0],
                                       rows, order)
        return gather

    timed("gather.by_token.every_row", by_token(False), rows_c, plan_c)
    timed("gather.by_token.live_chunks", by_token(True), rows_c, plan_c)
    rows_nan = jnp.where((plan_c["pair_of_row"] == N * k)[:, None], jnp.nan,
                         rows_c)
    for tile in args.tile or [None]:
        block = tile and tuple(int(n) for n in tile.split("x"))
        for kind, w in (("weighted", weight), ("ones", ones)):
            name = "sum.mxu.%s.%s" % (tile or "x".join(
                str(n) for n in moe._SUM_BLOCK), kind)
            gaps(name, timed(name, mxu(block), rows_nan,
                             None if w is ones else w, plan_c), w)

    # the weight's gradient: the pairs' rows against d_out, or the rows
    # against their tokens' d_out (which d_rows gathers anyway)
    def dweight_pairs(rows, plan):
        picked = take(rows, plan["row_of_pair"].reshape(N * k)).reshape(
            N, k, d)
        return jnp.einsum("nkd,nd->nk", picked.astype(jnp.float32),
                          d_out.astype(jnp.float32))

    def dweight_rows(rows, plan):
        token, w_row = row_side(plan)
        by_row = take(d_out, token).astype(jnp.float32)
        d_rows = (by_row * w_row[:, None]).astype(rows.dtype)
        scalar = jnp.sum(rows.astype(jnp.float32) * by_row, axis=1)
        return d_rows, take(scalar, plan["row_of_pair"].reshape(N * k)
                            ).reshape(N, k)

    a = timed("dweight.pairs.compact", dweight_pairs, rows_c, plan_c)
    _, b = timed("drows_and_dweight.rows.compact", dweight_rows, rows_c,
                 plan_c)
    print(json.dumps({"what": "dweight", "max_abs_gap": float(
        jnp.max(jnp.abs(a - b)))}))


if __name__ == "__main__":
    main()

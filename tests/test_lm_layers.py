"""A model described by its layers: latent attention (MLA), SwiGLU and
routed-expert layers of ``TransformerParallel`` against the plain reference
``perfbench/reference/mla_moe.py`` (float32, seeded, small widths), the
shares of an expert-parallel deployment adding up to the uncut layer, the
flash kernels at unequal q/k and v/o widths, the grouped matmul against a
per-expert loop, rotary/yarn closed forms, and the first block unchanged
to the bit."""
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu import observability as obs  # noqa: E402
from mxnet_tpu.parallel import lm_layers, make_mesh, moe  # noqa: E402
from mxnet_tpu.parallel.transformer import (TransformerParallel,  # noqa: E402
                                            _local_attention, _rms_norm)
from perfbench.reference import mla_moe as ref  # noqa: E402

fa = importlib.import_module("mxnet_tpu.parallel.flash_attention")
F32 = jnp.float32
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "deepseek_yarn"}


def small_cfg(layers=3, dense=1, experts=8, held=(2, 6), top_k=2):
    return dict(
        hidden_size=32, num_attention_heads=4, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
        intermediate_size=64, moe_intermediate_size=24,
        num_experts=held[1] - held[0], num_experts_per_tok=top_k,
        num_shared_experts=1, routed_scaling_factor=2.5,
        first_k_dense_replace=dense, num_hidden_layers=layers, vocab_size=64,
        rope_theta=10000, rms_norm_eps=1e-6, rope_scaling=dict(ROPE),
        published={"num_experts": experts},
        deployment={"experts_held": list(held)},
        optimizer={"learning_rate": 0.5})


def one_chip():
    return make_mesh({"dp": 1}, devices=jax.devices()[:1])


def seeded_params(model, seed, spread=1.0):
    """``model.init`` with the norm weights moved off 1 and the embedding
    spread out, so that every leaf's gradient and the routing say
    something."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, leaf in model.init(seed).items():
        if leaf.ndim == 1 and not name.endswith("router_bias"):
            leaf = leaf + 0.1 * jnp.asarray(rs.randn(*leaf.shape), F32)
        if name in ("embed",) or name.endswith("router"):
            leaf = leaf * (50.0 * spread)
        out[name] = leaf
    return out


def batch(seed, B=2, T=32, vocab=64):
    rs = np.random.RandomState(seed)
    return (rs.randint(0, vocab, (B, T)).astype(np.int32),
            rs.randint(0, vocab, (B, T)).astype(np.int32))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


# --- (a) the program against the plain reference -----------------------------
@pytest.mark.parametrize("layers,dense,remat,experts", [
    (1, 1, False, 8), (1, 0, False, 8), (3, 1, False, 8), (3, 1, True, 8),
    (3, 1, False, 32), (3, 1, True, 32)],
    ids=["dense_layer", "expert_layer", "model_1_plus_2",
         "model_1_plus_2_recomputed", "model_1_plus_2_two_layouts",
         "model_1_plus_2_two_layouts_recomputed"])
def test_program_matches_the_reference(layers, dense, remat, experts):
    """At 8 experts the compact row budget is the worst-case one (one
    layout, as before budgets were two); at 32 every routed layer traces
    both and its step runs the compact one."""
    cfg = small_cfg(layers, dense, experts=experts)
    model = TransformerParallel.from_config(one_chip(), cfg, remat=remat)
    assert ({n: tuple(s) for n, (s, _) in model.param_table().items()}
            == {n: tuple(s) for n, (s, _) in ref.param_table(cfg).items()})
    params = seeded_params(model, 3)
    start = {k: np.asarray(v) for k, v in params.items()}
    tok, tgt = batch(0)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
            params, tok, tgt)
        want_loss, want = ref.loss_and_grads(
            cfg, {k: jnp.asarray(v) for k, v in start.items()}, tok, tgt)
        assert abs(float(loss) - float(want_loss)) < 2e-6 * float(want_loss)
        for name in want:
            assert rel(grads[name], want[name]) < 2e-4, name
        held = [n for n in want if n.endswith("router_bias")]
        assert all(float(jnp.abs(grads[n]).max()) == 0.0 for n in held)
        # three SGD steps through the compiled step
        batches = [batch(i) for i in range(3)]
        step = model.step_fn(lr=cfg["optimizer"]["learning_rate"])
        losses = []
        for tok, tgt in batches:
            params, loss = step(params, *model.shard_batch(tok, tgt))
            losses.append(float(loss))
        followed = ref.three_steps(cfg, lambda n: jnp.asarray(start[n]),
                                   batches)
    np.testing.assert_allclose(losses, followed["loss"], rtol=5e-6)
    for name, norm in followed["change"].items():
        got = float(np.linalg.norm(np.asarray(params[name], np.float64)
                                   - start[name]))
        assert abs(got - norm) <= 2e-4 * max(norm, 1e-6), name


# --- (b) the shares add up ---------------------------------------------------
@pytest.mark.parametrize("ranks", [2, 4, 8])
def test_the_ranks_shares_add_up_to_the_uncut_layer(ranks):
    """Routed parts of every rank, plus attention and the shared expert
    counted once, equal the uncut reference's layer output."""
    E, per = 8, 8 // ranks
    whole = small_cfg(1, 0, experts=E, held=(0, E), top_k=3)
    model = TransformerParallel.from_config(one_chip(), whole)
    params = seeded_params(model, 11)
    for n in ("moe_wg", "moe_wu", "moe_wd"):   # a routed part of size
        params["l0_" + n] = 10.0 * params["l0_" + n]
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32), F32)
    w = {n: params["l0_" + n] for n in ref.layer_leaves(whole, "expert")}
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer(w, x, whole, "expert")
        y = ref.mla(w, x, whole, False)
        common = ref.ffn(w, y, whole, "expert", False, held=(0, 0))
        total = common
        for r in range(ranks):
            held = (r * per, (r + 1) * per)
            cfg = small_cfg(1, 0, experts=E, held=held, top_k=3)
            rank = TransformerParallel.from_config(one_chip(), cfg)
            mine = dict(params)
            for n in ("moe_wg", "moe_wu", "moe_wd"):
                mine["l0_" + n] = params["l0_" + n][held[0]:held[1]]
            out = jax.jit(lambda p, x, rank=rank: rank._layer(0, p, x)[0])(
                mine, x)
            total = total + (out - common)
    assert rel(total, uncut) < 1e-5
    assert rel(common, uncut) > 0.05      # the routed part is not nothing


# --- (c) flash attention, q/k wider than v/o ---------------------------------
def _dense_attention(q, k, v, scale):
    return fa._dense_with_lse(q, k, v, causal=True, scale=scale)


@pytest.mark.parametrize("widths", [(192, 128), (24, 16)])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_pass"])
def test_flash_with_unequal_widths_against_the_dense_formula(
        widths, fused, monkeypatch):
    D, Dv = widths
    if not fused:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_BUDGET", 0)
    obs.set_enabled(True)
    name = ("flash_attention.bwd_fused" if fused
            else "flash_attention.bwd_two_pass")
    before = (obs.metrics.get_value(name, 0),
              obs.metrics.get_value("flash_attention.dqk_ne_dv", 0))
    B, H, T = 1, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(D), 5)
    q, k = (jax.random.normal(ks[i], (B, H, T, D), F32) for i in (0, 1))
    v, do = (jax.random.normal(ks[i], (B, H, T, Dv), F32) for i in (2, 3))
    dlse = jax.random.normal(ks[4], (B, H, T), F32)
    scale = 1.37 ** 2 / math.sqrt(D)

    def run(fn):
        def total(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * do) + jnp.sum(lse * dlse), out
        return jax.value_and_grad(total, (0, 1, 2), has_aux=True)(q, k, v)

    (_, out), grads = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=scale, block_q=16, block_k=16,
        block_q_bwd=16, block_k_bwd=16, interpret=True, return_lse=True))
    (_, want_out), want = run(lambda q, k, v: _dense_attention(q, k, v,
                                                               scale))
    assert out.shape == (B, H, T, Dv)
    assert rel(out, want_out) < 2e-5
    for got, ref_grad, what in zip(grads, want, ("dq", "dk", "dv")):
        assert got.shape == ref_grad.shape
        assert rel(got, ref_grad) < 5e-5, what
    assert obs.metrics.get_value(name, 0) > before[0]
    assert obs.metrics.get_value("flash_attention.dqk_ne_dv", 0) > before[1]


def test_flash_refuses_q_and_k_of_different_widths():
    q = jnp.zeros((1, 1, 16, 8), F32)
    with pytest.raises(ValueError, match="q and k widths differ"):
        fa.flash_attention(q, jnp.zeros((1, 1, 16, 4), F32), q, interpret=True)


# --- (d, e) the grouped matmul and the dispatch ------------------------------
def _routing(case, N, k, E):
    if case == "uneven":       # counts that are no multiple of the tile
        logits = jax.random.normal(jax.random.PRNGKey(1), (N, E), F32)
        return moe.route(logits, jnp.zeros(E), k, 2.5)[0]
    if case == "empty_expert":  # expert 2 of the held range sees no row
        idx = moe.route(jax.random.normal(jax.random.PRNGKey(2), (N, E), F32)
                        .at[:, 4].set(-1e9), jnp.zeros(E), k, 2.5)[0]
        return idx
    # one held expert takes every row; the other pair falls outside
    return jnp.stack([jnp.full(N, 3), jnp.full(N, 7)], 1).astype(jnp.int32)


@pytest.mark.parametrize("case,layout", [
    ("uneven", "worst_case"), ("empty_expert", "worst_case"),
    ("one_takes_all", "worst_case"),
    ("uneven", "compact"), ("empty_expert", "compact"),
    ("uneven", "the_one_that_fits"), ("empty_expert", "the_one_that_fits"),
    ("one_takes_all", "the_one_that_fits")])
@pytest.mark.parametrize("tm", [8, 16])
def test_grouped_matmul_against_a_per_expert_loop(case, layout, tm):
    """``worst_case``: 4 of 8 experts held, where the two budgets are one.
    The others hold 4 of 32: ``compact`` lays the rows out in the compact
    budget, ``the_one_that_fits`` lets the routing choose, and where one
    expert takes every row that is the worst-case layout: no pair is
    dropped in either."""
    N, k, held, d, f = 40, 2, (2, 6), 16, 24
    E = 8 if layout == "worst_case" else 32
    idx = _routing(case, N, k, E)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (N, d), F32)
    w = 0.1 * jax.random.normal(ks[1], (held[1] - held[0], d, f), F32)
    weight = jax.random.uniform(ks[2], (N, k), F32)
    g = jax.random.normal(ks[3], (N, f), F32)
    worst = moe.plan_dispatch(idx, held, tm)
    R = moe.row_budget(N, k, 4, tm)
    Rc = moe.compact_row_budget(N, k, 4, E, tm)
    assert (Rc == R) == (layout == "worst_case")
    compact = moe.plan_dispatch(idx, held, tm, Rc)
    fits = int(worst["n_live"][0]) * tm <= Rc
    assert fits == (case != "one_takes_all" or layout == "worst_case")
    counts = np.asarray(worst["counts"])
    np.testing.assert_array_equal(counts, np.asarray(compact["counts"]))
    assert counts.sum() == int(np.sum((np.asarray(idx) >= held[0])
                                      & (np.asarray(idx) < held[1])))
    if case == "uneven":
        assert any(c % tm for c in counts)
    if case == "empty_expert":
        assert counts[2] == 0
    if case == "one_takes_all":
        assert counts.tolist() == [0, N, 0, 0]
    if fits:    # the compact layout is the worst-case one cut short
        for name in ("pair_of_row", "tile_expert", "tile_first", "tile_last"):
            np.testing.assert_array_equal(
                np.asarray(compact[name]),
                np.asarray(worst[name])[:compact[name].shape[0]])
        np.testing.assert_array_equal(
            np.asarray(compact["row_of_pair"]),
            np.minimum(np.asarray(worst["row_of_pair"]), Rc))

    def block(plan, x, w, weight):
        rows = moe.dispatch(x, plan)
        out = moe.combine(moe.gmm(rows, w, plan, block_rows=tm), weight, plan)
        # a last row that says which layout this was
        return jnp.concatenate(
            [out, jnp.full((1, f), plan["pair_of_row"].shape[0], F32)])

    def program(x, w, weight):
        if layout == "the_one_that_fits":
            out = moe.in_the_layout_that_fits(block, compact, worst,
                                              x, w, weight)
        else:
            out = block(compact if layout == "compact" else worst,
                        x, w, weight)
        return out[:N], out[N, 0]

    def loop(x, w, weight):
        out = jnp.zeros((N, f), F32)
        for e in range(*held):
            w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=1)
            out = out + w_e[:, None] * (x @ w[e - held[0]])
        return out

    with jax.default_matmul_precision("highest"):
        (got, rows_run), got_grads = jax.value_and_grad(
            lambda *a: (lambda out, rows: (jnp.sum(out * g), rows))(
                *program(*a)), (0, 1, 2), has_aux=True)(x, w, weight)
        want = jax.value_and_grad(
            lambda *a: jnp.sum(loop(*a) * g), (0, 1, 2))(x, w, weight)
    assert int(rows_run) == (Rc if fits else R)
    assert abs(float(got) - float(want[0])) < 1e-4 * abs(float(want[0]))
    for a, b, what in zip(got_grads, want[1], ("dx", "dw", "dweight")):
        assert rel(a, b) < 1e-5, what


def _eqns(jaxpr, into=("cond",)):
    """Every equation of a jaxpr and of what it calls; the Pallas kernels'
    bodies left out, and the branches of a ``cond`` unless ``into`` has
    it."""
    from jax.extend import core

    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" or (
                eqn.primitive.name == "cond" and "cond" not in into):
            continue
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda v: isinstance(v, (core.Jaxpr,
                                                 core.ClosedJaxpr))):
            if isinstance(sub, core.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, core.Jaxpr):
                yield from _eqns(sub, into)


def _conds_outside_kernels(jaxpr):
    """``cond`` equations of a jaxpr and of what it calls, the Pallas
    kernels' bodies (whose ``pl.when`` is one) left out."""
    return sum(eqn.primitive.name == "cond" for eqn in _eqns(jaxpr))


def test_two_budgets_trace_a_conditional_and_one_budget_none():
    """Where every expert is held the two budgets are equal and the routed
    block is traced once, in that layout; where they differ the forward is
    one conditional and the backward another, on the same predicate."""
    N, k, tm = 40, 2, 8
    x = jnp.ones((N, 16), F32)
    weight = jnp.ones((N, k), F32)

    def traced(E, held):
        idx = _routing("uneven", N, k, E)
        n_held = held[1] - held[0]
        w = jnp.ones((n_held, 16, 8), F32)
        budgets = (moe.compact_row_budget(N, k, n_held, E, tm),
                   moe.row_budget(N, k, n_held, tm))

        def loss(x, w, weight):
            plans = [moe.plan_dispatch(idx, held, tm, R) for R in budgets]
            return jnp.sum(moe.in_the_layout_that_fits(
                lambda plan, x, w, weight: moe.combine(
                    moe.gmm(moe.dispatch(x, plan), w, plan, block_rows=tm),
                    weight, plan), *plans, x, w, weight))

        return budgets, _conds_outside_kernels(jax.make_jaxpr(
            jax.grad(loss, (0, 1, 2)))(x, w, weight).jaxpr)

    (compact, worst), conds = traced(8, (0, 8))
    assert compact == worst == N * k + 8 * tm and conds == 0
    (compact, worst), conds = traced(32, (2, 6))
    assert compact == 2 * 10 + 4 * tm < worst == N * k + 4 * tm
    assert conds == 2
    assert moe.compact_row_budget(8192, 8, 16, 128, 256) == 20480
    assert moe.row_budget(8192, 8, 16, 256) == 69632


def test_no_pair_is_dropped_when_every_choice_is_held():
    """The worst case the routing can produce: every token's top-k inside
    the held range. The row budget covers it; nothing is dropped."""
    N, k, E, tm = 24, 4, 4, 8
    idx = jnp.stack([jnp.roll(jnp.arange(E), i)[:k] for i in range(N)]
                    ).astype(jnp.int32)
    plan = moe.plan_dispatch(idx, (0, E), tm)
    R = moe.row_budget(N, k, E, tm)
    assert plan["pair_of_row"].shape == (R,) and R == N * k + E * tm
    assert int(plan["counts"].sum()) == N * k
    rows = np.asarray(plan["row_of_pair"]).reshape(-1)
    assert len(set(rows.tolist())) == N * k and rows.max() < R
    assert int(plan["n_live"][0]) * tm <= R
    back = np.asarray(plan["pair_of_row"])[rows]
    assert back.tolist() == list(range(N * k))
    # and the layer's output is the whole routed sum
    x = jax.random.normal(jax.random.PRNGKey(0), (N, 16), F32)
    w = jax.random.normal(jax.random.PRNGKey(1), (E, 16, 8), F32)
    weight = jnp.ones((N, k), F32)
    out = moe.combine(moe.gmm(moe.dispatch(x, plan), w, plan, block_rows=tm),
                      weight, plan)
    want = sum(jnp.where((idx == e).any(1)[:, None], x @ w[e], 0.0)
               for e in range(E))
    assert rel(out, want) < 1e-5


# --- the rows -> tokens sum as a kernel ---------------------------------------
BF16 = jnp.bfloat16
#: (d, experts held, experts): the two routed cells' shape classes, small
SUM_SHAPES = [(256, 4, 32), (512, 8, 64)]
KERNEL_COUNTER = "moe.sum_to_tokens_kernel"


def _sum_case(d, held, E, N=64, k=8, tile=8):
    """A compact layout of fewer rows than pairs, with token 0 holding no
    pair and token 1 and the last token one in every held expert (all k
    where k experts are held); bf16 rows whose float32 sums are exact in
    any order, the dead and padding rows among them."""
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    idx = jax.lax.top_k(jax.random.uniform(ks[0], (N, E)), k)[1]
    most = np.r_[:min(k, held), E - k + min(k, held):E]
    idx = np.array(idx, np.int32)
    idx[0], idx[1], idx[N - 1] = np.r_[held:held + k], most, most
    idx = jnp.asarray(idx)
    R = moe.compact_row_budget(N, k, held, E, tile)
    plan = moe.plan_dispatch(idx, (0, held), tile, R)
    assert N * k > R >= int(plan["n_live"][0]) * tile
    pairs_of = np.sum(np.asarray(plan["row_of_pair"]) < R, axis=1)
    assert pairs_of[0] == 0 and pairs_of[1] == pairs_of[-1] == min(k, held)
    rows = (jax.random.randint(ks[1], (R, d), -128, 129) / 64).astype(BF16)
    weight = jax.random.uniform(ks[2], (N, k), F32, 0.1, 1.0)
    return idx, plan, rows, weight


def _float32_sum(rows, weight, plan):
    """The formula, float32 at ``highest``, not rounded."""
    N, k = weight.shape
    picked = moe._take_rows(rows.astype(F32),
                            plan["row_of_pair"].reshape(N * k))
    return jnp.einsum("nkd,nk->nd", picked.reshape(N, k, -1), weight,
                      precision="highest")


def _kernel_sum(rows, weight, plan, block=(16, 32)):
    return moe._sum_to_tokens(rows, weight, plan["row_of_pair"],
                              plan["pair_of_row"], block=block)


@pytest.mark.parametrize("d,held,E", SUM_SHAPES)
@pytest.mark.parametrize("case", ["weighted", "one_tile_pair", "ones",
                                  "nan_in_dead_rows"])
def test_the_rows_to_tokens_kernel_against_the_float32_formula(
        case, d, held, E, monkeypatch):
    # the gather before the kernel in several chunks, dead ones among them
    monkeypatch.setattr(moe, "_SUM_GATHER_ROWS", 32)
    _, plan, rows, weight = _sum_case(d, held, E)
    if case in ("weighted", "one_tile_pair"):
        # the file's bounds hold these sizes in one tile pair
        got = _kernel_sum(rows, weight, plan,
                          None if case == "one_tile_pair" else (16, 32))
        want = _float32_sum(rows, weight, plan)
        assert got.dtype == BF16
        # one rounding to bf16, half an ulp or 2**-9 of the value at most,
        # and the weight's two bf16 parts: 2**-17 of each of eight terms
        gap = jnp.abs(got.astype(F32) - want)
        assert bool(jnp.all(gap <= 2.0 ** -8 * jnp.abs(want) + 2.0 ** -13))
        assert not np.asarray(got[0]).any()       # no held pair: zeros
        assert np.asarray(got[1]).any() and np.asarray(got[-1]).any()
    elif case == "ones":
        # weights of one (dispatch's backward): the plain sum, to the bit
        got = _kernel_sum(rows, None, plan)
        want = _float32_sum(rows, jnp.ones_like(weight), plan).astype(BF16)
        np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                      np.asarray(want.astype(F32)))
    else:
        # what the grouped matmul leaves past the live rows is undefined
        dead = (plan["pair_of_row"] == weight.size)[:, None]
        assert 0 < int(dead.sum()) < dead.size
        for w in (weight, None):
            got = _kernel_sum(jnp.where(dead, jnp.nan, rows), w, plan)
            assert bool(jnp.all(jnp.isfinite(got.astype(F32))))
            np.testing.assert_array_equal(
                np.asarray(got.astype(F32)),
                np.asarray(_kernel_sum(rows, w, plan).astype(F32)))


def _moved_rows(plan, x, weight, g):
    """dispatch -> something elementwise -> combine, as a loss."""
    rows = moe.dispatch(x, plan)
    out = moe.combine(rows * rows.dtype.type(1.5), weight, plan)
    return jnp.sum(out.astype(F32) * g)


@pytest.mark.parametrize("d,held,E", SUM_SHAPES)
def test_dispatch_and_combine_differentiate_alike_through_the_kernel(
        d, held, E, monkeypatch):
    """The compact layout (the kernel, in several tile pairs) against one
    with room for every pair (the pairs' gather in XLA: the sum as it
    was), forward and through ``jax.grad``: within one bf16 ulp."""
    monkeypatch.setattr(moe, "_SUM_BLOCK", (16, 32))
    monkeypatch.setattr(moe, "_SUM_GATHER_ROWS", 32)
    idx, compact, _, weight = _sum_case(d, held, E)
    N, k = weight.shape
    worst = moe.plan_dispatch(idx, (0, held), 8, N * k + held * 8)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (N, d), BF16)
    g = jax.random.normal(ks[1], (N, d), F32)
    obs.set_enabled(True)
    before = obs.metrics.get_value(KERNEL_COUNTER, 0)
    want = jax.value_and_grad(_moved_rows, (1, 2))(worst, x, weight, g)
    assert obs.metrics.get_value(KERNEL_COUNTER, 0) == before
    got = jax.value_and_grad(_moved_rows, (1, 2))(compact, x, weight, g)
    # its combine forward and its dispatch backward
    assert obs.metrics.get_value(KERNEL_COUNTER, 0) == before + 2
    assert abs(float(got[0]) - float(want[0])) <= 2e-3 * abs(float(want[0]))
    for a, b, what in zip(got[1], want[1], ("dx", "dweight")):
        a, b = a.astype(F32), b.astype(F32)
        ulp = 2.0 ** -7 if what == "dx" else 1e-5
        assert bool(jnp.all(jnp.abs(a - b) <= ulp * jnp.abs(b))), what


@pytest.mark.parametrize("layout,calls", [
    ("compact", 2), ("room_for_every_pair", 0), ("the_one_that_fits", 3)])
def test_the_kernel_counter_counts_the_traced_calls(layout, calls):
    """One a traced call that takes the kernel: a routed block's forward
    and backward trace its ``combine`` forward and its ``dispatch``
    backward; under ``in_the_layout_that_fits`` the compact branch's
    backward traces its own forward besides (whose sum nothing reads),
    and the worst-case branch, with room for every pair, none."""
    d, held, E = SUM_SHAPES[1]
    idx, compact, _, weight = _sum_case(d, held, E)
    N, k = weight.shape
    worst = moe.plan_dispatch(idx, (0, held), 8)
    assert worst["pair_of_row"].shape[0] >= N * k
    x, g = jnp.ones((N, d), BF16), jnp.ones((N, d), F32)

    def loss(x, weight):
        if layout == "the_one_that_fits":
            return moe.in_the_layout_that_fits(
                lambda plan, x, weight: _moved_rows(plan, x, weight, g),
                compact, worst, x, weight)
        return _moved_rows(compact if layout == "compact" else worst,
                           x, weight, g)

    obs.set_enabled(True)
    before = obs.metrics.get_value(KERNEL_COUNTER, 0)
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, weight).jaxpr
    assert obs.metrics.get_value(KERNEL_COUNTER, 0) - before == calls
    kernels = [eqn.params["name"] for eqn in _eqns(jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert kernels == ["moe_sum_to_tokens"] * calls


@pytest.mark.parametrize("experts", [8, 32])
def test_routing_stats_and_the_trace_time_counters(experts):
    cfg = small_cfg(3, 1, experts=experts)
    model = TransformerParallel.from_config(one_chip(), cfg)
    params = seeded_params(model, 5)
    tok, _ = batch(1)
    obs.set_enabled(True)
    names = ("moe.experts_held", "moe.row_budget", "moe.compact_row_budget")
    before = {n: obs.metrics.get_value(n, 0) for n in names}
    stats = model.routing_stats(params, tok)
    assert [s["layer"] for s in stats] == [1, 2]
    budget = moe.row_budget(tok.size, 2, 4, moe.GMM_BLOCK_ROWS)
    compact = moe.compact_row_budget(tok.size, 2, 4, experts,
                                     moe.GMM_BLOCK_ROWS)
    assert (compact == budget) == (experts == 8)
    for s in stats:
        assert s["row_budget"] == budget and len(s["load"]) == 4
        assert s["pairs_held"] == sum(s["load"]) <= tok.size * 2
        assert max(s["load"]) <= budget
        assert s["compact_budget"] == compact
        # 64 tokens: every held expert's group is its one tile
        assert s["live_rows"] == 4 * moe.GMM_BLOCK_ROWS <= compact
        assert s["fits"] is True
    assert sum(s["pairs_held"] for s in stats) > 0
    moved = {n: obs.metrics.get_value(n, 0) - before[n] for n in names}
    assert moved == {"moe.experts_held": 2 * 4, "moe.row_budget": 2 * budget,
                     "moe.compact_row_budget": 2 * compact}


# --- (f) closed forms --------------------------------------------------------
SARVAM_ROPE = {"theta": 10000, "beta_fast": 32, "beta_slow": 1, "factor": 40,
               "mscale": 1, "mscale_all_dim": 1,
               "original_max_position_embeddings": 4096}


def test_yarn_frequencies_and_the_softmax_scale():
    inv = lm_layers.yarn_inv_freq(64, SARVAM_ROPE)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    # correction range: 64 ln(4096 / (2 pi n)) / (2 ln 10000) at n = 32, 1
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-12)
    np.testing.assert_allclose(inv[high:], plain[high:] / 40, rtol=1e-12)
    mid = 16
    ramp = (mid - low) / (high - low)
    np.testing.assert_allclose(
        inv[mid], plain[mid] / 40 * ramp + plain[mid] * (1 - ramp),
        rtol=1e-12)
    np.testing.assert_allclose(inv, ref.inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=10000, rope_scaling=SARVAM_ROPE)),
        rtol=1e-12)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3689) < 5e-5
    arch = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "rope": SARVAM_ROPE}
    assert lm_layers.mla_scale(arch) == pytest.approx(192 ** -0.5 * m * m,
                                                      rel=1e-12)
    plain_arch = dict(arch, rope={"theta": 10000})
    assert lm_layers.mla_scale(plain_arch) == pytest.approx(192 ** -0.5)
    np.testing.assert_allclose(
        lm_layers.yarn_inv_freq(64, {"theta": 10000}), plain)


@pytest.mark.parametrize("position", [0, 1, 4095, 8191])
def test_rope_rotates_each_pair_by_its_angle(position):
    T, dim = 8192, 64
    cos, sin = lm_layers.rope_tables(T, dim, SARVAM_ROPE)
    x = jax.random.normal(jax.random.PRNGKey(position), (T, dim), F32)
    got = np.asarray(lm_layers.apply_rope(x, cos, sin))[position]
    inv = lm_layers.yarn_inv_freq(dim, SARVAM_ROPE)
    row = np.asarray(x[position], np.float64)
    z = (row[:32] + 1j * row[32:]) * np.exp(1j * position * inv)
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag]),
                               atol=2e-5)
    if position == 0:
        np.testing.assert_array_equal(got, np.asarray(x[0]))
    # a rotation: the norm of every pair stands
    np.testing.assert_allclose(got[:32] ** 2 + got[32:] ** 2,
                               row[:32] ** 2 + row[32:] ** 2, rtol=1e-4)


def test_the_expert_bias_changes_the_selection_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16), F32)
    idx0, w0 = moe.route(logits, jnp.zeros(16), 8, 2.5)
    bias = jnp.zeros(16).at[3].set(10.0).at[5].set(-10.0)
    idx1, w1 = moe.route(logits, bias, 8, 2.5)
    assert bool((idx1 == 3).any(1).all()) and not bool((idx1 == 5).any())
    assert not bool((idx0 == 3).any(1).all())
    score = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(score, idx1, 1)
    np.testing.assert_allclose(
        w1, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w1.sum(1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(1), 2.5, rtol=1e-6)
    assert float(jnp.abs(jax.grad(lambda b: moe.route(logits, b, 8, 2.5)[1]
                                  .sum())(bias)).max()) == 0.0


# --- (g) the first block, unchanged to the bit -------------------------------
def _first_block_loss(model, params, tokens, targets):
    """The block as it stood before layers had kinds, copied."""
    c = model.cfg
    B, T = tokens.shape
    d, H = c["d_model"], c["n_heads"]
    x = params["embed"][tokens]
    for li in range(c["n_layers"]):
        p = "l%d_" % li
        ln = _rms_norm(x)
        q, k, v = ((ln @ params[p + n]).reshape(B, T, H, d // H)
                   .transpose(0, 2, 1, 3) for n in ("wq", "wk", "wv"))
        att = _local_attention(q, k, v, model.mesh)
        x = x + att.transpose(0, 2, 1, 3).reshape(B, T, d) @ params[p + "wo"]
        ln = _rms_norm(x)
        gate = jax.nn.softmax(ln @ params[p + "gate"], axis=-1)
        hidden = jax.nn.gelu(jnp.einsum("btd,edf->btef", ln,
                                        params[p + "w1"]))
        out = jnp.einsum("btef,efd->bted", hidden, params[p + "w2"])
        x = x + jnp.einsum("bted,bte->btd", out, gate)
    logits = _rms_norm(x) @ params["out_w"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1)[..., 0])


@pytest.mark.parametrize("sizes", [
    dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, n_experts=2),
    dict(vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32, n_experts=2),
    dict(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
         n_experts=1)], ids=["default", "tiny", "rehearsal"])
def test_the_first_block_is_unchanged_to_the_bit(sizes):
    model = TransformerParallel(one_chip(), **sizes)
    assert model.classic and "final_norm" not in model.param_table()
    assert list(model.param_table())[:3] == ["embed", "out_w", "l0_wq"]
    params = model.init(4)
    tok, tgt = batch(2, T=16, vocab=sizes["vocab"])
    got = jax.jit(jax.value_and_grad(model.loss_fn))(params, tok, tgt)
    want = jax.jit(jax.value_and_grad(
        lambda p, a, b: _first_block_loss(model, p, a, b)))(params, tok, tgt)
    assert float(got[0]) == float(want[0])
    for name in want[1]:
        np.testing.assert_array_equal(np.asarray(got[1][name]),
                                      np.asarray(want[1][name]))


def test_init_makes_every_leaf_on_the_device_from_the_key():
    model = TransformerParallel.from_config(one_chip(), small_cfg())
    a, b, c = model.init(1), model.init(1), model.init(2)
    table = model.param_table()
    assert set(a) == set(table) == set(model.param_shardings())
    for name, (shape, init) in table.items():
        assert a[name].shape == tuple(shape) and a[name].dtype == np.float32
        np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
        if isinstance(init, tuple):
            assert float(jnp.abs(a[name] - c[name]).max()) > 0
            assert 0.01 < float(jnp.std(a[name])) < 0.04
        else:
            assert float(jnp.abs(a[name] - init).max()) == 0.0


# --- (h) what this model does not serve yet ----------------------------------
@pytest.mark.parametrize("forward", ["prefill_forward", "decode_forward",
                                     "verify_forward"])
def test_serving_forwards_refuse_a_latent_layer(forward):
    model = TransformerParallel.from_config(one_chip(), small_cfg())
    tokens = jnp.zeros((1, 4), jnp.int32)
    args = (None, tokens) if forward == "prefill_forward" else (
        None, tokens, lambda *a: None)
    with pytest.raises(NotImplementedError, match="latent"):
        getattr(model, forward)(*args)


def test_new_kinds_refuse_a_mesh_that_is_not_data_parallel():
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="dp meshes"):
        TransformerParallel.from_config(mesh, small_cfg())
    with pytest.raises(ValueError, match="unknown layer kinds"):
        TransformerParallel(one_chip(), layers=[("mla", "dense")])


@pytest.mark.parametrize("experts", [8, 32],
                         ids=["one_layout", "two_layouts"])
def test_a_model_of_new_kinds_trains_data_parallel_on_two_devices(experts):
    """On a dp mesh every device routes its own rows under ``shard_map``
    (and, of two layouts, takes the one its own routing fits): the loss,
    every leaf after the step (the replicated weights' gradients are
    summed over the devices) and the routing counts are the one-device
    model's."""
    cfg = small_cfg(2, 1, experts=experts)
    two = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tok, tgt = batch(7)
    runs = []
    for mesh in (one_chip(), two):
        model = TransformerParallel.from_config(mesh, cfg)
        params = seeded_params(model, 9)
        stats = model.routing_stats(params, model.shard_batch(tok, tgt)[0])
        step = model.step_fn(lr=0.5)
        with jax.default_matmul_precision("highest"):
            params, loss = step(params, *model.shard_batch(tok, tgt))
        runs.append((float(loss), jax.device_get(params), stats))
    (loss1, leaves1, stats1), (loss2, leaves2, stats2) = runs
    assert loss1 == pytest.approx(loss2, rel=1e-5)
    for name in leaves1:
        assert rel(leaves2[name], leaves1[name]) < 1e-5, name
    assert [s["load"] for s in stats1] == [s["load"] for s in stats2]
    assert stats2[0]["row_budget"] == moe.row_budget(
        tok.size // 2, 2, 4, moe.GMM_BLOCK_ROWS)
    assert stats2[0]["compact_budget"] == moe.compact_row_budget(
        tok.size // 2, 2, 4, experts, moe.GMM_BLOCK_ROWS)
    assert all(s["fits"] for s in stats1 + stats2)


# --- (i) what a recomputed layer keeps ---------------------------------------
#: one layer of each kind a model is recomputed by: (dense layers, experts)
RECOMPUTED_LAYERS = {"dense_layer": (1, 8), "routed_one_layout": (0, 8),
                     "routed_two_layouts": (0, 16)}


def _one_layer(kind, remat):
    dense, experts = RECOMPUTED_LAYERS[kind]
    cfg = small_cfg(1, dense, experts=experts)
    return cfg, TransformerParallel.from_config(one_chip(), cfg, remat=remat)


def _grad_jaxpr(model, params, flash_alone=False, monkeypatch=None):
    """The jaxpr of the model's loss gradient; ``flash_alone``: with the
    policy of a layer that keeps the flash kernel's two names alone."""
    if flash_alone:
        only = jax.checkpoint_policies.save_only_these_names
        monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                            lambda *names: only("flash_out", "flash_lse"))
    tok, tgt = batch(0)
    return jax.make_jaxpr(jax.grad(model.loss_fn))(params, tok, tgt).jaxpr


def _matmuls(jaxpr, lhs, rhs, into=("cond",)):
    """``dot_general``s of these two operand shapes, either way round."""
    return sum(eqn.primitive.name == "dot_general"
               and sorted(tuple(v.aval.shape) for v in eqn.invars)
               == sorted([tuple(lhs), tuple(rhs)])
               for eqn in _eqns(jaxpr, into))


@pytest.mark.parametrize("kind", list(RECOMPUTED_LAYERS))
def test_a_recomputed_layer_gives_the_gradients_and_losses_of_one_that_is_not(
        kind):
    runs = []
    for remat in (False, True):
        cfg, model = _one_layer(kind, remat)
        params = seeded_params(model, 3)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
                params, *batch(0))
            step = model.step_fn(lr=cfg["optimizer"]["learning_rate"])
            losses = []
            for i in range(3):
                params, after = step(params, *model.shard_batch(*batch(i)))
                losses.append(float(after))
        runs.append((float(loss), grads, losses))
    (loss, grads, losses), (loss_r, grads_r, losses_r) = runs
    assert abs(loss_r - loss) < 2e-6 * loss
    for name in grads:
        assert rel(grads_r[name], grads[name]) < 2e-4, name
    np.testing.assert_allclose(losses_r, losses, rtol=5e-6)


@pytest.mark.parametrize("kind", list(RECOMPUTED_LAYERS))
def test_a_recomputed_layer_holds_its_inputs_and_what_the_constant_names(
        kind, monkeypatch):
    """The backward pass's recomputation of the layer takes in: the
    layer's leaves and input, the output's cotangent, the rotary tables
    (constants) and the values named by ``kept`` — every one of them, and
    nothing else the size of an activation. (Off the TPU attention is the
    dense formula: ``flash_out`` / ``flash_lse`` name nothing here.)"""
    from collections import Counter

    cfg, model = _one_layer(kind, True)
    named = []

    def kept(value, name, kept=lm_layers.kept):
        named.append((name, tuple(value.shape), str(value.dtype)))
        return kept(value, name)

    monkeypatch.setattr(lm_layers, "kept", kept)
    jaxpr = _grad_jaxpr(model, seeded_params(model, 3))
    assert {n for n, _, _ in named} <= set(
        lm_layers.KEPT_BY_A_RECOMPUTED_LAYER)
    assert {n for n, _, _ in named} == (
        {"mla_kva", "mla_q", "attn_residual"}
        | ({"ffn_gate", "ffn_up"} if kind == "dense_layer" else
           {"router_logits", "route_idx", "route_weight", "moe_plan",
            "shared_gate", "shared_up"}))
    recomputed = [e for e in _eqns(jaxpr) if e.primitive.name == "remat2"]
    assert len(recomputed) == 1
    taken = Counter((tuple(v.aval.shape), str(v.aval.dtype))
                    for v in recomputed[0].invars)
    floats = Counter({k: n for k, n in taken.items() if k[1] == "float32"})
    for _, shape, dtype in named:
        if dtype == "float32":
            assert floats[(shape, dtype)] > 0, (shape, "is made again")
            floats[(shape, dtype)] -= 1
    B, T = batch(0)[0].shape
    leaves = Counter((tuple(s), "float32") for n, (s, _)
                     in model.param_table().items() if n.startswith("l0_"))
    allowed = leaves + Counter({
        ((B, T, cfg["hidden_size"]), "float32"): 2,            # x, d_out
        ((T, cfg["qk_rope_head_dim"] // 2), "float32"): 2})    # cos, sin
    assert not (+floats - allowed), +floats - allowed
    ints = {shape for _, shape, dtype in named if dtype == "int32"}
    assert {k[0] for k in taken if k[1] == "int32"} <= ints


def test_a_recomputed_dense_layer_projects_q_and_out_once(monkeypatch):
    """With q (rotated) and the residual after attention kept, the
    backward pass holds no second ``d -> H dq`` projection and no second
    out-projection; a layer that keeps the flash names alone runs both
    again, and so does its kv down-projection."""
    cfg, model = _one_layer("dense_layer", True)
    params = seeded_params(model, 3)
    B, T = batch(0)[0].shape
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    shapes = {"wq": ((B, T, d), (d, H, dq)),
              "wo": ((B, H, T, cfg["v_head_dim"]),
                     (H, cfg["v_head_dim"], d)),
              "wkva": ((B, T, d),
                       (d, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])),
              "wg": ((B, T, d), (d, cfg["intermediate_size"]))}
    plain = _grad_jaxpr(_one_layer("dense_layer", False)[1], params)
    kept = _grad_jaxpr(model, params)
    bare = _grad_jaxpr(model, params, flash_alone=True,
                       monkeypatch=monkeypatch)
    for name, (lhs, rhs) in shapes.items():
        again = 2 if name == "wg" else 1   # wu is alike, and so is the head
        once = _matmuls(plain, lhs, rhs)
        assert once == (3 if name == "wg" else 1)
        assert _matmuls(kept, lhs, rhs) == once, name
        assert _matmuls(bare, lhs, rhs) == once + again, name


@pytest.mark.parametrize("kind", ["routed_one_layout", "routed_two_layouts"])
def test_a_recomputed_routed_layer_routes_and_plans_once(kind, monkeypatch):
    """The backward pass of a recomputed routed layer holds no second
    router matmul, no second top-k and builds no plan again: no sort of the
    N k pair keys outside the ``cond`` branches (inside them the compact
    layout sorts its rows, as before)."""
    cfg, model = _one_layer(kind, True)
    params = seeded_params(model, 3)
    tok, _ = batch(0)
    N, k = tok.size, cfg["num_experts_per_tok"]
    router = ((N, cfg["hidden_size"]),
              (cfg["hidden_size"], cfg["published"]["num_experts"]))

    def counts(jaxpr):
        outside = list(_eqns(jaxpr, into=()))
        return (_matmuls(jaxpr, *router, into=()),
                sum(e.primitive.name == "top_k" for e in outside),
                sum(e.primitive.name == "sort"
                    and e.invars[0].aval.shape == (N * k,) for e in outside))

    plain = counts(_grad_jaxpr(_one_layer(kind, False)[1], params))
    assert plain == (1, 1, 2)       # one routing, a plan at either budget
    kept = counts(_grad_jaxpr(model, params))
    # (of two plans alike, a recomputed forward traces the one it uses)
    assert kept == (1, 1, 2 if kind == "routed_two_layouts" else 1)
    bare = counts(_grad_jaxpr(model, params, flash_alone=True,
                              monkeypatch=monkeypatch))
    assert bare == (2, 2, 2 * kept[2])


@pytest.mark.parametrize("remat", [True, False],
                         ids=["recomputed", "not_recomputed"])
def test_kept_bytes_are_the_bytes_of_what_the_shapes_say(remat):
    """``remat.kept_bytes``: once a traced recomputed layer, the bytes of
    every value it keeps by name; nothing where no layer is recomputed."""
    cfg = small_cfg(3, 1, experts=32)
    model = TransformerParallel.from_config(one_chip(), cfg, remat=remat)
    tok, tgt = batch(0)
    B, T = tok.shape
    N, k, d = B * T, cfg["num_experts_per_tok"], cfg["hidden_size"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    held, tile = cfg["num_experts"], moe.GMM_BLOCK_ROWS
    attention = N * (r + dr) + N * H * (dn + dr) + N * d   # kva, q, residual
    plans = sum(N * k + rows + 3 * (rows // tile) + 1 + held for rows in (
        moe.row_budget(N, k, held, tile),
        moe.compact_row_budget(N, k, held, 32, tile)))
    routed = (N * 32 + 2 * N * k + plans               # logits, idx, weight
              + 2 * N * cfg["moe_intermediate_size"])   # the shared expert
    want = 4 * (3 * attention + 2 * N * cfg["intermediate_size"] + 2 * routed)
    obs.set_enabled(True)
    before = obs.metrics.get_value("remat.kept_bytes", 0)
    jax.make_jaxpr(jax.grad(model.loss_fn))(seeded_params(model, 3), tok, tgt)
    moved = obs.metrics.get_value("remat.kept_bytes", 0) - before
    print("remat.kept_bytes", moved, "of a (3 layer, %d token) step" % N)
    assert moved == (want if remat else 0)
    with pytest.raises(ValueError, match="not kept"):
        lm_layers.kept(jnp.zeros(3), "k_and_v")

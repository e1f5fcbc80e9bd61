"""A model built from the ``laguna`` family's published keys — grouped-query
attention whose heads, window and rotary tables are the layer's own, a gate
a head, routed experts without an expert bias and a shared expert of its own
width — against the plain reference ``perfbench/reference/gqa_moe.py``
(float32, seeded, small widths): loss and every leaf's gradient with and
without recomputation, the shares of an expert-parallel deployment adding up
to the uncut layer, rotary closed forms, the selection without a bias, the
scopes and counters, and what it does not serve."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu import observability as obs  # noqa: E402
from mxnet_tpu.parallel import lm_layers, make_mesh, moe  # noqa: E402
from mxnet_tpu.parallel.transformer import TransformerParallel  # noqa: E402
from perfbench.reference import gqa_moe as ref  # noqa: E402

F32 = jnp.float32
FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
SLIDING = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}
PATTERN = ["full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention"]


def small_cfg(layers=3, experts=8, held=(2, 6), top_k=2, window=8,
              dense_first=True):
    types = [PATTERN[i % 4] for i in range(layers)]
    return dict(
        model_type="laguna", hidden_size=32, head_dim=8,
        num_key_value_heads=2, num_attention_heads=4,
        num_attention_heads_per_layer=[4 if t == "full_attention" else 6
                                       for t in types],   # groups of 2, 3
        layer_types=types,
        mlp_layer_types=["dense" if dense_first and i == 0 else "sparse"
                         for i in range(layers)],
        intermediate_size=64, moe_intermediate_size=24,
        shared_expert_intermediate_size=40, sliding_window=window,
        num_experts=held[1] - held[0], num_experts_per_tok=top_k,
        moe_routed_scaling_factor=2.5, gating=True,
        num_hidden_layers=layers, vocab_size=64, rms_norm_eps=1e-6,
        rope_parameters={"full_attention": dict(FULL),
                         "sliding_attention": dict(SLIDING),
                         "original_max_position_embeddings": 16},
        published={"num_experts": experts},
        deployment={"experts_held": list(held)},
        optimizer={"learning_rate": 0.5})


def one_chip():
    return make_mesh({"dp": 1}, devices=jax.devices()[:1])


def seeded_params(model, seed):
    """``model.init`` with the norm weights moved off 1 and the embedding,
    the router and the gate spread out, so that every leaf's gradient, the
    routing and the gate say something."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, leaf in model.init(seed).items():
        if leaf.ndim == 1:
            leaf = leaf + 0.1 * jnp.asarray(rs.randn(*leaf.shape), F32)
        if name == "embed" or name.endswith(("router", "wgate")):
            leaf = leaf * 50.0
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
@pytest.mark.parametrize("layers,experts,remat", [
    (1, 8, False), (2, 8, False), (5, 8, False), (5, 8, True),
    (5, 32, False), (5, 32, True)],
    ids=["dense_full_layer", "sliding_expert_layer", "a_period",
         "a_period_recomputed", "a_period_two_layouts",
         "a_period_two_layouts_recomputed"])
def test_program_matches_the_reference(layers, experts, remat):
    cfg = small_cfg(layers, experts=experts)
    model = TransformerParallel.from_config(one_chip(), cfg, remat=remat)
    assert ({n: tuple(s) for n, (s, _) in model.param_table().items()}
            == {n: tuple(s) for n, (s, _) in ref.param_table(cfg).items()})
    assert not [n for n in model.param_table() if "router_bias" in n]
    params = seeded_params(model, 3)
    start = {k: np.asarray(v) for k, v in params.items()}
    tok, tgt = batch(0)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
            params, tok, tgt)
        want_loss, want = ref.loss_and_grads(
            cfg, {k: jnp.asarray(v) for k, v in start.items()}, tok, tgt)
        assert abs(float(loss) - float(want_loss)) < 2e-6 * float(want_loss)
        assert set(grads) == set(want)
        for name in want:
            assert rel(grads[name], want[name]) < 2e-4, name
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


@pytest.mark.parametrize("what", ["full_mask_in_a_sliding_layer",
                                  "no_gate", "kv_head_by_remainder"])
def test_the_reference_tells_the_planted_faults(what, monkeypatch):
    """What the chip run plants in a scratch copy of the reference, here at
    a small size: each moves some leaf's gradient by far more than the
    program's distance from the sound reference (2e-4)."""
    cfg = small_cfg(5)
    model = TransformerParallel.from_config(one_chip(), cfg)
    params = seeded_params(model, 3)
    tok, tgt = batch(0)
    p = {k: jnp.asarray(v) for k, v in params.items()}
    _, sound = ref.loss_and_grads(cfg, p, tok, tgt)
    if what == "full_mask_in_a_sliding_layer":
        broken = dict(cfg, sliding_window=10 ** 6)
    elif what == "no_gate":
        broken = dict(cfg, gating=False)
    else:
        broken = cfg
        repeat = jnp.repeat
        monkeypatch.setattr(ref.jnp, "repeat", lambda a, g, axis: jnp.tile(
            a, (1, g, 1, 1)) if axis == 1 and a.ndim == 4 else repeat(
                a, g, axis=axis))
    _, faulty = ref.loss_and_grads(broken, p, tok, tgt)
    assert max(rel(faulty[n], sound[n]) for n in sound) > 0.05


# --- (b) the shares add up ---------------------------------------------------
@pytest.mark.parametrize("ranks", [2, 4, 8])
def test_the_ranks_shares_add_up_to_the_uncut_layer(ranks):
    """Routed parts of every rank, plus attention and the shared expert
    counted once, equal the uncut reference's layer output (a sliding
    layer: the window, groups of 3, no expert bias, a shared expert wider
    than a routed one)."""
    E, per = 8, 8 // ranks
    whole = small_cfg(2, experts=E, held=(0, E), top_k=3)
    kind = ref.layer_kinds(whole)[1]
    assert kind == ("sparse", "sliding_attention", 6)
    model = TransformerParallel.from_config(one_chip(), whole)
    params = seeded_params(model, 11)
    for n in ("moe_wg", "moe_wu", "moe_wd"):   # a routed part of size
        params["l1_" + n] = 10.0 * params["l1_" + n]
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32), F32)
    w = {n: params["l1_" + n] for n in ref.layer_leaves(whole, kind)}
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer(w, x, whole, kind)
        y = ref.attention(w, x, whole, kind, False)
        common = ref.ffn(w, y, whole, kind, False, held=(0, 0))
        shared_alone = ref.ffn(w, y, whole, kind, False, held=(0, 0))
        assert rel(shared_alone - y, ref.ffn(
            w, y, whole, kind, False, held=(0, 0), shared=False) - y) > 1.0
        total = common
        for r in range(ranks):
            held = (r * per, (r + 1) * per)
            cfg = small_cfg(2, experts=E, held=held, top_k=3)
            rank = TransformerParallel.from_config(one_chip(), cfg)
            mine = dict(params)
            for n in ("moe_wg", "moe_wu", "moe_wd"):
                mine["l1_" + n] = params["l1_" + n][held[0]:held[1]]
            out = jax.jit(lambda p, x, rank=rank: rank._layer(1, p, x)[0])(
                mine, x)
            total = total + (out - common)
    assert rel(total, uncut) < 1e-5
    assert rel(common, uncut) > 0.05      # the routed part is not nothing


# --- (c) closed forms --------------------------------------------------------
LAGUNA_FULL = {"theta": 500000, "factor": 64, "beta_fast": 64, "beta_slow": 1,
               "original_max_position_embeddings": 4096,
               "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5}


def test_yarn_by_attention_factor_on_half_of_the_channels():
    """The published full-attention block: 64 of 128 channels rotate;
    correction range 64 ln(4096 / (2 pi n)) / (2 ln 500000) at n = 64, 1."""
    dim = 64
    inv = lm_layers.yarn_inv_freq(dim, LAGUNA_FULL)
    plain = 500000.0 ** (-np.arange(0, dim, 2) / dim)
    low = math.floor(dim * math.log(4096 / (64 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(dim * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (5, 16)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-12)
    np.testing.assert_allclose(inv[high:], plain[high:] / 64, rtol=1e-12)
    ramp = (10 - low) / (high - low)
    np.testing.assert_allclose(
        inv[10], plain[10] / 64 * ramp + plain[10] * (1 - ramp), rtol=1e-12)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)
    cos, sin = lm_layers.rope_tables(8192, dim, LAGUNA_FULL)
    assert cos.shape == sin.shape == (8192, 32)
    for t in (0, 1, 4095, 8191):
        np.testing.assert_allclose(
            cos[t], 1.4158883083359672 * np.cos(t * inv), atol=2e-6)
        np.testing.assert_allclose(
            sin[t], 1.4158883083359672 * np.sin(t * inv), atol=2e-6)
    # the reference's own tables, from the published keys
    cfg = dict(head_dim=128, rope_parameters={"full_attention": dict(
        FULL, original_max_position_embeddings=4096)})
    rc, rs, rdim = ref.rope_tables(cfg, "full_attention", 8192)
    assert rdim == 64
    np.testing.assert_allclose(rc, cos, atol=1e-6)
    np.testing.assert_allclose(rs, sin, atol=1e-6)


@pytest.mark.parametrize("position", [0, 1, 511, 8191])
@pytest.mark.parametrize("layer", ["full", "sliding"])
def test_a_layer_rotates_its_own_channels_by_its_own_angles(layer, position):
    """Through ``gqa_attention``'s own rotation (read off the value it
    keeps as ``gqa_q``): a full layer turns the first 64 channels by the
    yarn angles times ``attention_factor`` and passes the other 64; a
    sliding layer turns all 128 at theta 10,000."""
    hd, T = 128, 8192
    rope = (LAGUNA_FULL if layer == "full"
            else {"theta": 10000, "partial_rotary_factor": 1})
    arch = {"rms_norm_eps": 1e-6, "gqa": {
        "n_kv_heads": 1, "head_dim": hd, "gate": False,
        "layers": [{"n_heads": 1, "window": None, "rope": rope}]}}
    eye = jnp.eye(hd, dtype=F32)
    params = {"l0_attn_norm": jnp.ones(hd, F32), "l0_wq": eye, "l0_wk": eye,
              "l0_wv": eye, "l0_wo": eye}
    x = jax.random.normal(jax.random.PRNGKey(position), (1, T, hd), F32)
    seen = {}

    def attend(q, k, v, scale, window):
        seen.update(q=q, k=k, scale=scale, window=window)
        return q

    lm_layers.gqa_attention(params, 0, x, arch, attend)
    assert seen["scale"] == hd ** -0.5 and seen["window"] is None
    h = np.asarray(lm_layers.rms_norm(x, params["l0_attn_norm"], 1e-6),
                   np.float64)[0, position]
    rot = 64 if layer == "full" else 128
    inv = lm_layers.yarn_inv_freq(rot, rope)
    factor = rope.get("attention_factor", 1.0)
    z = (h[:rot // 2] + 1j * h[rot // 2:rot]) * np.exp(
        1j * position * inv) * factor
    want = np.concatenate([z.real, z.imag, h[rot:]])
    for name in ("q", "k"):
        np.testing.assert_allclose(
            np.asarray(seen[name])[0, 0, position], want, atol=3e-5)


def test_select_without_a_bias_takes_the_largest_scores():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16), F32)
    idx = np.asarray(moe.select(logits, None, 4))
    want = np.argsort(-np.asarray(logits), axis=1)[:, :4]
    assert idx.dtype == np.int32
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    np.testing.assert_array_equal(
        idx, np.asarray(moe.select(logits, jnp.zeros(16), 4)))
    weight = np.asarray(moe.weigh(logits, jnp.asarray(idx), 2.5))
    np.testing.assert_allclose(weight.sum(1), 2.5, rtol=1e-6)


# --- (d) scopes and counters -------------------------------------------------
def test_scopes_counters_and_routing_stats_of_the_new_model():
    cfg = small_cfg(5, experts=32)
    obs.set_enabled(True)
    names = ("moe.experts_held", "moe.row_budget", "moe.compact_row_budget",
             "remat.kept_bytes")
    before = {n: obs.metrics.get_value(n, 0) for n in names}
    model = TransformerParallel.from_config(one_chip(), cfg, remat=True)
    params = seeded_params(model, 5)
    tok, tgt = batch(1)
    text = jax.jit(jax.grad(model.loss_fn)).lower(
        params, tok, tgt).as_text(debug_info=True)
    for li in range(5):
        for part in ("proj", "rope", "flash", "gate", "out"):
            assert "l%d/attn/%s" % (li, part) in text, (li, part)
    assert "l0/ffn" in text and "l1/moe/router" in text
    assert "l4/moe/shared" in text and "l0/moe" not in text
    budget = moe.row_budget(tok.size, 2, 4, moe.GMM_BLOCK_ROWS)
    compact = moe.compact_row_budget(tok.size, 2, 4, 32, moe.GMM_BLOCK_ROWS)
    moved = {n: obs.metrics.get_value(n, 0) - before[n] for n in names}
    assert moved["moe.experts_held"] == 4 * 4
    assert moved["moe.row_budget"] == 4 * budget
    assert moved["moe.compact_row_budget"] == 4 * compact
    # what each recomputed layer keeps by name, from its shapes (float32)
    B, T = tok.shape
    d, hd, K = 32, 8, 2
    kept = 0
    for mlp, _, H in ref.layer_kinds(cfg):
        kept += 4 * B * T * (H * hd + 2 * K * hd + H + d)    # q, k, v, gate, y
        if mlp == "dense":
            kept += 4 * B * T * 2 * 64
        else:
            kept += 4 * B * T * (32 + 2 + 2 + 2 * 40)  # logits, ids, w, shared
            kept += 4 * sum(int(np.prod(a.shape)) for p in (
                moe.plan_dispatch(jnp.zeros((B * T, 2), jnp.int32), (2, 6),
                                  moe.GMM_BLOCK_ROWS, rows)
                for rows in (None, compact)) for a in p.values())
    assert moved["remat.kept_bytes"] == kept
    stats = model.routing_stats(params, tok)
    assert [s["layer"] for s in stats] == [1, 2, 3, 4]
    for s in stats:
        assert s["row_budget"] == budget and s["compact_budget"] == compact
        assert len(s["load"]) == 4 and s["pairs_held"] == sum(s["load"])
        assert s["fits"] is True


def test_the_new_names_are_kept_by_a_recomputed_layer(monkeypatch):
    model = TransformerParallel.from_config(one_chip(), small_cfg(2),
                                            remat=True)
    named = set()

    def kept(value, name, kept=lm_layers.kept):
        named.add(name)
        return kept(value, name)

    monkeypatch.setattr(lm_layers, "kept", kept)
    jax.make_jaxpr(jax.grad(model.loss_fn))(seeded_params(model, 3),
                                            *batch(0))
    assert named == {"gqa_q", "gqa_k", "gqa_v", "gqa_gate", "attn_residual",
                     "ffn_gate", "ffn_up", "router_logits", "route_idx",
                     "route_weight", "moe_plan", "shared_gate", "shared_up"}
    assert named <= set(lm_layers.KEPT_BY_A_RECOMPUTED_LAYER)


# --- (e) what this model does not serve yet, and where it trains -------------
@pytest.mark.parametrize("forward", ["prefill_forward", "decode_forward",
                                     "verify_forward"])
def test_serving_forwards_refuse_a_grouped_query_layer(forward):
    model = TransformerParallel.from_config(one_chip(), small_cfg())
    tokens = jnp.zeros((1, 4), jnp.int32)
    args = (None, tokens) if forward == "prefill_forward" else (
        None, tokens, lambda *a: None)
    with pytest.raises(NotImplementedError, match="grouped-query"):
        getattr(model, forward)(*args)


def test_the_new_kind_refuses_a_mesh_that_is_not_data_parallel():
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="dp meshes"):
        TransformerParallel.from_config(mesh, small_cfg())


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
def test_the_new_model_trains_data_parallel_on_two_devices(remat):
    cfg = small_cfg(3, experts=32)
    two = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tok, tgt = batch(7)
    runs = []
    for mesh in (one_chip(), two):
        model = TransformerParallel.from_config(mesh, cfg, remat=remat)
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


# --- (f) the configuration's file ---------------------------------------------
def test_the_configuration_builds_the_published_model_cut_to_its_share():
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "laguna_xs2_ep8.json")))
    model = TransformerParallel.from_config(one_chip(), cfg)
    table = model.param_table()
    assert sum(int(np.prod(s)) for s, _ in table.values()) == cfg[
        "parameters"] == 691_623_936
    assert ({n: tuple(s) for n, (s, _) in table.items()}
            == {n: tuple(s) for n, (s, _) in ref.param_table(cfg).items()})
    assert model.layers == (("gqa", "swiglu"),) + (("gqa", "moe"),) * 4
    g = model.arch["gqa"]
    assert (g["n_kv_heads"], g["head_dim"], g["gate"]) == (8, 128, True)
    assert [(l["n_heads"], l["window"]) for l in g["layers"]] == [
        (48, None), (64, 512), (64, 512), (64, 512), (48, None)]
    assert g["layers"][0]["rope"] == LAGUNA_FULL
    assert g["layers"][1]["rope"] == {"theta": 10000,
                                      "partial_rotary_factor": 1}
    m = model.arch["moe"]
    assert (m["n_experts"], m["top_k"], m["scale"], m["d_expert"],
            m["d_shared"], m["experts_held"], m["router_bias"]) == (
        256, 8, 2.5, 512, 512, (0, 32), False)

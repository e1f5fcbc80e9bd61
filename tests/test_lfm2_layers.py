"""A model built from the ``lfm2_moe`` family's published keys — gated
short-convolution layers, grouped-query attention with q/k norms, routed
experts selected with an expert bias and no shared expert — against the
plain reference ``perfbench/reference/lfm2_moe.py`` (float32, seeded,
small widths): loss and every leaf's gradient with and without
recomputation, the shares of an expert-parallel deployment adding up to the
uncut layer, the q/k norms and the absent shared expert, the planted
faults, the scopes and counters, and the configuration's file."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu import observability as obs  # noqa: E402
from mxnet_tpu.parallel import lm_layers, make_mesh  # noqa: E402
from mxnet_tpu.parallel.transformer import TransformerParallel  # noqa: E402
from perfbench.reference import lfm2_moe as ref  # noqa: E402
from tools import plant_faults  # noqa: E402

F32 = jnp.float32
PERIOD = ["full_attention", "conv", "conv", "conv"]


def small_cfg(layers=5, experts=4, held=(0, 4), top_k=2):
    """Published layer 1 (a dense conv layer), then periods of [attention,
    conv, conv, conv] with routed FFNs, at small widths (heads of 8)."""
    types = ["conv"] + [PERIOD[i % 4] for i in range(layers - 1)]
    return dict(
        model_type="lfm2_moe", hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64,
        moe_intermediate_size=24, conv_L_cache=3, conv_bias=False,
        norm_eps=1e-5, norm_topk_prob=True, num_dense_layers=1,
        num_hidden_layers=layers, layer_types=types, vocab_size=64,
        num_experts=held[1] - held[0], num_experts_per_tok=top_k,
        routed_scaling_factor=1.5, use_expert_bias=True,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        published={"num_experts": experts},
        deployment={"experts_held": list(held)},
        optimizer={"learning_rate": 0.5})


def one_chip():
    return make_mesh({"dp": 1}, devices=jax.devices()[:1])


def seeded_params(model, seed):
    """``model.init`` with the norm weights moved off 1, and the embedding,
    the router and its bias spread out, so that every leaf's gradient and
    the selection say something."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, leaf in model.init(seed).items():
        if leaf.ndim == 1:
            leaf = leaf + 0.1 * jnp.asarray(rs.randn(*leaf.shape), F32)
        if name == "embed" or name.endswith(("router", "router_bias")):
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


def shapes(table):
    return {n: tuple(s) for n, (s, _) in table.items()}


def reference_grads(cfg, params, tok, tgt):
    """The reference's loss and gradients, jitted (eager it takes five
    times as long on the CPU)."""
    return jax.jit(lambda p, a, b: ref.loss_and_grads(cfg, p, a, b))(
        params, tok, tgt)


# --- (a) the program against the plain reference -----------------------------
@pytest.mark.parametrize("layers,remat", [(5, False), (5, True)],
                         ids=["a_period", "a_period_recomputed"])
def test_program_matches_the_reference(layers, remat):
    cfg = small_cfg(layers)
    model = TransformerParallel.from_config(one_chip(), cfg, remat=remat)
    assert shapes(model.param_table()) == shapes(ref.param_table(cfg))
    assert list(model.param_table()) == list(ref.param_table(cfg))
    params = seeded_params(model, 3)
    start = {k: np.asarray(v) for k, v in params.items()}
    tok, tgt = batch(0)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
            params, tok, tgt)
        want_loss, want = reference_grads(
            cfg, {k: jnp.asarray(v) for k, v in start.items()}, tok, tgt)
        assert abs(float(loss) - float(want_loss)) < 2e-6 * float(want_loss)
        assert set(grads) == set(want)
        for name in want:
            if name.endswith("router_bias"):    # the selection's alone
                assert not np.any(np.asarray(grads[name])), name
                continue
            assert rel(grads[name], want[name]) < 2e-4, name
        if remat:       # the same step; the three steps below once
            return
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
def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Ranks holding experts [0, 2) and [2, 4) of 4: their routed parts,
    plus what every rank computes alike (the attention) counted once,
    equal the uncut reference's layer output. No shared expert."""
    whole = small_cfg(2, experts=4, held=(0, 4), top_k=2)
    kind = ref.layer_kinds(whole)[1]
    assert kind == ("attention", "expert")
    model = TransformerParallel.from_config(one_chip(), whole)
    params = seeded_params(model, 11)
    for n in ("moe_wg", "moe_wu", "moe_wd"):   # a routed part of size
        params["l1_" + n] = 10.0 * params["l1_" + n]
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32), F32)
    w = {n: params["l1_" + n] for n in ref.layer_leaves(whole, kind)}
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer(w, x, whole, kind)
        y = x + ref.attention(w, ref._norm_w(x, w["attn_norm"], 1e-5),
                              whole, False)
        total = common = ref.ffn(w, y, whole, kind, False, held=(0, 0))
        for held in ((0, 2), (2, 4)):
            rank = TransformerParallel.from_config(
                one_chip(), small_cfg(2, experts=4, held=held, top_k=2))
            mine = dict(params)
            for n in ("moe_wg", "moe_wu", "moe_wd"):
                mine["l1_" + n] = params["l1_" + n][held[0]:held[1]]
            out = jax.jit(lambda p, x, rank=rank: rank._layer(1, p, x)[0])(
                mine, x)
            total = total + (out - common)
    assert rel(common, y) == 0.0          # nothing shared is added
    assert rel(total, uncut) < 1e-5
    assert rel(common, uncut) > 0.05      # the routed part is not nothing


# --- (c) no shared expert; q/k norms -----------------------------------------
def test_no_shared_leaves_and_no_shared_matmul_when_n_shared_is_0():
    model = TransformerParallel.from_config(one_chip(), small_cfg(5))
    assert not [n for n in model.param_table() if "shared" in n]
    assert model.arch["moe"]["n_shared"] == 0
    text = jax.jit(model.loss_fn).lower(
        seeded_params(model, 1), *batch(0)).as_text(debug_info=True)
    assert "moe/shared" not in text


def _gqa_arch(qk_norm):
    g = {"n_kv_heads": 2, "head_dim": 8, "gate": True,
         "layers": [{"n_heads": 4, "window": None, "rope": {"theta": 1e4}}]}
    if qk_norm is not None:
        g["qk_norm"] = qk_norm
    return {"rms_norm_eps": 1e-5, "gqa": g}


def test_qk_norm_off_leaves_the_grouped_query_kinds_as_they_were():
    """Without ``qk_norm`` (absent or false) a ``gqa`` layer has the same
    leaves, in the same order, and gives the same output bit for bit; with
    it the layer leads with two head-wide weights and ``attend`` sees q
    and k normed a head."""
    cfg = {"d_model": 32, "n_heads": 4}
    before = lm_layers.layer_table(0, ("gqa", "swiglu"), dict(cfg, d_ff=16),
                                   _gqa_arch(None))
    assert list(before) == ["l0_attn_norm", "l0_wq", "l0_wk", "l0_wv",
                            "l0_wgate", "l0_wo", "l0_ffn_norm", "l0_wg",
                            "l0_wu", "l0_wd"]
    assert lm_layers.layer_table(0, ("gqa", "swiglu"), dict(cfg, d_ff=16),
                                 _gqa_arch(False)) == before
    normed = lm_layers.layer_table(0, ("gqa", "swiglu"), dict(cfg, d_ff=16),
                                   _gqa_arch(True))
    assert list(normed) == ["l0_q_norm", "l0_k_norm"] + list(before)
    assert normed["l0_q_norm"] == ((8,), 1.0)
    keys = jax.random.split(jax.random.PRNGKey(0), len(normed))
    params = {n: 0.3 * jax.random.normal(k, s, F32) + (init if isinstance(
        init, float) else 0.0) for k, (n, (s, init)) in zip(keys,
                                                            normed.items())}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), F32)
    seen = []

    def attend(q, k, v, scale, window, rope):
        seen.append((q, k))
        return jnp.repeat(v, 2, axis=1) + q

    plain = {n: v for n, v in params.items() if "_norm" not in n
             or n == "l0_attn_norm"}
    outs = [lm_layers.gqa_attention(plain, 0, x, _gqa_arch(q), attend)
            for q in (None, False)]
    np.testing.assert_array_equal(outs[0], outs[1])
    normed_out = lm_layers.gqa_attention(params, 0, x, _gqa_arch(True),
                                         attend)
    assert rel(normed_out, outs[0]) > 0.05
    (q0, k0), (q1, k1) = seen[0], seen[2]
    np.testing.assert_allclose(
        q1, lm_layers.rms_norm(q0, params["l0_q_norm"], 1e-5), rtol=1e-6)
    np.testing.assert_allclose(
        k1, lm_layers.rms_norm(k0, params["l0_k_norm"], 1e-5), rtol=1e-6)


# --- float32 master weights --------------------------------------------------
def test_master_weights_keep_the_update_bf16_rounds_away():
    """With ``optimizer.master_weights`` the step takes float32 leaves,
    runs the passes on their bf16 copy (the same loss and gradient as the
    bf16 model's at those weights) and updates in float32, so an update
    under half a bf16 weight's last place still moves its leaf, where the
    bf16 model's own step leaves it as it was."""
    bf16 = np.dtype(jnp.bfloat16)
    plain_cfg = small_cfg(5)
    cfg = dict(plain_cfg, optimizer={"learning_rate": 0.5,
                                     "master_weights": "float32"})
    plain = TransformerParallel.from_config(one_chip(), plain_cfg,
                                            dtype=bf16)
    model = TransformerParallel.from_config(one_chip(), cfg, dtype=bf16)
    assert {v.dtype for v in model.init(0).values()} == {np.dtype("f4")}
    start = {k: v.astype(bf16) for k, v in seeded_params(plain, 3).items()}
    tok, tgt = model.shard_batch(*batch(0))
    lr = cfg["optimizer"]["learning_rate"]
    loss, grads = jax.jit(jax.value_and_grad(plain.loss_fn))(start, tok, tgt)
    master = {k: v.astype(F32) for k, v in start.items()}

    def fresh(leaves, dtype):       # the steps donate what they are given
        return {k: jnp.array(v, dtype) for k, v in leaves.items()}

    moved, got = model.step_fn(lr=lr)(fresh(master, F32), tok, tgt)
    assert float(got) == float(loss)
    for name, leaf in moved.items():
        assert leaf.dtype == np.dtype("f4"), name
        # the update is -lr times the bf16 gradient (summed in bf16 or in
        # float32: a bf16 place), to the float32 place of the weight
        np.testing.assert_allclose(
            leaf - master[name], -lr * grads[name].astype(F32), rtol=2 ** -7,
            atol=2 ** -23 * float(jnp.abs(master[name]).max()), err_msg=name)
        if not name.endswith("router_bias"):
            assert np.any(np.asarray(leaf) != np.asarray(master[name])), name
    rounded, _ = plain.step_fn(lr=lr)(fresh(start, bf16), tok, tgt)
    stuck = [n for n in start if np.array_equal(rounded[n], start[n])]
    assert "l0_attn_norm" in stuck      # a conv layer's: a gradient of 1e-6
    assert model.routing_stats(moved, tok) == plain.routing_stats(
        fresh(moved, bf16), tok)


# --- (d) the planted faults --------------------------------------------------
@pytest.fixture(scope="module")
def sound():
    """A period's model, its seeded leaves, a batch and the reference's
    gradients on them."""
    cfg = small_cfg(5)
    model = TransformerParallel.from_config(one_chip(), cfg)
    params, (tok, tgt) = seeded_params(model, 3), batch(0)
    with jax.default_matmul_precision("highest"):
        _, grads = reference_grads(cfg, params, tok, tgt)
    return model, params, tok, tgt, grads


@pytest.mark.parametrize("fault", plant_faults.LFM2_FAULTS)
def test_the_planted_faults_move_the_gradients(fault, sound):
    """What ``tools/plant_faults.py`` breaks on the chip, here at a small
    size: each moves some leaf's gradient by far more than the program's
    distance from the sound reference (2e-4), and mends again."""
    model, params, tok, tgt, want = sound
    moved = [n for n in want if not n.endswith("router_bias")]
    with jax.default_matmul_precision("highest"):
        mend = plant_faults.plant(model, fault)
        try:
            faulty = jax.jit(jax.grad(model.loss_fn))(params, tok, tgt)
        finally:
            mend()
        again = jax.jit(jax.grad(model.loss_fn))(params, tok, tgt)
    assert max(rel(faulty[n], want[n]) for n in moved) > 0.05
    assert max(rel(again[n], want[n]) for n in moved) < 2e-4


# --- (e) scopes and counters -------------------------------------------------
def test_scopes_and_counters_of_the_new_model():
    cfg = small_cfg(5)
    obs.set_enabled(True)
    names = ("lm_layers.short_conv", "remat.kept_bytes")
    before = {n: obs.metrics.get_value(n, 0) for n in names}
    model = TransformerParallel.from_config(one_chip(), cfg, remat=True)
    tok, tgt = batch(1)
    text = jax.jit(jax.grad(model.loss_fn)).lower(
        seeded_params(model, 5), tok, tgt).as_text(debug_info=True)
    for li, kind in enumerate(model.layers):
        parts = (("conv", ("proj", "mix", "out")) if kind[0] == "conv"
                 else ("attn", ("proj", "flash", "out")))
        for part in parts[1]:
            assert "l%d/%s/%s" % (li, parts[0], part) in text, (li, part)
    assert "l0/ffn" in text and "l1/moe/router" in text
    moved = {n: obs.metrics.get_value(n, 0) - before[n] for n in names}
    assert moved["lm_layers.short_conv"] == 4
    # bcx (B, T, 3d) float32 a conv layer, beside what the other kinds keep
    named = {}

    def kept(value, name, kept=lm_layers.kept):
        named[name] = named.get(name, 0) + 1
        return kept(value, name)

    lm_layers_kept = lm_layers.kept
    lm_layers.kept = kept
    try:
        jax.make_jaxpr(jax.grad(model.loss_fn))(seeded_params(model, 3),
                                                tok, tgt)
    finally:
        lm_layers.kept = lm_layers_kept
    assert named["conv_bcx"] == 4
    assert "shared_gate" not in named
    assert set(named) <= set(lm_layers.KEPT_BY_A_RECOMPUTED_LAYER)


# --- (f) the configuration's file ---------------------------------------------
def test_the_configuration_builds_the_published_model_cut_to_its_share():
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "lfm2_24b_a2b_ep8.json")))
    model = TransformerParallel.from_config(one_chip(), cfg)
    table = model.param_table()
    assert sum(int(np.prod(s)) for s, _ in table.values()) == cfg[
        "parameters"] == 832_652_032
    assert shapes(table) == shapes(ref.param_table(cfg))
    assert model.layers == (("conv", "swiglu"), ("gqa", "moe")) + (
        ("conv", "moe"),) * 3 + (("gqa", "moe"),) + (("conv", "moe"),) * 3
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:10]
    g = model.arch["gqa"]
    assert (g["n_kv_heads"], g["head_dim"], g["gate"], g["qk_norm"]) == (
        8, 64, False, True)
    assert sorted(g["layers"]) == [1, 5]
    assert g["layers"][1] == {"n_heads": 32, "window": None,
                              "rope": {"theta": 1000000}}
    assert model.arch["conv"] == {"taps": 3} and model.arch["tied_head"]
    m = model.arch["moe"]
    assert (m["n_experts"], m["top_k"], m["scale"], m["d_expert"],
            m["n_shared"], m["experts_held"], m["router_bias"]) == (
        64, 4, 1, 1536, 0, (0, 8), True)
    assert table["l0_conv_in"][0] == (2048, 6144)
    assert table["l0_conv_w"][0] == (3, 2048)
    assert table["l1_q_norm"][0] == (64,)

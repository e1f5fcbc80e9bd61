"""The layer kinds PR 36 added (``"ssm"``, ``"gmu"``, ``"diff"``), the
values a layer hands to later layers, the tied head and ``from_config``
under ``model_type: phi4flash``, at small sizes on the CPU against the
plain reference ``perfbench/reference/sambay.py``."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu.observability import metrics as obs  # noqa: E402
from mxnet_tpu.parallel import lm_layers, make_mesh  # noqa: E402
from mxnet_tpu.parallel.flash_attention import (  # noqa: E402
    _dense_with_lse, flash_attention)
from mxnet_tpu.parallel.transformer import TransformerParallel  # noqa: E402
from perfbench import check, seeded  # noqa: E402
from perfbench.reference import sambay  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "plant_faults", os.path.join(ROOT, "tools", "plant_faults.py"))
plant_faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plant_faults)

#: the limits of the cell phi4flash_train_t8192_b1 on what one step shows
LIMITS = {"loss_gap": 0.0015, "grad_gap": 0.08, "grad_gap_median": 0.0005}


def tiny(**over):
    cfg = dict(
        model_type="phi4flash", hidden_size=64, intermediate_size=128,
        layer_norm_eps=1e-5, mb_per_layer=2, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=10, sliding_window=16,
        tie_word_embeddings=True, vocab_size=64,
        published={"num_hidden_layers": 32},
        deployment={"first_layer": 12},
        assumed={"mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
                 "mamba_dt_rank": 4},
        optimizer={"learning_rate": 1.0})
    cfg.update(over)
    return cfg


def one_chip():
    return make_mesh({"dp": 1}, devices=jax.devices()[:1])


def build(cfg, remat=False, seed=3):
    model = TransformerParallel.from_config(one_chip(), cfg,
                                            dtype=np.float32, remat=remat)
    params = seeded.make_params(sambay.param_table(cfg), seed, jnp.float32,
                                model.param_shardings())
    return model, params


def batch(cfg, T=64, B=2, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0,
                             cfg["vocab_size"]).astype(jnp.int32)
    return tok[:, :-1], tok[:, 1:]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def loss_and_grads(model, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(model.loss_fn)(params, tokens, targets)


_witness = {}


def witness(remat=False):
    """(cfg, the program's loss and gradients, the reference's)."""
    if remat not in _witness:
        cfg = tiny()
        model, params = build(cfg, remat)
        tokens, targets = batch(cfg)
        _witness[remat] = (cfg, loss_and_grads(model, params, tokens,
                                               targets),
                           sambay.loss_and_grads(cfg, dict(params), tokens,
                                                 targets))
    return _witness[remat]


# --- differential attention through the flash kernels ----------------------
@pytest.mark.parametrize("window", [None, 32])
def test_differential_maps_through_flash_match_the_dense_formula(window):
    """The shape the cell runs: q/k 64 wide, V 128 wide, two query head
    pairs to a k/v pair, with and without a window — through the kernels
    in the interpreter, outputs and the three gradients."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    f32 = jnp.float32
    q = jax.random.normal(k[0], (1, 4, 128, 64), f32)
    kk = jax.random.normal(k[1], (1, 2, 128, 64), f32)
    v = jax.random.normal(k[2], (1, 2, 128, 128), f32)
    w = jax.random.normal(k[3], (1, 4, 128, 128), f32)

    def through(fn):
        return jax.value_and_grad(
            lambda q, kk, v: jnp.sum(fn(q, kk, v) * w), argnums=(0, 1, 2))(
            q, kk, v)

    with jax.default_matmul_precision("highest"):
        got = through(lambda q, kk, v: flash_attention(
            q, kk, v, causal=True, scale=0.125, window=window,
            block_q=32, block_k=32, block_q_bwd=32, block_k_bwd=32,
            interpret=True))
        want = through(lambda q, kk, v: _dense_with_lse(
            q, kk, v, causal=True, scale=0.125, window=window)[0])
    assert abs(float(got[0] - want[0])) < 1e-4 * abs(float(want[0])) + 1e-3
    for g, r in zip(got[1], want[1]):
        assert rel(g, r) < 1e-4


def test_the_layer_is_the_difference_of_two_maps():
    """diff_attention against the dense formula written out: two softmax
    maps a head pair, lambda, the 2 hd-wide RMS norm, the scale."""
    cfg = tiny(num_hidden_layers=1, deployment={"first_layer": 13})
    model, params = build(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = lm_layers.diff_attention(params, 0, x, model.arch,
                                          model._attend)
        w = {n: params["l0_" + n] for n in sambay.layer_leaves(
            cfg, ("window", 13))}
        a = sambay._ln(x, w["attn_norm"], w["attn_norm_b"], 1e-5)
        want, _ = sambay.differential(w, a, cfg, ("window", 13), None, False)
    assert rel(got, want) < 1e-5
    assert model.arch["diff"]["layers"][0]["lambda_init"] == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 13))


# --- the whole model against the reference ----------------------------------
def test_the_parameters_are_the_configurations():
    cfg = tiny()
    model, _ = build(cfg)
    mine = {n: tuple(s) for n, (s, _) in model.param_table().items()}
    theirs = {n: tuple(s) for n, (s, _) in sambay.param_table(cfg).items()}
    assert mine == theirs
    assert "out_w" not in mine              # the head is the embedding
    assert [k for k, _ in model.layers] == [
        "ssm", "diff", "ssm", "diff", "ssm", "diff", "gmu", "diff", "gmu",
        "diff"]
    assert model.arch["shared"] == {"memory": 4, "kv": 5}
    layers = model.arch["diff"]["layers"]
    assert [layers[i]["window"] for i in (1, 3, 5, 7, 9)] == [
        16, 16, None, None, None]
    assert [layers[i]["cross"] for i in (1, 3, 5, 7, 9)] == [
        False, False, False, True, True]


@pytest.mark.parametrize("remat", [False, True])
def test_loss_matches_the_reference(remat):
    _, (got, _), (want, _) = witness(remat)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)


@pytest.mark.parametrize("leaf", sorted(sambay.param_table(tiny())))
def test_every_leafs_gradient_matches_the_reference(leaf):
    _, (_, got), (_, want) = witness()
    assert rel(got[leaf], want[leaf]) < 2e-4, leaf


def test_three_steps_of_the_reference_follow_the_whole_model():
    """The reference's layer-by-layer walk (what the chip run compares
    with) gives the whole-model gradient: the shared values' cotangents
    reach their makers, the tied leaf's two uses add up."""
    cfg, _, (loss, grads) = witness()
    tokens, targets = batch(cfg)
    table = sambay.param_table(cfg)
    out = sambay.three_steps(
        cfg, lambda n: seeded.make_leaf(table, n, 3, jnp.float32),
        [(np.asarray(tokens), np.asarray(targets))])
    assert out["loss"][0] == pytest.approx(float(loss), rel=1e-6)
    for n in table:
        assert out["grad"][n] == pytest.approx(
            float(jnp.linalg.norm(grads[n])), rel=1e-4), n


# --- the values a layer hands on ---------------------------------------------
def test_recomputation_leaves_the_shared_values_gradient_as_it_was():
    """The gradient that reaches the memory's and the k/v's makers (and
    every other leaf) is the same with each layer recomputed as without:
    a reader takes the value as an input, its gradient is the readers'
    summed."""
    _, (_, plain), _ = witness(False)
    _, (_, again), _ = witness(True)
    for n in plain:
        assert rel(again[n], plain[n]) < 1e-5, n


def test_the_readers_are_counted_and_the_makers_run_once(monkeypatch):
    cfg = tiny()
    model, params = build(cfg, remat=True)
    tokens, targets = batch(cfg)
    calls = {"ssm": 0, "kv": 0}
    ssm, diff = lm_layers.ssm_mixer, lm_layers.diff_attention

    def counted_ssm(*a, **k):
        calls["ssm"] += 1
        return ssm(*a, **k)

    def counted_diff(params, li, x, arch, attend, kv=None):
        calls["kv"] += kv is None
        return diff(params, li, x, arch, attend, kv)

    monkeypatch.setattr(lm_layers, "ssm_mixer", counted_ssm)
    monkeypatch.setattr(lm_layers, "diff_attention", counted_diff)
    obs.set_enabled(True)
    names = ("lm_layers.shared_readers", "flash_attention.differential",
             "remat.kept_bytes")
    before = {n: obs.get_value(n, 0) for n in names}
    try:
        jax.make_jaxpr(jax.grad(model.loss_fn))(params, tokens, targets)
        after = {n: obs.get_value(n, 0) - before[n] for n in names}
    finally:
        obs.set_enabled(False)
    # each layer is traced once (its recomputation runs the same trace
    # again): three state-space layers, three layers that make k and v of
    # their own — no reader among the makers
    assert calls == {"ssm": 3, "kv": 3}
    assert after["lm_layers.shared_readers"] == 4
    assert after["flash_attention.differential"] == 5
    assert after["remat.kept_bytes"] > 0


def test_the_tied_heads_gradient_is_the_sum_of_both_uses():
    cfg = tiny()
    model, params = build(cfg)
    tokens, targets = batch(cfg)

    def loss(lookup, head):
        p = dict(params, embed=lookup)
        x = p["embed"][tokens]
        return sambay.head_loss(dict(p, embed=head),
                                sambay.forward(cfg, p, x), targets, cfg)

    with jax.default_matmul_precision("highest"):
        of_lookup, of_head = jax.grad(loss, argnums=(0, 1))(
            params["embed"], params["embed"])
    _, (_, got), _ = witness()
    assert rel(got["embed"], of_lookup + of_head) < 2e-4
    assert rel(of_head, of_lookup + of_head) > 0.05     # both uses matter


# --- the cut tied to the model -------------------------------------------------
def test_the_cut_is_layers_12_to_21_of_the_uncut_model():
    """Layers 12-21 of an uncut 32-layer reference, fed the same hidden
    state, give what the cut model gives: kinds and lambda_init follow the
    published index."""
    cut = tiny()
    whole = tiny(num_hidden_layers=32, deployment={"first_layer": 0})
    whole_kinds = sambay.layer_kinds(whole)
    assert whole_kinds[12:22] == sambay.layer_kinds(cut)
    assert [k for k, _ in whole_kinds].count("mamba") == 9
    assert [k for k, _ in whole_kinds].count("window") == 8
    assert [k for k, _ in whole_kinds].count("full") == 1
    assert [k for k, _ in whole_kinds].count("gmu") == 7
    assert [k for k, _ in whole_kinds].count("cross") == 7
    model, params = build(cut)
    # the uncut model's layers 12-21 hold the cut model's leaves (the walk
    # below reads no other layer's)
    uncut = {"l%d_%s" % (12 + li, n): params["l%d_%s" % (li, n)]
             for li, kind in enumerate(sambay.layer_kinds(cut))
             for n in sambay.layer_leaves(cut, kind)}
    assert set(uncut) <= set(sambay.param_table(whole))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = sambay.forward(whole, uncut, x, whole_kinds[12:22], first=12)
        got, shared = x, {}
        for li in range(10):
            read = {k: shared[k] for k in lm_layers.reads(
                li, model.layers[li][0], model.arch)}
            got, made = model._layer(li, params, got, read)
            shared.update(made)
    assert rel(got, want) < 1e-5


# --- planted faults ----------------------------------------------------------
def _readings(loss, grads):
    norms = {n: float(jnp.linalg.norm(g)) for n, g in grads.items()}
    return {"loss": [float(loss)], "grad": norms, "change": norms}


def test_the_sound_program_is_correct_by_the_cells_limits():
    _, got, want = witness()
    correct, compared = check.compare(_readings(*got), _readings(*want),
                                      LIMITS)
    assert correct, compared


@pytest.mark.parametrize("fault", plant_faults.FAULTS[:4])
def test_a_planted_fault_is_not_correct(fault):
    cfg = tiny()
    model, params = build(cfg)
    tokens, targets = batch(cfg)
    mend = plant_faults.plant(model, fault)
    try:
        got = loss_and_grads(model, params, tokens, targets)
    finally:
        mend()
    _, _, want = witness()
    correct, compared = check.compare(_readings(*got), _readings(*want),
                                      LIMITS)
    assert not correct, compared


def test_a_bf16_state_shows_in_the_scan_and_not_in_the_norms():
    """The fifth planted fault, the scan's state rounded to bfloat16 every
    step, moves the scan's output by a hundred times the kernel pair's own
    error — and no norm by a limit's worth: ``perfbench/check.py``
    compares norms, and rounding noise moves a norm at second order
    (PERF.md section 6, PR 36)."""
    from mxnet_tpu.parallel.ssm_scan import ssm_scan, ssm_scan_xla

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    f32 = jnp.float32
    u = jax.random.normal(k[0], (1, 128, 32), f32)
    delta = 0.1 * jax.nn.softplus(jax.random.normal(k[1], (1, 128, 32), f32))
    A = -jnp.arange(1, 5, dtype=f32) * jnp.ones((32, 4), f32)
    Bm, Cm = (jax.random.normal(k[i], (1, 128, 4), f32) for i in (2, 3))
    D = jnp.ones((32,), f32)
    want = ssm_scan_xla(u, delta, A, Bm, Cm, D, chunk=16)
    sound = ssm_scan(u, delta, A, Bm, Cm, D, chunk=16, channels=8,
                     interpret=True)
    faulty = plant_faults.scan_with_a_bf16_state(u, delta, A, Bm, Cm, D,
                                                 chunk=16)
    assert rel(sound, want) < 1e-6
    assert rel(faulty, want) > 1e-4
    norms = abs(float(jnp.linalg.norm(faulty) / jnp.linalg.norm(want)) - 1)
    assert norms < 1e-4
    # and in the model, at the seeded start, under every limit
    cfg = tiny()
    model, params = build(cfg)
    mend = plant_faults.plant(model, "scan_state_in_bf16")
    try:
        got = loss_and_grads(model, params, *batch(cfg))
    finally:
        mend()
    correct, _ = check.compare(_readings(*got), _readings(*witness()[2]),
                               LIMITS)
    assert correct


def test_serving_forwards_refuse_the_new_kinds():
    model, params = build(tiny())
    with pytest.raises(NotImplementedError):
        model.prefill_forward(params, jnp.zeros((1, 8), jnp.int32))

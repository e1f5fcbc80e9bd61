"""Plain reference: a causal LM of gated short-convolution layers and
grouped-query attention layers with q/k norms, the first
``num_dense_layers`` with a SwiGLU FFN and the rest with sigmoid top-k
routed experts selected with an expert bias, no shared expert and ONE
RANK'S SHARE of the routed sum — the ``lfm2_moe`` family's published keys
— in float32 ``jax.numpy`` at ``highest`` matmul precision. Imports
nothing of the program.

``d`` the hidden size, ``n_w(x) = x * rsqrt(mean(x^2) + eps) * w`` with
``eps = norm_eps``; every layer is ``h = x + Op(n_1(x)); out = h +
FFN(n_2(h))`` with ``Op``, by ``layer_types``:

- ``conv``: ``[B | C | x~] = u W_in`` (d -> 3d, in that order); ``z = B *
  x~``; ``c_t = sum_{k < K} w_k * z_{t - K + 1 + k}`` (depthwise, causal,
  ``K = conv_L_cache``, zero before the first token); ``Op = (C * c)
  W_out``. No biases (``conv_bias: false``).
- ``full_attention``: ``q = u W_q`` in ``num_attention_heads`` heads, ``k
  = u W_k`` and ``v = u W_v`` in ``num_key_value_heads``, all ``d / H``
  wide; ``q <- n_q(q)``, ``k <- n_k(k)`` over each head's channels (a
  learned weight a channel); rotary positions at ``rope_theta`` on every
  channel (half-split pairing); causal ``softmax(q k^T / sqrt(hd)) v``,
  query head ``h`` reading k/v head ``h // (H / K)``; ``Op = concat_h(o_h)
  W_o``.
- FFN: dense ``(silu(u W_g) * (u W_u)) W_d``; routed: ``s = sigmoid(u
  W_r)`` over the published experts, the ``num_experts_per_tok`` with the
  largest ``s + b`` (``b`` the expert bias: selection only), ``w_e =
  routed_scaling_factor * s_e / sum_topk s``, out ``= sum over (top-k and
  held) of w_e E_e(u)``; the experts outside ``experts_held`` add nothing,
  here as in the program.
- head: ``n_f`` (the published ``embedding_norm``), logits ``= . embed^T``
  (the tied head) over the vocabulary's slice, float32 log-softmax, mean
  NLL; plain SGD.

What fits is ``gqa_moe``'s and ``mla_moe``'s own, imported: the blocked
attention core, the rotation and its tables, the SwiGLU, the matmul with
the control's rounding and the biased routing (``mla_moe.route``). The
loop over the held experts is written here: ``gqa_moe.ffn`` selects
without a bias and ``mla_moe.ffn`` always adds a shared expert.

Attention runs in blocks of query rows with k and v repeated to the query
heads (each block recomputed in the backward pass), every held expert is
a dense loop over all tokens, and the float32 leaves wait on the host
between layers, so that the model fits a 16 GB chip at its published
widths.

``quant`` puts the control in its place: every matmul's operands, result
and their cotangents rounded to fp8 (e4m3, per-tensor scale).
"""
import jax
import jax.numpy as jnp
import numpy as np

from .gqa_moe import (_attention, _mm, _norm, _norm_w, _rope, _swiglu,
                      held_range, rope_tables)
from .mla_moe import route

_STD = ("normal", 0.02)


def layer_kinds(cfg):
    """One (op, ffn) a layer: ``conv`` or ``attention``, ``dense`` or
    ``expert``."""
    ops = {"conv": "conv", "full_attention": "attention"}
    return [(ops[t], "dense" if li < cfg["num_dense_layers"] else "expert")
            for li, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def layer_leaves(cfg, kind):
    """short name -> (shape, init) of one layer's leaves."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, K = d // H, cfg["num_key_value_heads"]
    if kind[0] == "conv":
        t = {"attn_norm": ((d,), 1.0), "conv_in": ((d, 3 * d), _STD),
             "conv_w": ((cfg["conv_L_cache"], d), _STD),
             "conv_out": ((d, d), _STD)}
    else:
        t = {"q_norm": ((hd,), 1.0), "k_norm": ((hd,), 1.0),
             "attn_norm": ((d,), 1.0), "wq": ((d, H * hd), _STD),
             "wk": ((d, K * hd), _STD), "wv": ((d, K * hd), _STD),
             "wo": ((H * hd, d), _STD)}
    t["ffn_norm"] = ((d,), 1.0)
    if kind[1] == "dense":
        f = cfg["intermediate_size"]
        t.update({"wg": ((d, f), _STD), "wu": ((d, f), _STD),
                  "wd": ((f, d), _STD)})
    else:
        f = cfg["moe_intermediate_size"]
        lo, hi = held_range(cfg)
        E = cfg["published"]["num_experts"]
        t.update({"router": ((d, E), _STD), "router_bias": ((E,), _STD),
                  "moe_wg": ((hi - lo, d, f), _STD),
                  "moe_wu": ((hi - lo, d, f), _STD),
                  "moe_wd": ((hi - lo, f, d), _STD)})
    return t


def param_table(cfg):
    """name -> (shape, init), in the order the seed's keys are folded in."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    table = {"embed": ((v, d), _STD), "final_norm": ((d,), 1.0)}
    for li, kind in enumerate(layer_kinds(cfg)):
        for name, spec in layer_leaves(cfg, kind).items():
            table["l%d_%s" % (li, name)] = spec
    return table


# --- the equations ---------------------------------------------------------
def short_conv(u, w, cfg, quant):
    """The conv op on the normed input ``u`` (B, T, d), the filter as the
    explicit shifted sum."""
    T, d = u.shape[1], cfg["hidden_size"]
    bcx = _mm("btd,de->bte", u, w["conv_in"], quant)
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    K = cfg["conv_L_cache"]
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][k] * z[:, k:k + T] for k in range(K))
    return _mm("btd,de->bte", c * conv, w["conv_out"], quant)


def attention(w, u, cfg, quant):
    """The attention op on the normed input ``u`` (B, T, d)."""
    B, T, d = u.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // H, cfg["norm_eps"]
    heads = lambda y, n: y.reshape(B, T, n, hd).transpose(0, 2, 1, 3)
    q = _norm_w(heads(_mm("btd,de->bte", u, w["wq"], quant), H),
                w["q_norm"], eps)
    k = _norm_w(heads(_mm("btd,de->bte", u, w["wk"], quant), K),
                w["k_norm"], eps)
    v = heads(_mm("btd,de->bte", u, w["wv"], quant), K)
    tables = rope_tables({"head_dim": hd, "rope_parameters": {
        "full_attention": cfg["rope_parameters"]}}, "full_attention", T)
    q, k = _rope(q, tables), _rope(k, tables)
    k, v = (jnp.repeat(a, H // K, axis=1) for a in (k, v))
    att = _attention(q, k, v, hd ** -0.5, None, quant)
    att = att.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return _mm("bte,ed->btd", att, w["wo"], quant)


def ffn(w, y, cfg, kind, quant, held=None):
    """The layer's second half on (B, T, d). ``held``: the range of experts
    whose part is added (the configuration's share by default)."""
    B, T, d = y.shape
    u = _norm_w(y, w["ffn_norm"], cfg["norm_eps"]).reshape(B * T, d)
    if kind[1] == "dense":
        return y + _swiglu(u, w["wg"], w["wu"], w["wd"], quant).reshape(
            B, T, d)
    lo, hi = held_range(cfg)
    first, last = (lo, hi) if held is None else held
    _, weight = route(u, w, cfg, quant)

    @jax.checkpoint
    def expert(u, weight, wg, wu, wd, e):
        """w_e E_e(u) on every token (zero weight off the top-k)."""
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, e - lo, 0, False)
        w_e = jax.lax.dynamic_index_in_dim(weight, e, 1, False)
        return w_e[:, None] * _swiglu(u, pick(wg), pick(wu), pick(wd), quant)

    out, _ = jax.lax.scan(       # every token through every expert held
        lambda acc, e: (acc + expert(u, weight, w["moe_wg"], w["moe_wu"],
                                     w["moe_wd"], e), None),
        jnp.zeros_like(u), jnp.arange(first, last))
    return y + out.reshape(B, T, d)


def layer(w, x, cfg, kind, quant=False):
    u = _norm_w(x, w["attn_norm"], cfg["norm_eps"])
    op = (short_conv(u, w, cfg, quant) if kind[0] == "conv"
          else attention(w, u, cfg, quant))
    return ffn(w, x + op, cfg, kind, quant)


def head_loss(w, x, targets, cfg, quant=False):
    logits = _mm("btd,vd->btv", _norm_w(x, w["final_norm"], cfg["norm_eps"]),
                 w["embed"], quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def forward(cfg, p, x, kinds=None):
    """The layers ``kinds`` (all held by default) on a hidden state ``x``:
    the tests' handle."""
    for li, kind in enumerate(kinds or layer_kinds(cfg)):
        x = layer({n: p["l%d_%s" % (li, n)] for n in layer_leaves(cfg, kind)},
                  x, cfg, kind)
    return x


def loss_and_grads(cfg, p, tokens, targets):
    """The whole model at once (small sizes: the tests' witness): loss and
    every leaf's gradient, ``p`` the flat dict of float32 leaves."""
    def loss(p):
        return head_loss(p, forward(cfg, p, p["embed"][tokens]), targets,
                         cfg)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(p)


def three_steps(cfg, make_leaf, batches, quant=False):
    """Follow the program's first steps. ``make_leaf(name)`` gives a leaf's
    float32 starting value (made again from the seed, not handed over by
    the program); ``batches`` is a list of (tokens, targets) int32 (B, T).
    Returns the numbers the comparison reads, as host floats."""
    lr = cfg["optimizer"]["learning_rate"]
    kinds = layer_kinds(cfg)
    fwd, bwd = {}, {}
    for kind in set(kinds):
        fwd[kind] = jax.jit(
            lambda w, x, kind=kind: layer(w, x, cfg, kind, quant))

        def back(w, x, dy, kind=kind):
            _, vjp = jax.vjp(lambda w, x: layer(w, x, cfg, kind, quant), w, x)
            dw, dx = vjp(dy)
            return ({k: w[k] - lr * dw[k] for k in w}, dx,
                    {k: _norm(dw[k]) for k in w})

        bwd[kind] = jax.jit(back, donate_argnums=(0,))

    @jax.jit
    def head(w, x, targets):
        loss, (dw, dx) = jax.value_and_grad(
            lambda w, x: head_loss(w, x, targets, cfg, quant), (0, 1))(w, x)
        return loss, dw, dx

    @jax.jit
    def embed_update(embed, of_head, tokens, dx):
        # the tied leaf's gradient is the sum of its two uses
        g = of_head.at[tokens].add(dx)
        return embed - lr * g, _norm(g)

    diff = jax.jit(lambda a, b: _norm(a - b))

    def names_of(li):
        return {n: "l%d_%s" % (li, n) for n in layer_leaves(cfg, kinds[li])}

    with jax.default_matmul_precision("highest"):
        # the leaves wait on the host; a layer's are on the device while
        # that layer is worked on
        host = {n: np.asarray(make_leaf(n)) for n in param_table(cfg)}
        losses, grad = [], None
        for tokens, targets in batches:
            g = {}
            xs = [jnp.asarray(host["embed"])[tokens]]
            for li, kind in enumerate(kinds):
                w = {n: jnp.asarray(host[full])
                     for n, full in names_of(li).items()}
                xs.append(fwd[kind](w, xs[-1]))
                del w
            w = {n: jnp.asarray(host[n]) for n in ("embed", "final_norm")}
            loss, dw, dx = head(w, xs.pop(), targets)
            host["final_norm"] = np.asarray(w["final_norm"]
                                            - lr * dw["final_norm"])
            g["final_norm"] = _norm(dw["final_norm"])
            of_head = dw["embed"]
            del w, dw
            for li in reversed(range(len(kinds))):
                names = names_of(li)
                w = {n: jnp.asarray(host[full]) for n, full in names.items()}
                w, dx, gn = bwd[kinds[li]](w, xs.pop(), dx)
                for n, full in names.items():
                    host[full], g[full] = np.asarray(w[n]), gn[n]
                del w
            embed, g["embed"] = embed_update(jnp.asarray(host["embed"]),
                                             of_head, tokens, dx)
            host["embed"] = np.asarray(embed)
            del embed, of_head
            losses.append(float(loss))
            grad = grad or {k: float(v) for k, v in g.items()}
        change = {n: float(diff(jnp.asarray(host.pop(n)), make_leaf(n)))
                  for n in param_table(cfg)}
    return {"loss": losses, "grad": grad, "change": change}

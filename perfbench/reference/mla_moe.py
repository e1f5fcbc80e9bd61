"""Plain reference: a causal LM of latent-attention (MLA) layers, the first
``first_k_dense_replace`` with a SwiGLU FFN and the rest with sigmoid top-k
routed experts, a shared expert and ONE RANK'S SHARE of the routed sum, in
float32 ``jax.numpy`` at ``highest`` matmul precision. Imports nothing of
the program.

With ``n_w(x) = x * rsqrt(mean(x^2) + eps) * w`` and tokens ``x``:

- MLA: ``h = n_1(x)``; ``q = h W_q`` in heads of ``[nope | rope]``;
  ``[c_kv | k_rope] = h W_kva`` (one ``k_rope`` a token, shared by the
  heads); ``[k_nope | v]`` per head ``= n_kv(c_kv) W_kvb``; rotary
  positions (``deepseek_yarn`` frequencies, half-split pairing) on
  ``q_rope`` and ``k_rope``; causal ``softmax(q k^T s) v`` with ``s =
  q_head_dim^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  ``y = x + concat_h(o_h) W_o``.
- dense FFN: ``y + (silu(u W_g) * (u W_u)) W_d``, ``u = n_2(y)``.
- expert layer: ``sigma = sigmoid(u W_r)``; the k experts with the largest
  ``sigma + b``; ``w_e = scale * sigma_e / sum_topk sigma``; out ``= y +
  S(u) + sum over (top-k and held) of w_e E_e(u)``. The experts outside
  ``experts_held`` add nothing, here as in the program.
- head: ``n_f``, ``W_out``, float32 log-softmax, mean NLL; plain SGD.

Attention runs in blocks of heads and query rows and every expert is a
dense loop over all tokens (each block and expert recomputed in the
backward pass), and the float32 leaves wait on the host between layers, so
that the model fits a 16 GB chip at its published widths.

``quant`` puts the control in its place: every matmul's operands, result
and their cotangents rounded to fp8 (e4m3, per-tensor scale).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .resnet_preact import store

_STD = ("normal", 0.02)


def held_range(cfg):
    lo, hi = cfg["deployment"]["experts_held"]
    return int(lo), int(hi)


def layer_kinds(cfg):
    dense = cfg["first_k_dense_replace"]
    return ["dense" if li < dense else "expert"
            for li in range(cfg["num_hidden_layers"])]


def layer_leaves(cfg, kind):
    """short name -> (shape, init) of one layer's leaves."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    t = {"attn_norm": ((d,), 1.0), "wq": ((d, H * (dn + dr)), _STD),
         "wkva": ((d, r + dr), _STD), "kv_norm": ((r,), 1.0),
         "wkvb": ((r, H * (dn + dv)), _STD), "wo": ((H * dv, d), _STD),
         "ffn_norm": ((d,), 1.0)}
    if kind == "dense":
        f = cfg["intermediate_size"]
        t.update({"wg": ((d, f), _STD), "wu": ((d, f), _STD),
                  "wd": ((f, d), _STD)})
    else:
        f = cfg["moe_intermediate_size"]
        fs = f * cfg["num_shared_experts"]
        lo, hi = held_range(cfg)
        E = cfg["published"]["num_experts"]
        t.update({"router": ((d, E), _STD), "router_bias": ((E,), _STD),
                  "shared_wg": ((d, fs), _STD), "shared_wu": ((d, fs), _STD),
                  "shared_wd": ((fs, d), _STD),
                  "moe_wg": ((hi - lo, d, f), _STD),
                  "moe_wu": ((hi - lo, d, f), _STD),
                  "moe_wd": ((hi - lo, f, d), _STD)})
    return t


def param_table(cfg):
    """name -> (shape, init), in the order the seed's keys are folded in."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    table = {"embed": ((v, d), _STD), "out_w": ((d, v), _STD),
             "final_norm": ((d,), 1.0)}
    for li, kind in enumerate(layer_kinds(cfg)):
        for name, spec in layer_leaves(cfg, kind).items():
            table["l%d_%s" % (li, name)] = spec
    return table


# --- the equations ---------------------------------------------------------
def _mm(spec, a, b, quant):
    return store(jnp.einsum(spec, store(a, quant), store(b, quant)), quant)


def _norm_w(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg):
    """The rotary channels' inverse frequencies under ``rope_scaling``."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return plain

    def correction(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / rs["factor"] * ramp + plain * (1 - ramp)


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling") or {}
    m = (yarn_mscale(rs["factor"], rs["mscale_all_dim"])
         if rs.get("mscale_all_dim") else 1.0)
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """Half-split rotation of (..., T, dim) by position."""
    T = x.shape[-2]
    rs = cfg.get("rope_scaling") or {}
    ratio = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
             / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
             if rs else 1.0)
    angle = np.arange(T, dtype=np.float64)[:, None] * inv_freq(cfg)[None]
    cos = jnp.asarray(np.cos(angle) * ratio, jnp.float32)
    sin = jnp.asarray(np.sin(angle) * ratio, jnp.float32)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block_size(n, bound):
    return max(b for b in range(1, min(n, bound) + 1) if n % b == 0)


def _attention(q, k, v, scale, quant, head_block=8, row_block=1024):
    """Causal attention on (G, T, .) with G = batch x heads, dense, in
    blocks of ``head_block`` heads and ``row_block`` query rows (two nested
    ``lax.map``); a block's scores are recomputed in the backward pass."""
    G, T, _ = q.shape
    hb, rb = _block_size(G, head_block), _block_size(T, row_block)

    @jax.checkpoint
    def block(qb, kb, vb, first_row):
        s = _mm("gqd,gkd->gqk", qb, kb, quant) * scale
        rows = first_row + jnp.arange(rb)[:, None]
        s = jnp.where(rows >= jnp.arange(T)[None, :], s, -jnp.inf)
        return _mm("gqk,gkd->gqd", jax.nn.softmax(s, axis=-1), vb, quant)

    def heads(group):
        qg, kg, vg = group                                  # (hb, T, .)
        q_rows = qg.reshape(hb, T // rb, rb, -1).transpose(1, 0, 2, 3)
        out = jax.lax.map(lambda a: block(a[0], kg, vg, a[1]),
                          (q_rows, jnp.arange(0, T, rb)))
        return out.transpose(1, 0, 2, 3).reshape(hb, T, -1)

    out = jax.lax.map(heads, tuple(
        a.reshape(G // hb, hb, T, a.shape[-1]) for a in (q, k, v)))
    return out.reshape(G, T, -1)


def mla(w, x, cfg, quant):
    B, T, d = x.shape
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    h = _norm_w(x, w["attn_norm"], eps)
    q = _mm("btd,de->bte", h, w["wq"], quant).reshape(B, T, H, dn + dr)
    kva = _mm("btd,de->bte", h, w["wkva"], quant)
    c_kv = _norm_w(kva[..., :r], w["kv_norm"], eps)
    kvb = _mm("btr,re->bte", c_kv, w["wkvb"], quant).reshape(B, T, H, dn + dv)
    q = q.transpose(0, 2, 1, 3)                             # (B, H, T, .)
    kvb = kvb.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], axis=-1)
    k_rope = _rope(kva[..., r:], cfg)[:, None]              # (B, 1, T, dr)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope, (B, H, T, dr))], axis=-1)
    att = _attention(q.reshape(B * H, T, dn + dr), k.reshape(B * H, T, -1),
                     kvb[..., dn:].reshape(B * H, T, dv), softmax_scale(cfg),
                     quant)
    att = att.reshape(B, H, T, dv).transpose(0, 2, 1, 3).reshape(B, T, H * dv)
    return x + _mm("bte,ed->btd", att, w["wo"], quant)


def _swiglu(u, wg, wu, wd, quant):
    hidden = jax.nn.silu(_mm("nd,df->nf", u, wg, quant)) * _mm(
        "nd,df->nf", u, wu, quant)
    return _mm("nf,fd->nd", hidden, wd, quant)


def route(u, w, cfg, quant):
    """(selected (N, E) bool, weight (N, E) float32, zero off the top-k)."""
    k = cfg["num_experts_per_tok"]
    sigma = jax.nn.sigmoid(_mm("nd,de->ne", u, w["router"], quant))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sigma) + w["router_bias"], k)
    selected = jnp.zeros(sigma.shape, bool).at[
        jnp.arange(sigma.shape[0])[:, None], idx].set(True)
    picked = jnp.where(selected, sigma, 0.0)
    weight = (cfg["routed_scaling_factor"] * picked
              / jnp.sum(picked, axis=-1, keepdims=True))
    return selected, weight


def ffn(w, y, cfg, kind, quant, held=None):
    """The layer's second half on (B, T, d). ``held``: the range of experts
    whose part is added (the configuration's share by default)."""
    B, T, d = y.shape
    u = _norm_w(y, w["ffn_norm"], cfg["rms_norm_eps"]).reshape(B * T, d)
    if kind == "dense":
        return y + _swiglu(u, w["wg"], w["wu"], w["wd"], quant).reshape(
            B, T, d)
    lo, hi = held_range(cfg)
    first, last = (lo, hi) if held is None else held
    _, weight = route(u, w, cfg, quant)
    out = _swiglu(u, w["shared_wg"], w["shared_wu"], w["shared_wd"], quant)

    @jax.checkpoint
    def expert(u, weight, wg, wu, wd, e):
        """w_e E_e(u) on every token (zero weight off the top-k)."""
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, e - lo, 0, False)
        w_e = jax.lax.dynamic_index_in_dim(weight, e, 1, False)
        return w_e[:, None] * _swiglu(u, pick(wg), pick(wu), pick(wd), quant)

    out, _ = jax.lax.scan(       # every token through every expert held
        lambda acc, e: (acc + expert(u, weight, w["moe_wg"], w["moe_wu"],
                                     w["moe_wd"], e), None),
        out, jnp.arange(first, last))
    return y + out.reshape(B, T, d)


def layer(w, x, cfg, kind, quant=False):
    return ffn(w, mla(w, x, cfg, quant), cfg, kind, quant)


def head_loss(w, x, targets, cfg, quant=False):
    logits = _mm("btd,dv->btv", _norm_w(x, w["final_norm"],
                                        cfg["rms_norm_eps"]),
                 w["out_w"], quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grads(cfg, p, tokens, targets):
    """The whole model at once (small sizes: the tests' witness): loss and
    every leaf's gradient, ``p`` the flat dict of float32 leaves."""
    kinds = layer_kinds(cfg)

    def loss(p):
        x = p["embed"][tokens]
        for li, kind in enumerate(kinds):
            w = {n: p["l%d_%s" % (li, n)] for n in layer_leaves(cfg, kind)}
            x = layer(w, x, cfg, kind)
        return head_loss(p, x, targets, cfg)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(p)


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


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
        return (loss, {k: w[k] - lr * dw[k] for k in w},
                {k: _norm(dw[k]) for k in w}, dx)

    @jax.jit
    def embed_update(embed, tokens, dx):
        g = jnp.zeros_like(embed).at[tokens].add(dx)
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
            w = {n: jnp.asarray(host[n]) for n in ("out_w", "final_norm")}
            loss, w, gn, dx = head(w, xs.pop(), targets)
            for n in w:
                host[n], g[n] = np.asarray(w[n]), gn[n]
            for li in reversed(range(len(kinds))):
                names = names_of(li)
                w = {n: jnp.asarray(host[full]) for n, full in names.items()}
                w, dx, gn = bwd[kinds[li]](w, xs.pop(), dx)
                for n, full in names.items():
                    host[full], g[full] = np.asarray(w[n]), gn[n]
                del w
            embed, g["embed"] = embed_update(jnp.asarray(host["embed"]),
                                             tokens, dx)
            host["embed"] = np.asarray(embed)
            del embed
            losses.append(float(loss))
            grad = grad or {k: float(v) for k, v in g.items()}
        change = {n: float(diff(jnp.asarray(host.pop(n)), make_leaf(n)))
                  for n in param_table(cfg)}
    return {"loss": losses, "grad": grad, "change": change}

"""Plain reference: a causal LM of grouped-query attention layers whose
head count, mask and rotary positions follow the layer's type (``full`` or
``sliding``), with a sigmoid gate a query head, a dense SwiGLU FFN where
``mlp_layer_types`` says ``dense`` and elsewhere sigmoid top-k routed
experts, a shared expert and ONE RANK'S SHARE of the routed sum — the
``laguna`` family's published keys — in float32 ``jax.numpy`` at
``highest`` matmul precision. Imports nothing of the program.

One layer, input ``x`` (B, T, d), ``H`` that layer's query heads over
``K = num_key_value_heads`` k/v heads, ``G = H / K``, ``n_w(x) = x *
rsqrt(mean(x^2) + eps) * w``:

- ``h = n_1(x)``; ``q = h W_q`` in H heads, ``k = h W_k`` and ``v = h W_v``
  in K heads, all ``head_dim`` wide; ``g = sigmoid(h W_g)``, one scalar a
  query head.
- positions: a full layer rotates the first ``partial_rotary_factor *
  head_dim`` channels of q and k by its yarn tables (interpolated
  frequencies below the correction range, plain above, a linear ramp
  between; cos and sin times ``attention_factor``) and passes the rest; a
  sliding layer rotates every channel at its own plain ``rope_theta``.
  Half-split pairing.
- mask: key ``j`` is visible to query ``i`` when ``j <= i``, and in a
  sliding layer when also ``j > i - sliding_window`` (the token itself
  counts).
- ``a_hq = softmax(q_hq . k_(hq // G) / sqrt(head_dim) + mask) v_(hq //
  G)``; ``y = x + concat_hq(g_hq * a_hq) W_o``.
- dense FFN: ``y + (silu(u W_g) * (u W_u)) W_d``, ``u = n_2(y)``.
- expert layer: ``s = sigmoid(u W_r)``; the k experts with the largest
  ``s`` (no expert bias); ``w_e = scale * s_e / sum_topk s``; out ``= y +
  S(u) + sum over (top-k and held) of w_e E_e(u)``, ``S`` the shared
  expert of its own width. The experts outside ``experts_held`` add
  nothing, here as in the program.
- head: ``n_f``, ``W_out``, float32 log-softmax over the vocabulary's
  slice, mean NLL; plain SGD.

Attention runs in blocks of query rows with k and v repeated to the query
heads (each block recomputed in the backward pass), every held expert is a
dense loop over all tokens, and the float32 leaves wait on the host between
layers, so that the model fits a 16 GB chip at its published widths.

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
    """One (mlp type, layer type, query heads) a layer."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["mlp_layer_types"][:n], cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def layer_leaves(cfg, kind):
    """short name -> (shape, init) of one layer's leaves."""
    mlp, _, H = kind
    d, hd, K = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    t = {"attn_norm": ((d,), 1.0), "wq": ((d, H * hd), _STD),
         "wk": ((d, K * hd), _STD), "wv": ((d, K * hd), _STD)}
    if cfg["gating"]:
        t["wgate"] = ((d, H), _STD)
    t.update({"wo": ((H * hd, d), _STD), "ffn_norm": ((d,), 1.0)})
    if mlp == "dense":
        f = cfg["intermediate_size"]
        t.update({"wg": ((d, f), _STD), "wu": ((d, f), _STD),
                  "wd": ((f, d), _STD)})
    else:
        f, fs = (cfg["moe_intermediate_size"],
                 cfg["shared_expert_intermediate_size"])
        lo, hi = held_range(cfg)
        E = cfg["published"]["num_experts"]
        t.update({"router": ((d, E), _STD),
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


def rope_tables(cfg, layer_type, T):
    """(cos, sin, rotated channels) of a layer type: (T, channels / 2)."""
    rp = cfg["rope_parameters"][layer_type]
    dim = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ratio = 1.0
    if rp.get("rope_type", "default") == "yarn":
        original = rp["original_max_position_embeddings"]

        def correction(rotations):      # the channel that turns that often
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction(rp["beta_fast"])), 0)
        high = min(math.ceil(correction(rp["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        inv = inv / rp["factor"] * ramp + inv * (1 - ramp)
        ratio = rp["attention_factor"]
    angle = np.arange(T, dtype=np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(angle) * ratio, jnp.float32),
            jnp.asarray(np.sin(angle) * ratio, jnp.float32), dim)


def _rope(x, tables):
    """Half-split rotation of the first ``dim`` channels of (..., T, hd)."""
    cos, sin, dim = tables
    a, b = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dim:]], axis=-1)


def _block_size(n, bound):
    return max(b for b in range(1, min(n, bound) + 1) if n % b == 0)


def _attention(q, k, v, scale, window, quant, row_block=256):
    """softmax(q k^T scale + mask) v on (B, H, T, hd) with k and v already
    repeated to the H query heads, dense, ``row_block`` query rows at a
    time (a ``lax.map``); a block's scores are recomputed in the backward
    pass. ``window`` None: every earlier key and the token's own."""
    B, H, T, hd = q.shape
    rb = _block_size(T, row_block)

    @jax.checkpoint
    def block(qb, first_row):
        s = _mm("bhqd,bhkd->bhqk", qb, k, quant) * scale
        i = first_row + jnp.arange(rb)[:, None]
        j = jnp.arange(T)[None, :]
        visible = j <= i
        if window is not None:
            visible = visible & (j > i - window)
        s = jnp.where(visible, s, -jnp.inf)
        return _mm("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v, quant)

    q_rows = q.reshape(B, H, T // rb, rb, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (q_rows, jnp.arange(0, T, rb)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, T, hd)


def attention(w, x, cfg, kind, quant):
    _, layer_type, H = kind
    B, T, d = x.shape
    K, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // K
    h = _norm_w(x, w["attn_norm"], cfg["rms_norm_eps"])
    heads = lambda y, n: y.reshape(B, T, n, hd).transpose(0, 2, 1, 3)
    q = heads(_mm("btd,de->bte", h, w["wq"], quant), H)
    k = heads(_mm("btd,de->bte", h, w["wk"], quant), K)
    v = heads(_mm("btd,de->bte", h, w["wv"], quant), K)
    tables = rope_tables(cfg, layer_type, T)
    q, k = _rope(q, tables), _rope(k, tables)
    # query head hq reads k/v head hq // G
    k, v = (jnp.repeat(a, G, axis=1) for a in (k, v))
    window = (cfg["sliding_window"] if layer_type == "sliding_attention"
              else None)
    att = _attention(q, k, v, hd ** -0.5, window, quant)
    if cfg["gating"]:
        gate = jax.nn.sigmoid(_mm("btd,dh->bth", h, w["wgate"], quant))
        att = att * gate.transpose(0, 2, 1)[..., None]
    att = att.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return x + _mm("bte,ed->btd", att, w["wo"], quant)


def _swiglu(u, wg, wu, wd, quant):
    hidden = jax.nn.silu(_mm("nd,df->nf", u, wg, quant)) * _mm(
        "nd,df->nf", u, wu, quant)
    return _mm("nf,fd->nd", hidden, wd, quant)


def route(u, w, cfg, quant):
    """(selected (N, E) bool, weight (N, E) float32, zero off the top-k)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("nd,de->ne", u, w["router"], quant))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s), k)
    selected = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    picked = jnp.where(selected, s, 0.0)
    weight = (cfg["moe_routed_scaling_factor"] * picked
              / jnp.sum(picked, axis=-1, keepdims=True))
    return selected, weight


def ffn(w, y, cfg, kind, quant, held=None, shared=True):
    """The layer's second half on (B, T, d). ``held``: the range of experts
    whose part is added (the configuration's share by default);
    ``shared``: whether the shared expert's is."""
    B, T, d = y.shape
    u = _norm_w(y, w["ffn_norm"], cfg["rms_norm_eps"]).reshape(B * T, d)
    if kind[0] == "dense":
        return y + _swiglu(u, w["wg"], w["wu"], w["wd"], quant).reshape(
            B, T, d)
    lo, hi = held_range(cfg)
    first, last = (lo, hi) if held is None else held
    _, weight = route(u, w, cfg, quant)
    out = (_swiglu(u, w["shared_wg"], w["shared_wu"], w["shared_wd"], quant)
           if shared else jnp.zeros_like(u))

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
    return ffn(w, attention(w, x, cfg, kind, quant), cfg, kind, quant)


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

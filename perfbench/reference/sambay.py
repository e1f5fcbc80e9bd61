"""Plain reference: a decoder-hybrid-decoder causal LM (SambaY,
arXiv:2507.06607; ``model_type: phi4flash``) of state-space layers
(Mamba-1, arXiv:2312.00752), differential attention (arXiv:2410.05258)
under a window or over the whole prefix, gated memory units on one
state-space layer's scan output and differential cross-attention onto one
layer's k and v, in float32 ``jax.numpy`` at ``highest`` matmul precision.
Imports nothing of the program.

``d`` the hidden size; every layer ``l`` (published index) is ``h = x +
Mix_l(LN(x)); out = h + MLP(LN'(h))`` with ``LN`` a LayerNorm (weight,
bias) and ``MLP(u) = (silu(u W_g) * (u W_u)) W_d``. With 2h published
layers and ``mb_per_layer`` 2, ``Mix_l`` is

- even ``l <= h`` — *Mamba* (``E = expand * d`` channels, ``N`` states,
  ``R = dt_rank``): ``[u | z] = a W_in``; ``u <- silu(conv(u) + b_c)``, the
  convolution causal, depthwise, ``d_conv`` wide; ``[dl | B_t | C_t] = u
  W_x``; ``delta = softplus(dl W_dt + b_dt)``; ``A = -exp(log(1..N) +
  a_log)`` (the leaf ``a_log`` is the offset from the family's start);
  ``h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) (x) B_t``, ``h_0 =
  0``; ``y_t = h_t C_t + D * u_t``; ``Mix = (y * silu(z)) W_out``. Layer
  ``h`` also hands on ``m = y``.
- odd ``l < h`` — *differential attention* under ``sliding_window`` (key
  ``j`` is seen by query ``i`` when ``i - window < j <= i``); ``l = h + 1``
  the same over the whole prefix, handing on its k and v. ``[q | k | v] =
  a W_qkv + b``; consecutive heads pair up, query pair ``j`` reads k/v pair
  ``j // G``; ``P_s = softmax(mask(q_2j+s k_2g+s^T / sqrt(hd)))``; ``O_j =
  (P_0 - lambda P_1) [v_2g | v_2g+1]``; ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3
  l)``; ``O_j <- rmsnorm(O_j; w, 1e-5) * (1 - lambda_init)``; ``Mix =
  concat_j(O_j) W_o + b_o``.
- even ``l > h`` — *gated memory unit*: ``Mix = (m * silu(a W_1)) W_2``.
- odd ``l > h + 1`` — differential *cross*-attention: ``q = a W_q + b_q``
  alone, k and v layer ``h + 1``'s.

No positional term anywhere. A final LayerNorm; logits ``= . embed^T``
(the tied head) over the vocabulary's slice; float32 log-softmax, mean
NLL; plain SGD. The configuration holds published layers
``deployment.first_layer`` onward.

The recurrence is a ``lax.scan`` over the time steps (blocks of them
recomputed in the backward pass), attention is dense in blocks of query
rows, and the float32 leaves wait on the host between layers, so that
three steps at 8,192 tokens fit a 16 GB chip.

``quant`` puts the control in its place: every matmul's operands, result
and their cotangents rounded to fp8 (e4m3, per-tensor scale).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .resnet_preact import store

_STD = ("normal", 0.02)
_LAMBDA_STD = ("normal", 0.1)


def sizes(cfg):
    a = cfg["assumed"]
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "E": a["mamba_expand"] * d, "N": a["mamba_d_state"],
            "R": a["mamba_dt_rank"], "K": a["mamba_d_conv"],
            "H": H, "Hkv": cfg["num_key_value_heads"], "hd": d // H,
            "f": cfg["intermediate_size"]}


def layer_kinds(cfg):
    """One (kind, published index) a layer held here; kinds ``mamba``,
    ``window``, ``full``, ``gmu``, ``cross``."""
    total = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    half = total // 2
    first = cfg.get("deployment", {}).get("first_layer", 0)
    out = []
    for pub in range(first, first + cfg["num_hidden_layers"]):
        if pub % cfg["mb_per_layer"] == 0:
            kind = "mamba" if pub <= half else "gmu"
        else:
            kind = ("window" if pub < half else
                    "full" if pub == half + 1 else "cross")
        out.append((kind, pub))
    return out


def makes(cfg, kind):
    """The shared value a layer hands on (None for most)."""
    half = cfg.get("published", {}).get(
        "num_hidden_layers", cfg["num_hidden_layers"]) // 2
    return {("mamba", half): "memory", ("full", half + 1): "kv"}.get(kind)


def takes(kind):
    return {"gmu": "memory", "cross": "kv"}.get(kind[0])


def layer_leaves(cfg, kind):
    """short name -> (shape, init) of one layer's leaves."""
    s = sizes(cfg)
    d, E, N, R, hd = s["d"], s["E"], s["N"], s["R"], s["hd"]
    t = {"attn_norm": ((d,), 1.0), "attn_norm_b": ((d,), 0.0)}
    if kind[0] == "mamba":
        t.update({"ssm_in": ((d, 2 * E), _STD),
                  "ssm_conv_w": ((s["K"], E), _STD),
                  "ssm_conv_b": ((E,), _STD),
                  "ssm_x": ((E, R + 2 * N), _STD),
                  "ssm_dt": ((R, E), _STD),
                  "ssm_dt_b": ((E,), math.log(math.expm1(0.01))),
                  "ssm_a_log": ((E, N), 0.0), "ssm_d": ((E,), 1.0),
                  "ssm_out": ((E, d), _STD)})
    elif kind[0] == "gmu":
        t.update({"gmu_in": ((d, E), _STD), "gmu_out": ((E, d), _STD)})
    else:
        dq, dkv = s["H"] * hd, s["Hkv"] * hd
        if kind[0] == "cross":
            t.update({"wq": ((d, dq), _STD), "bq": ((dq,), _STD)})
        else:
            t.update({"wqkv": ((d, dq + 2 * dkv), _STD),
                      "bqkv": ((dq + 2 * dkv,), _STD)})
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            t[name] = ((hd,), _LAMBDA_STD)
        t.update({"subln": ((2 * hd,), 1.0), "wo": ((dq, d), _STD),
                  "bo": ((d,), _STD)})
    t.update({"ffn_norm": ((d,), 1.0), "ffn_norm_b": ((d,), 0.0),
              "wg": ((d, s["f"]), _STD), "wu": ((d, s["f"]), _STD),
              "wd": ((s["f"], d), _STD)})
    return t


def param_table(cfg):
    """name -> (shape, init), in the order the seed's keys are folded in."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    table = {"embed": ((v, d), _STD), "final_norm": ((d,), 1.0),
             "final_norm_b": ((d,), 0.0)}
    for li, kind in enumerate(layer_kinds(cfg)):
        for name, spec in layer_leaves(cfg, kind).items():
            table["l%d_%s" % (li, name)] = spec
    return table


# --- the equations ---------------------------------------------------------
def _mm(spec, a, b, quant):
    return store(jnp.einsum(spec, store(a, quant), store(b, quant)), quant)


def _ln(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w + b


def _block_size(n, bound):
    return max(b for b in range(1, min(n, bound) + 1) if n % b == 0)


def recurrence(u, delta, A, Bm, Cm, block=256):
    """``y_t = h_t . C_t`` of ``h_t = exp(delta_t A) h_{t-1} + (delta_t
    u_t) (x) B_t`` on (B, T, E) and (B, T, N), one time step at a time;
    a block of steps is recomputed in the backward pass."""
    Bsz, T, E = u.shape
    tb = _block_size(T, block)

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[:, :, None] * A) * h
             + (d_t * u_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("ben,bn->be", h, c_t)

    @jax.checkpoint
    def steps(h, xs):
        return jax.lax.scan(step, h, xs)

    xs = tuple(x.transpose(1, 0, 2).reshape(T // tb, tb, Bsz, -1)
               for x in (u, delta, Bm, Cm))
    _, y = jax.lax.scan(steps, jnp.zeros((Bsz, E, A.shape[1]), u.dtype), xs)
    return y.reshape(T, Bsz, E).transpose(1, 0, 2)


def mamba(w, a, cfg, quant):
    """(Mix, y) of a state-space layer on the normed input ``a``."""
    s = sizes(cfg)
    E, N, R, K = s["E"], s["N"], s["R"], s["K"]
    uz = _mm("btd,de->bte", a, w["ssm_in"], quant)
    u, z = uz[..., :E], uz[..., E:]
    conv = jax.lax.conv_general_dilated(
        u, w["ssm_conv_w"][:, None, :], (1,), [(K - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=E)
    u = jax.nn.silu(conv + w["ssm_conv_b"])
    low = _mm("bte,er->btr", u, w["ssm_x"], quant)
    delta = jax.nn.softplus(
        _mm("btr,re->bte", low[..., :R], w["ssm_dt"], quant) + w["ssm_dt_b"])
    A = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=u.dtype))
                 + w["ssm_a_log"])
    y = recurrence(u, delta, A, low[..., R:R + N], low[..., R + N:])
    y = y + w["ssm_d"] * u
    return _mm("bte,ed->btd", y * jax.nn.silu(z), w["ssm_out"], quant), y


def _softmax_v(q, k, v, scale, window, quant, row_block=256):
    """softmax(q k^T scale + mask) v on q, k (B, H, T, hd) and v (B, H, T,
    dv), dense, ``row_block`` query rows at a time, each block recomputed
    in the backward pass. ``window`` None: every earlier key and the
    token's own."""
    B, H, T, hd = q.shape
    rb = _block_size(T, row_block)

    @jax.checkpoint
    def block(qb, first_row):
        sc = _mm("bhqd,bhkd->bhqk", qb, k, quant) * scale
        i = first_row + jnp.arange(rb)[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        sc = jnp.where(seen, sc, -jnp.inf)
        return _mm("bhqk,bhkd->bhqd", jax.nn.softmax(sc, axis=-1), v, quant)

    q_rows = q.reshape(B, H, T // rb, rb, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (q_rows, jnp.arange(0, T, rb)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, T, v.shape[-1])


def lambda_init(pub):
    return 0.8 - 0.6 * math.exp(-0.3 * pub)


def differential(w, a, cfg, kind, kv, quant):
    """(Mix, (k, v)) of a differential-attention layer; ``kv`` another
    layer's k and v (B, T, Hkv, hd) for a ``cross`` layer."""
    s = sizes(cfg)
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    B, T, _ = a.shape
    if kind[0] == "cross":
        q = _mm("btd,de->bte", a, w["wq"], quant) + w["bq"]
        k, v = kv
    else:
        qkv = _mm("btd,de->bte", a, w["wqkv"], quant) + w["bqkv"]
        q = qkv[..., :H * hd]
        k = qkv[..., H * hd:(H + Hkv) * hd].reshape(B, T, Hkv, hd)
        v = qkv[..., (H + Hkv) * hd:].reshape(B, T, Hkv, hd)
    q = q.reshape(B, T, H, hd)
    G = H // Hkv
    window = cfg["sliding_window"] if kind[0] == "window" else None
    # pair j of the query heads reads pair j // G of the k/v heads
    pairs_q = q.reshape(B, T, H // 2, 2, hd)
    pairs_k = jnp.repeat(k.reshape(B, T, Hkv // 2, 2, hd), G, axis=2)
    V = jnp.repeat(v.reshape(B, T, Hkv // 2, 2 * hd), G, axis=2)
    V = V.transpose(0, 2, 1, 3)
    maps = [_softmax_v(pairs_q[:, :, :, i].transpose(0, 2, 1, 3),
                       pairs_k[:, :, :, i].transpose(0, 2, 1, 3), V,
                       hd ** -0.5, window, quant) for i in (0, 1)]
    init = lambda_init(kind[1])
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + init)
    o = maps[0] - lam * maps[1]                       # (B, H/2, T, 2 hd)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5)
    o = o * w["subln"] * (1.0 - init)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return _mm("bte,ed->btd", o, w["wo"], quant) + w["bo"], (k, v)


def layer(w, x, shared, cfg, kind, quant=False):
    """(out, made): one layer on (B, T, d); ``shared`` holds the value it
    takes (``takes``), ``made`` the one it hands on (``makes``)."""
    eps = cfg["layer_norm_eps"]
    a = _ln(x, w["attn_norm"], w["attn_norm_b"], eps)
    made = {}
    if kind[0] == "mamba":
        mix, y = mamba(w, a, cfg, quant)
        made = {"memory": y}
    elif kind[0] == "gmu":
        gate = jax.nn.silu(_mm("btd,de->bte", a, w["gmu_in"], quant))
        mix = _mm("bte,ed->btd", shared["memory"] * gate, w["gmu_out"],
                  quant)
    else:
        mix, kv = differential(w, a, cfg, kind, shared.get("kv"), quant)
        made = {"kv": kv}
    h = x + mix
    u = _ln(h, w["ffn_norm"], w["ffn_norm_b"], eps)
    hidden = jax.nn.silu(_mm("btd,df->btf", u, w["wg"], quant)) * _mm(
        "btd,df->btf", u, w["wu"], quant)
    out = h + _mm("btf,fd->btd", hidden, w["wd"], quant)
    name = makes(cfg, kind)
    return out, ({name: made[name]} if name else {})


def head_loss(w, x, targets, cfg, quant=False):
    logits = _mm("btd,vd->btv", _ln(x, w["final_norm"], w["final_norm_b"],
                                    cfg["layer_norm_eps"]),
                 w["embed"], quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def forward(cfg, p, x, kinds=None, first=0):
    """The layers ``kinds`` (all held by default; the first of them the
    ``first``-th held) on a hidden state ``x``: the tests' handle."""
    shared = {}
    for li, kind in enumerate(kinds or layer_kinds(cfg), first):
        w = {n: p["l%d_%s" % (li, n)] for n in layer_leaves(cfg, kind)}
        took = takes(kind)
        x, made = layer(w, x, {took: shared[took]} if took else {}, cfg, kind)
        shared.update(made)
    return x


def loss_and_grads(cfg, p, tokens, targets):
    """The whole model at once (small sizes: the tests' witness): loss and
    every leaf's gradient, ``p`` the flat dict of float32 leaves."""
    def loss(p):
        return head_loss(p, forward(cfg, p, p["embed"][tokens]), targets,
                         cfg)

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
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
    fwd, bwd = [], []
    for kind in kinds:
        run = lambda w, x, shared, kind=kind: layer(w, x, shared, cfg, kind,
                                                    quant)
        fwd.append(jax.jit(run))

        def back(w, x, shared, dy, dmade, run=run):
            _, vjp = jax.vjp(run, w, x, shared)
            dw, dx, dshared = vjp((dy, dmade))
            return ({k: w[k] - lr * dw[k] for k in w}, dx, dshared,
                    {k: _norm(dw[k]) for k in w})

        bwd.append(jax.jit(back, donate_argnums=(0,)))

    head_names = ("embed", "final_norm", "final_norm_b")

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
            g, shared, took = {}, {}, []
            xs = [jnp.asarray(host["embed"])[tokens]]
            for li, kind in enumerate(kinds):
                w = {n: jnp.asarray(host[full])
                     for n, full in names_of(li).items()}
                name = takes(kind)
                took.append({name: shared[name]} if name else {})
                x, made = fwd[li](w, xs[-1], took[-1])
                xs.append(x)
                shared.update(made)
                del w
            w = {n: jnp.asarray(host[n]) for n in head_names}
            loss, dw, dx = head(w, xs.pop(), targets)
            for n in ("final_norm", "final_norm_b"):
                host[n], g[n] = np.asarray(w[n] - lr * dw[n]), _norm(dw[n])
            of_head = dw["embed"]
            del w, dw
            # what the readers send back to a shared value's maker
            owed = {}
            for li in reversed(range(len(kinds))):
                names = names_of(li)
                w = {n: jnp.asarray(host[full]) for n, full in names.items()}
                name = makes(cfg, kinds[li])
                dmade = {}
                if name:    # no reader here: nothing is owed
                    dmade[name] = (owed.pop(name) if name in owed else
                                   jax.tree_util.tree_map(jnp.zeros_like,
                                                          shared[name]))
                w, dx, dshared, gn = bwd[li](w, xs.pop(), took[li], dx, dmade)
                for n, d in dshared.items():
                    owed[n] = add(owed[n], d) if n in owed else d
                for n, full in names.items():
                    host[full], g[full] = np.asarray(w[n]), gn[n]
                del w
            embed, g["embed"] = embed_update(jnp.asarray(host["embed"]),
                                             of_head, tokens, dx)
            host["embed"] = np.asarray(embed)
            del embed, of_head, shared, took
            losses.append(float(loss))
            grad = grad or {k: float(v) for k, v in g.items()}
        change = {n: float(diff(jnp.asarray(host.pop(n)), make_leaf(n)))
                  for n in param_table(cfg)}
    return {"loss": losses, "grad": grad, "change": change}

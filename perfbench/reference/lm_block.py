"""Plain reference: the causal LM block the program trains (embedding,
weightless RMSNorm, dense causal attention, GELU FFN behind a one-expert
gate, untied head, float32 log-softmax loss, plain SGD) in float32
``jax.numpy`` at ``highest`` matmul precision. Imports nothing of the
program. It walks the layers one at a time, forward and then backward, and
applies each layer's update as soon as its gradient exists, so that the
1.4-billion-parameter model fits beside nothing else on a 16 GB chip.

``quant`` puts the control in its place: every matmul's operands, result
and their cotangents rounded to fp8 (e4m3, per-tensor scale).
"""
import math

import jax
import jax.numpy as jnp

from .resnet_preact import store

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2", "gate")


def param_table(cfg):
    """name -> (shape, ("normal", std)), in the order the seed's keys are
    folded in: the program's flat dict."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    e, std = cfg["n_experts"], ("normal", 0.02)
    table = {"embed": ((v, d), std), "out_w": ((d, v), std)}
    for li in range(cfg["num_hidden_layers"]):
        p = "l%d_" % li
        for name, shape in (("wq", (d, d)), ("wk", (d, d)), ("wv", (d, d)),
                            ("wo", (d, d)), ("w1", (e, d, f)),
                            ("w2", (e, f, d)), ("gate", (d, e))):
            table[p + name] = (shape, std)
    return table


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _mm(spec, a, b, quant):
    return store(jnp.einsum(spec, store(a, quant), store(b, quant)),
                  quant)


def layer(w, x, n_heads, quant):
    """One block on (B, T, d); ``w`` holds the seven leaves by short name."""
    B, T, d = x.shape
    hd = d // n_heads
    ln = _rms(x)
    q, k, v = (_mm("btd,de->bte", ln, w[n], quant)
               .reshape(B, T, n_heads, hd) for n in ("wq", "wk", "wv"))
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    att = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _mm("btd,de->bte", att.reshape(B, T, d), w["wo"], quant)
    ln = _rms(x)
    gate = jax.nn.softmax(ln @ w["gate"], axis=-1)
    hidden = jax.nn.gelu(_mm("btd,edf->btef", ln, w["w1"], quant))
    out = _mm("btef,efd->bted", hidden, w["w2"], quant)
    return x + jnp.einsum("bted,bte->btd", out, gate)


def head_loss(out_w, x, targets, quant):
    logits = _mm("btd,dv->btv", _rms(x), out_w, quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def three_steps(cfg, make_leaf, batches, quant=False):
    """Follow the program's first steps. ``make_leaf(name)`` gives a leaf's
    float32 starting value (made again from the seed, not handed over by
    the program); ``batches`` is a list of (tokens, targets) int32 (B, T).
    Returns the numbers the comparison reads, as host floats."""
    lr = cfg["optimizer"]["learning_rate"]
    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]

    fwd = jax.jit(lambda w, x: layer(w, x, H, quant))

    @jax.jit
    def bwd(w, x, dy):
        _, vjp = jax.vjp(lambda w, x: layer(w, x, H, quant), w, x)
        dw, dx = vjp(dy)
        return ({k: w[k] - lr * dw[k] for k in w}, dx,
                {k: _norm(dw[k]) for k in w})

    @jax.jit
    def head(out_w, x, targets):
        loss, (dw, dx) = jax.value_and_grad(
            lambda o, x: head_loss(o, x, targets, quant), (0, 1))(out_w, x)
        return loss, out_w - lr * dw, _norm(dw), dx

    @jax.jit
    def embed_update(embed, tokens, dx):
        g = jnp.zeros_like(embed).at[tokens].add(dx)
        return embed - lr * g, _norm(g)

    diff = jax.jit(lambda a, b: _norm(a - b))

    with jax.default_matmul_precision("highest"):
        names = ["embed", "out_w"] + ["l%d_%s" % (li, n) for li in range(L)
                                      for n in LAYER_LEAVES]
        p = {n: make_leaf(n) for n in names}
        losses, grad = [], None
        for tokens, targets in batches:
            g = {}
            xs = [p["embed"][tokens]]
            for li in range(L):
                w = {n: p["l%d_%s" % (li, n)] for n in LAYER_LEAVES}
                xs.append(fwd(w, xs[-1]))
            loss, p["out_w"], g["out_w"], dx = head(p["out_w"], xs.pop(),
                                                    targets)
            for li in reversed(range(L)):
                w = {n: p.pop("l%d_%s" % (li, n)) for n in LAYER_LEAVES}
                w, dx, gn = bwd(w, xs.pop(), dx)
                for n in LAYER_LEAVES:
                    p["l%d_%s" % (li, n)] = w[n]
                    g["l%d_%s" % (li, n)] = gn[n]
            p["embed"], g["embed"] = embed_update(p["embed"], tokens, dx)
            losses.append(float(loss))
            grad = grad or {k: float(v) for k, v in g.items()}
        change = {n: float(diff(p.pop(n), make_leaf(n))) for n in names}
    return {"loss": losses, "grad": grad, "change": change}

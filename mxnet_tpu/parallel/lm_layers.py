"""Layer kinds a :class:`~.transformer.TransformerParallel` can be built
from beside its first block: latent attention (``"mla"``), grouped-query
attention whose heads, window and rotary tables are the layer's own
(``"gqa"``), a state-space mixer (``"ssm"``: a selective scan behind a
causal convolution), a gated memory unit (``"gmu"``: a gate on another
layer's scan output), differential attention (``"diff"``: the difference
of two softmax maps, on the layer's own k and v or on another layer's), a
SwiGLU FFN (``"swiglu"``) and a routed expert layer that is told which
experts it holds (``"moe"``). Each kind is its leaves (name -> (shape,
init)) and its forward on (B, T, d).

A norm is ``x * rsqrt(mean(x^2) + eps) * w`` with a learned ``w`` — or,
where the architecture states ``layer_norm_eps``, a LayerNorm with weight
and bias — computed in float32. Positions are rotary (``rope_tables``), in the
yarn scaling where the architecture states one (by ``mscale`` ratios as
DeepSeek writes it, or by an ``attention_factor``), on all of a head's
channels or on its first ``partial_rotary_factor`` of them; the pairing
is half-split (``rotate_half``). docs/lm_layers.md has the equations.
"""
from __future__ import annotations

import math

import numpy as np

from ..observability import counter, device_scope
from . import moe as _moe

__all__ = ["ATTENTION_KINDS", "FFN_KINDS", "KEPT_BY_A_RECOMPUTED_LAYER",
           "kept", "recomputed", "layer_table", "rope_tables",
           "yarn_inv_freq", "yarn_mscale", "mla_scale", "rms_norm",
           "layer_norm", "norm", "apply_rope", "gqa_attention", "ssm_mixer",
           "gated_memory", "diff_attention", "reads", "publishes"]

ATTENTION_KINDS = ("mha", "mla", "gqa", "ssm", "gmu", "diff")
FFN_KINDS = ("soft_moe", "swiglu", "moe")
#: what a layer that the backward pass recomputes (``remat=True``) keeps of
#: its forward beside its inputs, by the names :func:`kept` gives the values
#: where they are made: cheap to hold and dear to make again, in the order
#: of time bought per byte held (docs/lm_layers.md, Recomputation). The
#: first two are the flash kernel's own (``flash_attention.py``). All else
#: (norms, k and v, SwiGLU's elementwise part, the routed block) is made
#: again from these and the layer's input
KEPT_BY_A_RECOMPUTED_LAYER = (
    "flash_out", "flash_lse",
    "router_logits", "route_idx", "route_weight", "moe_plan", "mla_kva",
    "gqa_gate", "gqa_k", "gqa_v",
    "attn_residual",
    "mla_q", "gqa_q",
    "ffn_gate", "ffn_up",
    "shared_gate", "shared_up",
    "ssm_y", "ssm_starts", "ssm_in", "gmu_gate",
    "diff_q", "diff_k", "diff_v")

_NORMAL = ("normal", 0.02)
#: a state-space layer's step bias at the start: softplus^-1(0.01)
SSM_DT_BIAS = math.log(math.expm1(0.01))


# --- leaves ------------------------------------------------------------
def norm_leaves(name, d, arch):
    """A norm's leaves under ``name``: its weight, and under an
    architecture of LayerNorms (``layer_norm_eps``) its bias."""
    t = {name: ((d,), 1.0)}
    if "layer_norm_eps" in arch:
        t[name + "_b"] = ((d,), 0.0)
    return t


def layer_table(li, kinds, cfg, arch):
    """name -> (shape, init) of layer ``li``'s leaves, in a fixed order."""
    attn, ffn = kinds
    p = "l%d_" % li
    d = cfg["d_model"]
    H = cfg["n_heads"]
    t = {}
    if attn == "ssm":
        m = arch["ssm"]
        E, N, R = m["d_inner"], m["d_state"], m["dt_rank"]
        t.update(norm_leaves(p + "attn_norm", d, arch))
        t[p + "ssm_in"] = ((d, 2 * E), _NORMAL)
        t[p + "ssm_conv_w"] = ((m["d_conv"], E), _NORMAL)
        t[p + "ssm_conv_b"] = ((E,), _NORMAL)
        t[p + "ssm_x"] = ((E, R + 2 * N), _NORMAL)
        t[p + "ssm_dt"] = ((R, E), _NORMAL)
        t[p + "ssm_dt_b"] = ((E,), SSM_DT_BIAS)
        # A = -exp(log(1..N) + this leaf): the family's start, held as the
        # offset from it (the same function of the leaf, the same gradient)
        t[p + "ssm_a_log"] = ((E, N), 0.0)
        t[p + "ssm_d"] = ((E,), 1.0)
        t[p + "ssm_out"] = ((E, d), _NORMAL)
    if attn == "gmu":
        E = arch["ssm"]["d_inner"]
        t.update(norm_leaves(p + "attn_norm", d, arch))
        t[p + "gmu_in"] = ((d, E), _NORMAL)
        t[p + "gmu_out"] = ((E, d), _NORMAL)
    if attn == "diff":
        g = arch["diff"]
        hd = g["head_dim"]
        dq, dkv = g["n_heads"] * hd, g["n_kv_heads"] * hd
        t.update(norm_leaves(p + "attn_norm", d, arch))
        if g["layers"][li]["cross"]:
            t[p + "wq"] = ((d, dq), _NORMAL)
            t[p + "bq"] = ((dq,), _NORMAL)
        else:
            t[p + "wqkv"] = ((d, dq + 2 * dkv), _NORMAL)
            t[p + "bqkv"] = ((dq + 2 * dkv,), _NORMAL)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            t[p + name] = ((hd,), ("normal", 0.1))
        t[p + "subln"] = ((2 * hd,), 1.0)
        t[p + "wo"] = ((dq, d), _NORMAL)
        t[p + "bo"] = ((d,), _NORMAL)
    if attn == "mla":
        dq = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
        r = arch["kv_lora_rank"]
        t[p + "attn_norm"] = ((d,), 1.0)
        t[p + "wq"] = ((d, H * dq), _NORMAL)
        t[p + "wkva"] = ((d, r + arch["qk_rope_head_dim"]), _NORMAL)
        t[p + "kv_norm"] = ((r,), 1.0)
        t[p + "wkvb"] = ((r, H * (arch["qk_nope_head_dim"]
                                  + arch["v_head_dim"])), _NORMAL)
        t[p + "wo"] = ((H * arch["v_head_dim"], d), _NORMAL)
    if attn == "gqa":
        g = arch["gqa"]
        H, hd = g["layers"][li]["n_heads"], g["head_dim"]
        t[p + "attn_norm"] = ((d,), 1.0)
        t[p + "wq"] = ((d, H * hd), _NORMAL)
        t[p + "wk"] = ((d, g["n_kv_heads"] * hd), _NORMAL)
        t[p + "wv"] = ((d, g["n_kv_heads"] * hd), _NORMAL)
        if g["gate"]:
            t[p + "wgate"] = ((d, H), _NORMAL)
        t[p + "wo"] = ((H * hd, d), _NORMAL)
    if ffn == "swiglu":
        f = cfg["d_ff"]
        t.update(norm_leaves(p + "ffn_norm", d, arch))
        t[p + "wg"] = ((d, f), _NORMAL)
        t[p + "wu"] = ((d, f), _NORMAL)
        t[p + "wd"] = ((f, d), _NORMAL)
    if ffn == "moe":
        m = arch["moe"]
        f = m["d_expert"]
        fs = m.get("d_shared", f * m["n_shared"])   # the shared width
        lo, hi = m["experts_held"]
        t[p + "ffn_norm"] = ((d,), 1.0)
        t[p + "router"] = ((d, m["n_experts"]), _NORMAL)
        if m.get("router_bias", True):
            t[p + "router_bias"] = ((m["n_experts"],), _NORMAL)
        t[p + "shared_wg"] = ((d, fs), _NORMAL)
        t[p + "shared_wu"] = ((d, fs), _NORMAL)
        t[p + "shared_wd"] = ((fs, d), _NORMAL)
        t[p + "moe_wg"] = ((hi - lo, d, f), _NORMAL)
        t[p + "moe_wu"] = ((hi - lo, d, f), _NORMAL)
        t[p + "moe_wd"] = ((hi - lo, f, d), _NORMAL)
    return t


# --- norms and positions -----------------------------------------------
def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def norm(params, name, x, arch):
    """The norm whose leaves stand under ``name``: a LayerNorm where it
    has a bias (``norm_leaves``), else the RMS norm."""
    if name + "_b" in params:
        return layer_norm(x, params[name], params[name + "_b"],
                          arch["layer_norm_eps"])
    return rms_norm(x, params[name], arch["rms_norm_eps"])


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, rope):
    """Inverse frequencies of the ``dim`` rotary channels (dim/2 of them):
    plain ``theta^(-2i/dim)`` without ``factor``; under ``deepseek_yarn``
    the interpolated ones (``/ factor``) below the correction range, the
    plain ones above it and a linear ramp between."""
    theta = float(rope["theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = float(rope.get("factor", 1.0))
    if factor <= 1:
        return plain

    def correction_dim(rotations):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def rope_tables(T, dim, rope):
    """(cos, sin), each (T, dim/2) float32, scaled by the architecture's
    ``attention_factor`` where it states one, else by the yarn ratio
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    angle = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(
        dim, rope)[None, :]
    factor = float(rope.get("factor", 1.0))
    if "attention_factor" in rope:
        ratio = float(rope["attention_factor"])
    else:
        ratio = (yarn_mscale(factor, rope.get("mscale", 1.0))
                 / yarn_mscale(factor, rope.get("mscale_all_dim", 0.0) or 0.0)
                 if factor > 1 else 1.0)
    return ((np.cos(angle) * ratio).astype(np.float32),
            (np.sin(angle) * ratio).astype(np.float32))


def apply_rope(x, cos, sin):
    """Half-split rotation of the last axis: ``x`` (..., T, dim) with
    tables (T, dim/2) broadcast over the leading axes."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def mla_scale(arch):
    """Softmax scale: ``q_head_dim^-1/2 * m^2`` with ``m`` the yarn mscale
    of ``mscale_all_dim`` (1 without yarn)."""
    dq = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    rope = arch["rope"]
    m = (yarn_mscale(float(rope.get("factor", 1.0)),
                     rope.get("mscale_all_dim", 0.0))
         if rope.get("mscale_all_dim") else 1.0)
    return dq ** -0.5 * m * m


# --- what a recomputed layer keeps ---------------------------------------
_recomputed_layers = []     # one entry a recomputed layer being traced


def recomputed(layer):
    """``layer`` as ``jax.checkpoint`` is to wrap it: while it is traced,
    the bytes of what it keeps by name go to ``remat.kept_bytes``."""
    def counted(*args):
        _recomputed_layers.append(True)
        try:
            return layer(*args)
        finally:
            _recomputed_layers.pop()

    return counted


def kept(value, name):
    """``value`` under a name of ``KEPT_BY_A_RECOMPUTED_LAYER``: a layer
    that is recomputed holds it for its backward pass in place of making
    it again; anywhere else the name is an identity that lowers to
    nothing."""
    from jax.ad_checkpoint import checkpoint_name

    if name not in KEPT_BY_A_RECOMPUTED_LAYER:
        raise ValueError("%r is not kept by a recomputed layer" % (name,))
    if _recomputed_layers:
        counter("remat.kept_bytes").inc(value.size * value.dtype.itemsize)
    return checkpoint_name(value, name)


# --- forwards ----------------------------------------------------------
def mla_attention(params, li, x, cfg, arch, attend):
    """Latent attention's residual branch on (B, T, d); ``attend(q, k, v,
    scale, rope=)`` takes (B, H, T, .) operands, q and k unrotated, and
    rotates their last ``qk_rope_head_dim`` channels by ``rope``'s tables
    (``flash_attention``'s operand)."""
    import jax.numpy as jnp

    p = "l%d_" % li
    B, T, _ = x.shape
    H = cfg["n_heads"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    r = arch["kv_lora_rank"]
    eps = arch["rms_norm_eps"]
    with device_scope("l%d/attn/proj" % li):
        h = rms_norm(x, params[p + "attn_norm"], eps)
        q = kept(jnp.einsum("btd,dhe->bhte", h,
                            params[p + "wq"].reshape(-1, H, dn + dr)),
                 "mla_q")                  # unrotated: the kernel rotates
        kva = kept(h @ params[p + "wkva"], "mla_kva")
        c_kv = rms_norm(kva[..., :r], params[p + "kv_norm"], eps)
        kvb = jnp.einsum("btr,rhe->bhte", c_kv,
                         params[p + "wkvb"].reshape(r, H, dn + dv))
        k = jnp.concatenate(
            [kvb[..., :dn],
             jnp.broadcast_to(kva[:, None, :, r:], (B, H, T, dr))], axis=-1)
        v = kvb[..., dn:]
    cos, sin = rope_tables(T, dr, arch["rope"])
    with device_scope("l%d/attn/flash" % li):
        att = attend(q, k, v, mla_scale(arch),
                     rope=(cos, sin, dn))                  # (B, H, T, dv)
    with device_scope("l%d/attn/out" % li):
        return jnp.einsum("bhte,hed->btd", att,
                          params[p + "wo"].reshape(H, dv, -1))


def gqa_attention(params, li, x, arch, attend):
    """Grouped-query attention's residual branch on (B, T, d): layer
    ``li``'s own head count over the model's k/v heads, its rotary tables
    on the first ``partial_rotary_factor`` of each head's channels, its
    window (None: causal), and one sigmoid gate a query head on the
    head's output before the out-projection. ``attend(q, k, v, scale,
    window, rope=)`` takes q (B, H, T, hd) and k, v (B, Hkv, T, hd), q
    and k unrotated, and rotates their first ``rot`` channels by
    ``rope``'s tables (``flash_attention``'s operand)."""
    import jax
    import jax.numpy as jnp

    p = "l%d_" % li
    g = arch["gqa"]
    mine = g["layers"][li]
    H, Hkv, hd = mine["n_heads"], g["n_kv_heads"], g["head_dim"]
    T = x.shape[1]
    with device_scope("l%d/attn/proj" % li):
        h = rms_norm(x, params[p + "attn_norm"], arch["rms_norm_eps"])
        q = kept(jnp.einsum("btd,dhe->bhte", h,
                            params[p + "wq"].reshape(-1, H, hd)), "gqa_q")
        k = kept(jnp.einsum("btd,dhe->bhte", h,
                            params[p + "wk"].reshape(-1, Hkv, hd)), "gqa_k")
        v = kept(jnp.einsum("btd,dhe->bhte", h,
                            params[p + "wv"].reshape(-1, Hkv, hd)), "gqa_v")
    # the first ``rot`` channels of q and k turn; the rest pass
    rot = int(hd * mine["rope"].get("partial_rotary_factor", 1))
    cos, sin = rope_tables(T, rot, mine["rope"])
    with device_scope("l%d/attn/flash" % li):
        att = attend(q, k, v, hd ** -0.5, mine["window"],
                     rope=(cos, sin, 0))                    # (B, H, T, hd)
    if g["gate"]:
        with device_scope("l%d/attn/gate" % li):
            gate = jax.lax.logistic(kept(
                jnp.einsum("btd,dh->bht", h, params[p + "wgate"]),
                "gqa_gate").astype(jnp.float32))
            att = (att.astype(jnp.float32) * gate[..., None]).astype(
                att.dtype)
    with device_scope("l%d/attn/out" % li):
        return jnp.einsum("bhte,hed->btd", att,
                          params[p + "wo"].reshape(H, hd, -1))


# --- values one layer makes and later layers read -------------------------
def publishes(li, kind, arch):
    """Which shared values layer ``li`` hands on: ``"memory"`` (a
    state-space layer's scan output, before its gate) or ``"kv"`` (a
    differential layer's k and v), where ``arch["shared"]`` names it the
    maker."""
    shared = arch.get("shared", {})
    return tuple(name for name, of in (("memory", "ssm"), ("kv", "diff"))
                 if kind == of and shared.get(name) == li)


def reads(li, kind, arch):
    """Which shared values layer ``li`` takes in: a gated memory unit the
    memory, a differential layer without k and v of its own the k/v."""
    if kind == "gmu":
        return ("memory",)
    if kind == "diff" and arch["diff"]["layers"][li]["cross"]:
        return ("kv",)
    return ()


def ssm_mixer(params, li, x, arch):
    """A state-space layer's residual branch on (B, T, d), and its scan
    output ``y`` (B, T, E) before the gate (what a gated memory unit
    reads): in-projection to ``[u | z]``, a causal depthwise convolution
    and ``silu`` on ``u``, the step ``delta = softplus(. W_dt + b)`` from a
    ``dt_rank``-wide projection of ``u``, which also gives ``B_t`` and
    ``C_t``, the selective scan (``ssm_scan``), the gate ``silu(z)`` and
    the out-projection. ``delta`` and ``A`` are float32."""
    import jax
    import jax.numpy as jnp

    from .ssm_scan import ssm_scan

    p = "l%d_" % li
    m = arch["ssm"]
    E, N, R, K = m["d_inner"], m["d_state"], m["dt_rank"], m["d_conv"]
    T = x.shape[1]
    f32 = jnp.float32
    with device_scope("l%d/ssm/proj" % li):
        a = norm(params, p + "attn_norm", x, arch)
        uz = kept(a @ params[p + "ssm_in"], "ssm_in")
        u, z = uz[..., :E], uz[..., E:]
    with device_scope("l%d/ssm/conv" % li):
        padded = jnp.pad(u.astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
        w = params[p + "ssm_conv_w"].astype(f32)
        conv = sum(w[k] * padded[:, k:k + T] for k in range(K))
        conv = conv + params[p + "ssm_conv_b"].astype(f32)
        u = (conv * jax.lax.logistic(conv)).astype(x.dtype)
    with device_scope("l%d/ssm/proj" % li):
        low = u @ params[p + "ssm_x"]
        delta = jax.nn.softplus(
            jnp.dot(low[..., :R], params[p + "ssm_dt"],
                    preferred_element_type=f32)
            + params[p + "ssm_dt_b"].astype(f32))
        A = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=f32))
                     + params[p + "ssm_a_log"].astype(f32))
    with device_scope("l%d/ssm/scan" % li):
        y = kept(ssm_scan(u, delta, A, low[..., R:R + N], low[..., R + N:],
                          params[p + "ssm_d"]), "ssm_y")
    with device_scope("l%d/ssm/out" % li):
        z32 = z.astype(f32)
        gated = (y.astype(f32) * z32 * jax.lax.logistic(z32)).astype(x.dtype)
        return gated @ params[p + "ssm_out"], y


def gated_memory(params, li, x, memory, arch):
    """A gated memory unit's residual branch: ``(memory * silu(a W_1))
    W_2`` with ``memory`` (B, T, E) another layer's scan output."""
    import jax
    import jax.numpy as jnp

    p = "l%d_" % li
    counter("lm_layers.shared_readers").inc()
    with device_scope("l%d/gmu" % li):
        a = norm(params, p + "attn_norm", x, arch)
        gate = kept(a @ params[p + "gmu_in"], "gmu_gate").astype(jnp.float32)
        gated = (memory.astype(jnp.float32) * gate
                 * jax.lax.logistic(gate)).astype(x.dtype)
        return gated @ params[p + "gmu_out"]


def diff_attention(params, li, x, arch, attend, kv=None):
    """Differential attention's residual branch on (B, T, d), and the k
    and v it attended. Consecutive heads pair up: query pair ``j`` is
    heads ``(2j, 2j + 1)``, k/v pair ``g`` likewise with ``V_g = [v_2g |
    v_2g+1]``, and pair ``j`` reads pair ``j // group``. ``O_j = P_0 V_g -
    lambda P_1 V_g`` with ``P_s = softmax(mask(q_2j+s k_2g+s^T /
    sqrt(hd)))``, then an RMS norm over ``O_j``'s ``2 hd`` channels times
    ``1 - lambda_init``. Each ``P_s V_g`` is one ``attend(q_s, k_s, V,
    scale, window)`` with q (B, H/2, T, hd), k (B, Hkv/2, T, hd), V (B,
    Hkv/2, T, 2 hd). ``kv``: another layer's ``((k_0, k_1), V)`` in place
    of this layer's own (a layer with ``cross`` has no k/v projection)."""
    import jax.numpy as jnp

    p = "l%d_" % li
    g = arch["diff"]
    mine = g["layers"][li]
    H, Hkv, hd = g["n_heads"] // 2, g["n_kv_heads"] // 2, g["head_dim"]
    f32 = jnp.float32
    counter("flash_attention.differential").inc()

    def heads(h, w, b, n, width):
        """``h w + b`` as (B, n, T, width) heads."""
        return (jnp.einsum("btd,dhe->bhte", h, w.reshape(-1, n, width))
                + b.reshape(1, n, 1, width))

    with device_scope("l%d/attn/proj" % li):
        h = norm(params, p + "attn_norm", x, arch)
        if mine["cross"]:
            counter("lm_layers.shared_readers").inc()
            wq, bq = params[p + "wq"], params[p + "bq"]
            ks, v = kv
        else:
            dq, dkv = 2 * H * hd, 2 * Hkv * hd
            w, b = params[p + "wqkv"], params[p + "bqkv"]
            wq, bq = w[:, :dq], b[:dq]
            wk = w[:, dq:dq + dkv].reshape(-1, Hkv, 2, hd)
            bk = b[dq:dq + dkv].reshape(Hkv, 2, hd)
            ks = tuple(kept(heads(h, wk[:, :, s], bk[:, s], Hkv, hd),
                            "diff_k") for s in (0, 1))
            v = kept(heads(h, w[:, dq + dkv:], b[dq + dkv:], Hkv, 2 * hd),
                     "diff_v")
        wq, bq = wq.reshape(-1, H, 2, hd), bq.reshape(H, 2, hd)
        qs = tuple(kept(heads(h, wq[:, :, s], bq[:, s], H, hd), "diff_q")
                   for s in (0, 1))
    with device_scope("l%d/attn/flash" % li):
        outs = [attend(qs[s], ks[s], v, hd ** -0.5, mine["window"])
                for s in (0, 1)]                       # (B, H, T, 2 hd)
    with device_scope("l%d/attn/diff" % li):
        lam = (jnp.exp(jnp.sum(params[p + "lambda_q1"].astype(f32)
                               * params[p + "lambda_k1"].astype(f32)))
               - jnp.exp(jnp.sum(params[p + "lambda_q2"].astype(f32)
                                 * params[p + "lambda_k2"].astype(f32)))
               + mine["lambda_init"])
        o = outs[0].astype(f32) - lam * outs[1].astype(f32)
        o = (rms_norm(o, params[p + "subln"], g["subln_eps"])
             * (1.0 - mine["lambda_init"])).astype(x.dtype)
    with device_scope("l%d/attn/out" % li):
        out = jnp.einsum("bhte,hed->btd", o,
                         params[p + "wo"].reshape(H, 2 * hd, -1))
        return out + params[p + "bo"], (ks, v)


def _swiglu(u, wg, wu, wd, whose):
    """``(silu(u wg) * (u wu)) wd`` with the two matmuls' outputs kept
    under ``whose``'s names. ``silu`` is written out: the jitted
    ``jax.nn.silu`` of a kept value would hold its own residual, as large
    as the value, where the elementwise part is to be made again."""
    import jax

    gate = kept(u @ wg, whose + "_gate")
    return (gate * jax.lax.logistic(gate)
            * kept(u @ wu, whose + "_up")) @ wd


def swiglu_ffn(params, li, x, arch):
    p = "l%d_" % li
    with device_scope("l%d/ffn" % li):
        u = norm(params, p + "ffn_norm", x, arch)
        return _swiglu(u, params[p + "wg"], params[p + "wu"],
                       params[p + "wd"], "ffn")


def moe_ffn(params, li, x, arch):
    """Shared expert plus this rank's part of the routed sum on (B, T, d),
    and what the router sent here: each held expert's pairs ``counts``
    (held,) and the tiles its layout needs ``live_tiles`` (1,), int32."""
    import jax
    import jax.numpy as jnp

    p = "l%d_" % li
    m = arch["moe"]
    held = tuple(m["experts_held"])
    tile = _moe.GMM_BLOCK_ROWS
    B, T, d = x.shape
    u = rms_norm(x, params[p + "ffn_norm"], arch["rms_norm_eps"])
    rows_in = u.reshape(B * T, d)
    with device_scope("l%d/moe/router" % li):
        logits = kept(jnp.dot(rows_in.astype(jnp.float32),
                              params[p + "router"].astype(jnp.float32),
                              precision="highest"), "router_logits")
        idx = kept(_moe.select(logits, params.get(p + "router_bias"),
                               m["top_k"]), "route_idx")
        weight = kept(_moe.weigh(logits, idx, m["scale"]), "route_weight")
    with device_scope("l%d/moe/dispatch" % li):
        plan = _moe.plan_dispatch(idx, held, tile)
        compact = _moe.plan_dispatch(idx, held, tile, _moe.compact_row_budget(
            B * T, m["top_k"], held[1] - held[0], m["n_experts"], tile))
        plan, compact = jax.tree_util.tree_map(
            lambda a: kept(a, "moe_plan"), (plan, compact))
        counter("moe.experts_held").inc(held[1] - held[0])
        counter("moe.row_budget").inc(plan["pair_of_row"].shape[0])
        counter("moe.compact_row_budget").inc(compact["pair_of_row"].shape[0])

    def routed(plan, rows_in, weight, wg, wu, wd):
        with device_scope("l%d/moe/dispatch" % li):
            rows = _moe.dispatch(rows_in, plan)
        with device_scope("l%d/moe/experts" % li):
            act = (jax.nn.silu(_moe.gmm(rows, wg, plan))
                   * _moe.gmm(rows, wu, plan))
            out_rows = _moe.gmm(act, wd, plan)
        with device_scope("l%d/moe/combine" % li):
            return _moe.combine(out_rows, weight, plan)

    out = _moe.in_the_layout_that_fits(
        routed, compact, plan, rows_in, weight,
        *(params[p + n] for n in ("moe_wg", "moe_wu", "moe_wd")))
    with device_scope("l%d/moe/shared" % li):
        shared = _swiglu(u, params[p + "shared_wg"], params[p + "shared_wu"],
                         params[p + "shared_wd"], "shared")
    with device_scope("l%d/moe/combine" % li):
        return (shared + out.reshape(B, T, d),
                {"counts": plan["counts"], "live_tiles": plan["n_live"]})

"""Operations and bytes a grouped-query model with windowed and full
layers and routed experts NEEDS, from the configuration's shapes, as
``flops_mla_moe.py`` has them for latent attention: recomputed work is
never counted, a multiply-accumulate is 2 operations, a backward pass
costs twice its forward, routed work is counted at its expectation (a
token's ``num_experts_per_tok`` pairs fall on the held experts with
probability held / published). A full layer's attention is the causal
half square; a sliding layer's **the band only**: query ``i`` sees
``min(i + 1, sliding_window)`` keys."""


def layers_of(cfg):
    """(mlp type, layer type, query heads) a layer."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["mlp_layer_types"][:n], cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def attention_matmul_params(cfg, heads):
    """W_q, W_k, W_v, W_o and the gate of a layer with ``heads`` query
    heads (the norms are no matmuls)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    return (2 * d * heads * hd + 2 * d * kv
            + (d * heads if cfg["gating"] else 0))


def visible_pairs(seq, window=None):
    """(query, key) pairs of one head: the causal half square with its
    diagonal, or under a window the band ``sum_i min(i + 1, window)``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def window_of(cfg, layer_type):
    return (cfg["sliding_window"] if layer_type == "sliding_attention"
            else None)


def expected_pairs_per_token(cfg):
    """(token, expert) pairs a token sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def expert_matmul_params(cfg):
    """One routed expert's SwiGLU: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops_per_token(cfg, seq):
    """By part, one token's forward pass at sequence length ``seq``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    parts = dict.fromkeys(("attn_proj", "full_scores", "window_scores",
                           "dense_ffn", "router", "shared", "routed"), 0)
    for mlp, layer_type, heads in layers_of(cfg):
        parts["attn_proj"] += 2 * attention_matmul_params(cfg, heads)
        window = window_of(cfg, layer_type)
        # q k^T and p v: 2 matmuls x 2 operations x hd a visible pair
        parts["window_scores" if window else "full_scores"] += (
            heads * 4 * hd * visible_pairs(seq, window) / seq)
        if mlp == "dense":
            parts["dense_ffn"] += 2 * 3 * d * cfg["intermediate_size"]
        else:
            parts["router"] += 2 * d * cfg["published"]["num_experts"]
            parts["shared"] += 2 * 3 * d * cfg[
                "shared_expert_intermediate_size"]
            parts["routed"] += (2 * expected_pairs_per_token(cfg)
                                * expert_matmul_params(cfg))
    parts["head"] = 2 * d * cfg["vocab_size"]
    return parts


def train_flops_per_token(cfg, seq):
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def flash_needed(cfg, batch, seq, windowed, itemsize=2):
    """(flops, bytes) one training step's attention needs over the
    ``windowed`` (or the full) layers: forward q k^T and p v over the
    visible pairs, backward their four gradients; forward reads q, k, v
    and writes o, backward reads q, k, v, o, do and writes dq, dk, dv. k,
    v and their gradients are one tensor a k/v head — read once a group,
    not once a query head; the row statistics are float32."""
    hd, kv_heads = cfg["head_dim"], cfg["num_key_value_heads"]
    flops = byts = 0
    for _, layer_type, heads in layers_of(cfg):
        window = window_of(cfg, layer_type)
        if bool(window) != bool(windowed):
            continue
        flops += 3 * batch * heads * 4 * hd * visible_pairs(seq, window)
        rows = batch * seq * hd * itemsize
        q, kv = rows * heads, rows * kv_heads
        stats = batch * heads * seq * 4
        # forward: q, k, v in, o and lse out; backward: q, k, v, o, do, lse
        # and delta in, dq, dk, dv out
        byts += (2 * q + 2 * kv + stats) + (4 * q + 4 * kv + 2 * stats)
    return flops, byts

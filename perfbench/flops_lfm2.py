"""Operations and bytes an LFM2 model (gated short-convolution and
grouped-query attention layers, routed experts) NEEDS, from the
configuration's shapes, as ``flops_gqa_moe.py`` has them: recomputed work
is never counted, a multiply-accumulate is 2 operations, a backward pass
costs twice its forward, routed work is counted at its expectation (a
token's ``num_experts_per_tok`` pairs fall on the held experts with
probability held / published). Attention is the causal half square."""


def layers_of(cfg):
    """(op, ffn) a layer: ``conv`` or ``full_attention``, ``dense`` or
    ``expert``."""
    return [(op, "dense" if li < cfg["num_dense_layers"] else "expert")
            for li, op in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def conv_flops_per_token(cfg):
    """One conv op's elementwise work a token: ``B * x~``, the ``K``-tap
    filter (K products, K - 1 sums) and ``C * .``, a channel."""
    return (2 * cfg["conv_L_cache"] + 1) * cfg["hidden_size"]


def forward_flops_per_token(cfg, seq):
    """By part, one token's forward pass at sequence length ``seq``."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, kv = d // H, cfg["num_key_value_heads"] * (d // H)
    parts = dict.fromkeys(("conv_proj", "conv_mix", "attn_proj", "scores",
                           "dense_ffn", "router", "routed"), 0)
    for op, ffn in layers_of(cfg):
        if op == "conv":
            parts["conv_proj"] += 2 * (d * 3 * d + d * d)
            parts["conv_mix"] += conv_flops_per_token(cfg)
        else:
            parts["attn_proj"] += 2 * (2 * d * H * hd + 2 * d * kv)
            # q k^T and p v: 2 matmuls x 2 operations x hd a visible pair
            parts["scores"] += H * 4 * hd * (seq + 1) / 2
        if ffn == "dense":
            parts["dense_ffn"] += 2 * 3 * d * cfg["intermediate_size"]
        else:
            parts["router"] += 2 * d * cfg["published"]["num_experts"]
            parts["routed"] += (2 * cfg["num_experts_per_tok"]
                                * cfg["num_experts"]
                                / cfg["published"]["num_experts"]
                                * 3 * d * cfg["moe_intermediate_size"])
    parts["head"] = 2 * d * cfg["vocab_size"]
    return parts


def train_flops_per_token(cfg, seq):
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def short_conv_needed(cfg, batch, seq, itemsize=2):
    """(flops, bytes) one training step's gated short convolutions need,
    over the conv layers: forward reads ``bcx`` (3d) and writes ``y`` (d),
    backward reads ``dy`` (d) and ``bcx`` and writes ``d bcx`` (3d), all
    at ``itemsize``; the filter once a pass, its gradient written in
    float32. The recomputed forward is not counted."""
    d, K = cfg["hidden_size"], cfg["conv_L_cache"]
    layers = sum(op == "conv" for op, _ in layers_of(cfg))
    tokens = batch * seq
    rows = tokens * itemsize * ((3 * d + d) + (d + 3 * d + 3 * d))
    filters = K * d * (2 * itemsize + 4)
    return (layers * 3 * tokens * conv_flops_per_token(cfg),
            layers * (rows + filters))


def flash_needed(cfg, batch, seq, itemsize=2):
    """(flops, bytes) one training step's attention needs over the
    attention layers, as ``flops_gqa_moe.flash_needed`` counts a full
    layer: q k^T and p v over the causal half square, forward and the
    backward's four gradients; q, k, v in and o out forward, q, k, v, o,
    do in and dq, dk, dv out backward, k and v once a k/v head, the row
    statistics in float32."""
    H, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    layers = sum(op == "full_attention" for op, _ in layers_of(cfg))
    pairs = seq * (seq + 1) // 2
    rows = batch * seq * hd * itemsize
    q, kv = rows * H, rows * kv_heads
    stats = batch * H * seq * 4
    return (layers * 3 * batch * H * 4 * hd * pairs,
            layers * ((2 * q + 2 * kv + stats) + (4 * q + 4 * kv + 2 * stats)))

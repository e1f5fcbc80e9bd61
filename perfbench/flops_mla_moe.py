"""Operations and bytes a latent-attention (MLA) model with routed experts
NEEDS, from the configuration's shapes, as ``flops.py`` has them for the
first LM: recomputed work is never counted, a multiply-accumulate is 2
operations, a backward pass costs twice its forward. Routed work is counted
at its expectation — a token's ``num_experts_per_tok`` pairs fall on the
held experts with probability held / published — so a step's count does
not follow the routing (the binomial spread is about 1% at 8,192 tokens)."""


def _widths(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def mla_matmul_params(cfg):
    """W_q, W_kva, W_kvb, W_o of one layer (the norms are no matmuls)."""
    d, h, dq, dv = _widths(cfg)
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (d * h * dq + d * (r + dr)
            + r * h * (cfg["qk_nope_head_dim"] + dv) + h * dv * d)


def expected_pairs_per_token(cfg):
    """(token, expert) pairs a token sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def expert_matmul_params(cfg):
    """One expert's SwiGLU: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops_per_token(cfg, seq):
    """By part, one token's forward pass at sequence length ``seq``."""
    d, h, dq, dv = _widths(cfg)
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    routed = layers - dense
    return {
        "mla_proj": layers * 2 * mla_matmul_params(cfg),
        # causal QK^T and PV: half the T x T square each
        "mla_scores": layers * h * seq * (dq + dv),
        "dense_ffn": dense * 2 * 3 * d * cfg["intermediate_size"],
        "router": routed * 2 * d * cfg["published"]["num_experts"],
        "shared": routed * 2 * cfg["num_shared_experts"]
        * expert_matmul_params(cfg),
        "routed": routed * 2 * expected_pairs_per_token(cfg)
        * expert_matmul_params(cfg),
        "head": 2 * d * cfg["vocab_size"]}


def train_flops_per_token(cfg, seq):
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def flash_needed(cfg, batch, seq, itemsize=2):
    """(flops, bytes) one training step's attention needs over all layers:
    forward QK^T (width dq) and PV (width dv), backward their four
    gradients; forward reads q, k, v and writes o, backward reads q, k, v,
    o, do and writes dq, dk, dv. ``k_rope`` (and its gradient) is one
    vector a token, counted once; the row statistics are float32."""
    d, h, dq, dv = _widths(cfg)
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    flops = 3 * batch * h * seq * seq * (dq + dv)
    rows = batch * seq * itemsize
    q, k, v = rows * h * dq, rows * (h * dn + dr), rows * h * dv
    stats = batch * h * seq * 4
    byts = (q + k + v + v + stats) + (2 * (q + k + v) + 2 * v + 2 * stats)
    return layers * flops, layers * byts


def gmm_needed(cfg, tokens, itemsize=2):
    """(flops, bytes) one training step's grouped matmuls need over the
    routed layers: pairs x 3 matmuls forward and twice that backward;
    every pass (forward, input gradient, weight gradient) moves the held
    experts' weights once and a pair's row in and out."""
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    pairs = tokens * expected_pairs_per_token(cfg)
    flops = 3 * 2 * pairs * expert_matmul_params(cfg)
    weights = cfg["num_experts"] * expert_matmul_params(cfg) * itemsize
    row = cfg["hidden_size"] * itemsize
    byts = 3 * (weights + 2 * pairs * row)
    return routed * flops, routed * byts

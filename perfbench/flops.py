"""Operations and bytes the algorithms NEED, from a configuration's shapes.
Recomputed work is never counted, whatever implements the step: a
multiply-accumulate is 2 operations, a backward pass costs twice its
forward (one matmul for the input's gradient, one for the weight's)."""


def resnet_forward_macs(cfg):
    """Multiply-accumulates of one image's forward pass: the stem, the
    bottleneck units (stride on the 3x3), the projections, the classifier."""
    f = cfg["filters"]
    side = cfg["image_size"] // 2  # 7x7 stride 2
    macs = side * side * 7 * 7 * cfg["image_channels"] * f[0]
    side //= 2  # 3x3 max pool stride 2
    cin = f[0]
    for s, n_units in enumerate(cfg["units"]):
        cout = f[s + 1]
        mid = int(cout * cfg["bottleneck_ratio"])
        for u in range(n_units):
            stride = 2 if (u == 0 and s > 0) else 1
            out = side // stride
            macs += side * side * cin * mid          # 1x1
            macs += out * out * 9 * mid * mid        # 3x3, strided
            macs += out * out * mid * cout           # 1x1
            if u == 0:
                macs += out * out * cin * cout       # projection
            side, cin = out, cout
    return macs + cin * cfg["num_classes"]


def resnet_train_flops_per_image(cfg):
    return 2 * 3 * resnet_forward_macs(cfg)


def lm_matmul_params(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    e = cfg["n_experts"]
    per_layer = 4 * d * d + e * 2 * d * f + d * e
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops(batch, heads, seq, head_dim, backward):
    """Causal attention: QK^T and PV forward (2 matmuls), their four
    gradients backward, each over the lower half of the T x T square."""
    matmuls = 4 if backward else 2
    return matmuls * 2 * batch * heads * seq * seq * head_dim // 2


def lm_train_flops_per_token(cfg, seq):
    """6 per matmul parameter (forward 2, backward 4; the embedding is a
    lookup) plus causal attention, forward and backward."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    att = (attention_flops(1, h, seq, d // h, False)
           + attention_flops(1, h, seq, d // h, True)) / seq
    return 6 * lm_matmul_params(cfg) + cfg["num_hidden_layers"] * att


def flash_needed(cfg, batch, seq, itemsize=2):
    """(flops, bytes) one training step's attention needs over all layers:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv; the row statistics are float32."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    flops = (attention_flops(batch, h, seq, d // h, False)
             + attention_flops(batch, h, seq, d // h, True))
    tensor = batch * seq * d * itemsize
    stats = batch * h * seq * 4
    byts = (4 * tensor + stats) + (8 * tensor + 2 * stats)
    return layers * flops, layers * byts

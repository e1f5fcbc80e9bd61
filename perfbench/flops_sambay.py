"""Operations and bytes a decoder-hybrid-decoder model of state-space,
differential-attention and gated-memory layers NEEDS, from the
configuration's shapes, as ``flops_gqa_moe.py`` has them for grouped-query
attention: recomputed work is never counted, a multiply-accumulate is 2
operations, a backward pass costs twice its forward. A differential layer
is two softmax maps a head pair, each ``q k^T`` at the head width and ``p
V`` at twice it; a windowed layer's pairs are **the band only**. The
selective scan is counted at 7 operations a state element and step
(``delta A``, ``exp``, two products and a sum for the state, a product and
a sum for the output) and at the bytes ANY implementation of the layer's
interface must move, whatever it fuses."""
from perfbench.flops_gqa_moe import visible_pairs
from perfbench.reference.sambay import layer_kinds, sizes


def mixer_matmul_params(cfg, kind):
    s = sizes(cfg)
    d, E, hd = s["d"], s["E"], s["hd"]
    if kind == "mamba":
        return d * 2 * E + E * (s["R"] + 2 * s["N"]) + s["R"] * E + E * d
    if kind == "gmu":
        return 2 * d * E
    dq, dkv = s["H"] * hd, s["Hkv"] * hd
    return 2 * d * dq + (0 if kind == "cross" else 2 * d * dkv)


def window_of(cfg, kind):
    return cfg["sliding_window"] if kind == "window" else None


def pair_flops(cfg):
    """One visible (query, key) pair over all head pairs of a differential
    layer: two maps, each q k^T (hd wide) and p V (2 hd wide)."""
    s = sizes(cfg)
    return (s["H"] // 2) * 2 * (2 * s["hd"] + 2 * 2 * s["hd"])


def scan_flops_per_token(cfg):
    s = sizes(cfg)
    return 7 * s["E"] * s["N"]


def forward_flops_per_token(cfg, seq):
    """By part, one token's forward pass at sequence length ``seq``."""
    s = sizes(cfg)
    parts = dict.fromkeys(("mixer_proj", "full_scores", "window_scores",
                           "scan", "ffn"), 0)
    for kind, _ in layer_kinds(cfg):
        parts["mixer_proj"] += 2 * mixer_matmul_params(cfg, kind)
        parts["ffn"] += 2 * 3 * s["d"] * s["f"]
        if kind == "mamba":
            parts["scan"] += scan_flops_per_token(cfg)
        elif kind != "gmu":
            window = window_of(cfg, kind)
            parts["window_scores" if window else "full_scores"] += (
                pair_flops(cfg) * visible_pairs(seq, window) / seq)
    parts["head"] = 2 * s["d"] * cfg["vocab_size"]
    return parts


def train_flops_per_token(cfg, seq):
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def flash_needed(cfg, batch, seq, windowed, itemsize=2):
    """(flops, bytes) one training step's attention needs over the
    ``windowed`` layers (or the others: the full layer and the cross
    layers). A layer is two calls; a call reads q, k, V and writes o
    forward, reads q, k, V, o, do and writes dq, dk, dV backward; k and V
    are one tensor a k/v head pair, read once a group."""
    s = sizes(cfg)
    hd, H, Hkv = s["hd"], s["H"] // 2, s["Hkv"] // 2
    flops = byts = 0
    for kind, _ in layer_kinds(cfg):
        if kind in ("mamba", "gmu") or (kind == "window") != bool(windowed):
            continue
        flops += 3 * batch * pair_flops(cfg) * visible_pairs(
            seq, window_of(cfg, kind))
        rows = batch * seq * itemsize
        q, k, v, o = rows * H * hd, rows * Hkv * hd, rows * Hkv * 2 * hd, \
            rows * H * 2 * hd
        stats = batch * H * seq * 4
        byts += 2 * ((q + k + v + o + stats)
                     + (2 * q + 2 * k + 2 * v + 2 * o + 2 * stats))
    return flops, byts


def scan_needed(cfg, batch, seq, itemsize=2):
    """(flops, bytes) one training step's selective scans need. Forward:
    ``u`` in, ``y`` out, ``B_t``, ``C_t`` and ``delta``'s ``dt_rank``-wide
    preimage in; backward: ``u`` and ``dy`` in, ``du`` out, the small ones
    in and their gradients out."""
    s = sizes(cfg)
    layers = sum(kind == "mamba" for kind, _ in layer_kinds(cfg))
    small = s["R"] + 2 * s["N"]
    per_token = itemsize * ((2 * s["E"] + small) + (3 * s["E"] + 2 * small))
    return (layers * 3 * batch * seq * scan_flops_per_token(cfg),
            layers * batch * seq * per_token)

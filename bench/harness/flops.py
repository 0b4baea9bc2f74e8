"""Operations and bytes that the algorithms need, from the configurations'
shapes alone: the same numbers whatever implements them. A roofline share
divides the least time these allow by a measured device time."""
from __future__ import annotations

import math


# ------------------------------------------------------------------ resnet
def resnet_convs(cfg: dict) -> list:
    """Every convolution of a ResNet (basic blocks) on one image:
    ``(h_in, w_in, c_in, h_out, w_out, c_out, k)``."""
    size, c = cfg["image_size"], 3
    h = size
    out = []

    def conv(h_in, c_in, c_out, k, stride):
        h_out = math.ceil(h_in / stride)
        out.append((h_in, h_in, c_in, h_out, h_out, c_out, k))
        return h_out

    h = conv(h, c, cfg["stem_width"], 7, 2)
    if size >= 64:
        h = math.ceil(h / 2)                   # 3x3/2 max pool
    c = cfg["stem_width"]
    for si, (blocks, width) in enumerate(zip(cfg["stage_sizes"],
                                             cfg["stage_widths"])):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            h_in = h
            h = conv(h_in, c, width, 3, stride)
            conv(h, width, width, 3, 1)
            if stride != 1 or c != width:
                conv(h_in, c, width, 1, stride)
            c = width
    return out


def resnet_conv_flops(cfg: dict) -> float:
    """Multiply-add FLOPs (2 per MAC) of all convolutions, one image."""
    return float(sum(2 * ho * wo * co * k * k * ci
                     for _, _, ci, ho, wo, co, k in resnet_convs(cfg)))


def resnet_flops(cfg: dict) -> float:
    """Convolutions plus the classifier, one image."""
    last = cfg["stage_widths"][-1]
    return resnet_conv_flops(cfg) + 2.0 * last * cfg["num_classes"]


def resnet_conv_bytes(cfg: dict, images: int, dispatches: int,
                      itemsize: int = 4) -> float:
    """Least bytes the convolutions move: each input and output activation
    once per image, each weight once per dispatch."""
    act = sum(hi * wi * ci + ho * wo * co
              for hi, wi, ci, ho, wo, co, _ in resnet_convs(cfg))
    wts = sum(k * k * ci * co for _, _, ci, _, _, co, k in resnet_convs(cfg))
    return float(itemsize * (act * images + wts * dispatches))


# ---------------------------------------------------------------------- lm
def lm_matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies by (the output head included,
    the embedding lookup not)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = d * hd + 2 * d * kv + hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def lm_param_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight one decode step reads (norms and biases too;
    with tied embeddings the table is read once, as the output head)."""
    d = cfg["hidden_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    extra = cfg["num_hidden_layers"] * (2 * d + (hd + 2 * kv
                                                 if cfg.get("qkv_bias")
                                                 else 0)) + d
    n = lm_matmul_params(cfg) + extra
    if not cfg.get("tie_word_embeddings"):
        n += d * cfg["vocab_size"]
    return itemsize * n


def lm_kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def lm_attn_flops(cfg: dict, context: float) -> float:
    """Score and value FLOPs of one token attending to ``context`` keys."""
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * hd * context * cfg["num_hidden_layers"]


def lm_prefill_flops(cfg: dict, length: int) -> float:
    """One prompt of ``length`` tokens, causal."""
    return (2.0 * lm_matmul_params(cfg) * length
            + lm_attn_flops(cfg, length * (length + 1) / 2))


def lm_decode_flops(cfg: dict, contexts: float, tokens: int) -> float:
    """``tokens`` decoded tokens whose contexts sum to ``contexts``."""
    return 2.0 * lm_matmul_params(cfg) * tokens + lm_attn_flops(cfg, contexts)

"""FLOP and byte counts against hand-worked ResNet-18 and qwen2-1.5b."""
import json

from harness import flops
from harness.registry import BENCH

R18 = json.loads((BENCH / "configs" / "resnet18.json").read_text())
QWEN = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())


def test_resnet18_conv_flops_by_hand():
    # MACs per image of each conv, from the output map and the kernel:
    stem = 112 * 112 * 64 * 7 * 7 * 3                  # 118,013,952
    s0 = 4 * (56 * 56 * 64 * 9 * 64)                   # 4 convs 3x3
    s1 = (28 * 28 * 128 * 9 * 64 + 3 * 28 * 28 * 128 * 9 * 128
          + 28 * 28 * 128 * 64)                        # + 1x1 projection
    s2 = (14 * 14 * 256 * 9 * 128 + 3 * 14 * 14 * 256 * 9 * 256
          + 14 * 14 * 256 * 128)
    s3 = (7 * 7 * 512 * 9 * 256 + 3 * 7 * 7 * 512 * 9 * 512
          + 7 * 7 * 512 * 256)
    macs = stem + s0 + s1 + s2 + s3
    assert macs == 1_813_561_344
    assert flops.resnet_conv_flops(R18) == 2 * macs
    assert flops.resnet_flops(R18) == 2 * macs + 2 * 512 * 1000
    assert len(flops.resnet_convs(R18)) == 20


def test_resnet18_conv_bytes():
    convs = flops.resnet_convs(R18)
    weights = sum(k * k * ci * co for _, _, ci, _, _, co, k in convs)
    assert weights == 11_166_912                 # conv weights of ResNet-18
    one = flops.resnet_conv_bytes(R18, images=1, dispatches=0)
    assert flops.resnet_conv_bytes(R18, 8, 2) == 8 * one + 2 * 4 * weights


def test_qwen2_per_token_by_hand():
    d, f, L, V = 1536, 8960, 28, 151936
    layer = d * 12 * 128 * 2 + 2 * d * 2 * 128 + 3 * d * f
    assert layer == 46_792_704
    assert flops.lm_matmul_params(QWEN) == L * layer + d * V
    assert flops.lm_matmul_params(QWEN) == 1_543_569_408
    assert round(2 * flops.lm_matmul_params(QWEN) / 1e9, 3) == 3.087
    assert flops.lm_kv_bytes_per_token(QWEN) == 2 * 28 * 2 * 128 * 2
    # norms (2 a layer and the final) and q/k/v biases ride along
    extra = L * (2 * d + 1536 + 2 * 256) + d
    assert flops.lm_param_bytes(QWEN) == 2 * (1_543_569_408 + extra)


def test_qwen2_attention_flops():
    # one token at context c: 4 * heads * head_dim * c per layer
    assert flops.lm_attn_flops(QWEN, 100) == 4 * 1536 * 100 * 28
    p = flops.lm_prefill_flops(QWEN, 4)
    assert p == 2 * 1_543_569_408 * 4 + 4 * 1536 * 28 * (1 + 2 + 3 + 4)
    assert flops.lm_decode_flops(QWEN, 10, 2) == \
        2 * 1_543_569_408 * 2 + 4 * 1536 * 28 * 10

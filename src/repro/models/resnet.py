"""ResNet-18 (He et al. 2016) — the paper's case-study workload.

Pure-JAX reference implementation (the oracle), plus BN-folding into
inference scale/shift pairs. The RCTC toolchain (core/rctc.py) flattens this
network into a fine-grained RCB program (CONV2D / SCALE_SHIFT / RELU / ADD /
POOL / DENSE / SOFTMAX ops) executed by the generic engine — the same
deployment path the paper demonstrates on the 4x7 AIE grid.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.resnet18 import ResNetConfig
from repro.models.common import ParamSpec, init_params


def _conv_spec(kh, kw, cin, cout):
    # He init over the true fan-in kh*kw*cin (init_params divides by cin
    # only): without the taps every 3x3 conv triples the activation scale
    # and the 224x224 logits reach ~1e8, a one-hot softmax.
    return ParamSpec((kh, kw, cin, cout), "float32", (None, None, None, None),
                     "normal", 1.4 / math.sqrt(kh * kw))


def _bn_specs(c):
    return {
        "scale": ParamSpec((c,), "float32", (None,), "ones"),
        "bias": ParamSpec((c,), "float32", (None,), "zeros"),
        "mean": ParamSpec((c,), "float32", (None,), "zeros"),
        "var": ParamSpec((c,), "float32", (None,), "ones"),
    }


def resnet_specs(cfg: ResNetConfig) -> dict:
    specs: dict[str, Any] = {
        "stem_conv": _conv_spec(7, 7, 3, cfg.stem_width),
        "stem_bn": _bn_specs(cfg.stem_width),
        "fc_w": ParamSpec((cfg.stage_widths[-1], cfg.num_classes), "float32",
                          (None, None)),
        "fc_b": ParamSpec((cfg.num_classes,), "float32", (None,), "zeros"),
    }
    cin = cfg.stem_width
    for si, (n_blocks, width) in enumerate(zip(cfg.stage_sizes,
                                               cfg.stage_widths)):
        for bi in range(n_blocks):
            pre = f"s{si}b{bi}_"
            stride = 2 if (bi == 0 and si > 0) else 1
            specs[pre + "conv1"] = _conv_spec(3, 3, cin, width)
            specs[pre + "bn1"] = _bn_specs(width)
            specs[pre + "conv2"] = _conv_spec(3, 3, width, width)
            specs[pre + "bn2"] = _bn_specs(width)
            if stride != 1 or cin != width:
                specs[pre + "proj"] = _conv_spec(1, 1, cin, width)
                specs[pre + "proj_bn"] = _bn_specs(width)
            cin = width
    return specs


def init_resnet(rng: jax.Array, cfg: ResNetConfig) -> dict:
    return init_params(rng, resnet_specs(cfg))


def _bn(x, p, eps=1e-5):
    inv = jax.lax.rsqrt(p["var"] + eps)
    return (x - p["mean"]) * inv * p["scale"] + p["bias"]


def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def resnet_forward(cfg: ResNetConfig, params: dict, x: jax.Array,
                   softmax: bool = True) -> jax.Array:
    """Oracle forward: x (N,H,W,3) float32 -> (N, classes)."""
    h = _conv(x, params["stem_conv"], stride=2)
    h = jax.nn.relu(_bn(h, params["stem_bn"]))
    if cfg.image_size >= 64:
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    cin = cfg.stem_width
    for si, (n_blocks, width) in enumerate(zip(cfg.stage_sizes,
                                               cfg.stage_widths)):
        for bi in range(n_blocks):
            pre = f"s{si}b{bi}_"
            stride = 2 if (bi == 0 and si > 0) else 1
            res = h
            y = _conv(h, params[pre + "conv1"], stride)
            y = jax.nn.relu(_bn(y, params[pre + "bn1"]))
            y = _conv(y, params[pre + "conv2"], 1)
            y = _bn(y, params[pre + "bn2"])
            if pre + "proj" in params:
                res = _bn(_conv(h, params[pre + "proj"], stride),
                          params[pre + "proj_bn"])
            h = jax.nn.relu(y + res)
            cin = width
    h = jnp.mean(h, axis=(1, 2))
    logits = h @ params["fc_w"] + params["fc_b"]
    return jax.nn.softmax(logits, axis=-1) if softmax else logits


def fold_bn(params: dict, eps: float = 1e-5) -> dict:
    """Fold BN into per-channel (scale, shift) pairs for inference RCBs."""
    out = {}
    for k, v in params.items():
        if isinstance(v, dict) and set(v) == {"scale", "bias", "mean", "var"}:
            inv = 1.0 / np.sqrt(np.asarray(v["var"]) + eps)
            out[k + "_scale"] = np.asarray(v["scale"]) * inv
            out[k + "_shift"] = np.asarray(v["bias"]) - \
                np.asarray(v["mean"]) * np.asarray(v["scale"]) * inv
        else:
            out[k] = np.asarray(v)
    return out

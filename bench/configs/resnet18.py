"""Plain reference for ``resnet18.json``: weights from a seed, and the
forward pass in ``jax.numpy`` (He et al. 2016, basic blocks, BN unfolded).

It imports nothing of the program. The configuration states float32 with
matmuls and convolutions at ``"highest"`` precision, so ``log_probs``
computes at the configuration's ``matmul_precision``; the control is the
same network one step down, at ``"high"`` (three bfloat16 passes on a
TPU; a CPU computes both in float32, so the control means something only
on the chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LOG_FLOOR = 1e-30      # a probability below this counts as the floor


def _key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def layout(cfg: dict) -> dict:
    """Parameter names and shapes, as the program's RCB lowering reads
    them: convs are HWIO, BN groups hold scale, bias, mean and var."""
    out = {"stem_conv": (7, 7, 3, cfg["stem_width"]),
           "stem_bn": cfg["stem_width"],
           "fc_w": (cfg["stage_widths"][-1], cfg["num_classes"]),
           "fc_b": (cfg["num_classes"],)}
    c = cfg["stem_width"]
    for si, (n, w) in enumerate(zip(cfg["stage_sizes"],
                                    cfg["stage_widths"])):
        for bi in range(n):
            pre = f"s{si}b{bi}_"
            stride = 2 if (bi == 0 and si > 0) else 1
            out[pre + "conv1"] = (3, 3, c, w)
            out[pre + "bn1"] = w
            out[pre + "conv2"] = (3, 3, w, w)
            out[pre + "bn2"] = w
            if stride != 1 or c != w:
                out[pre + "proj"] = (1, 1, c, w)
                out[pre + "proj_bn"] = w
            c = w
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight, on the device, in one jitted call. Convolutions are He
    normal over their true fan-in; BN statistics are random, so folding
    them is exercised; the classifier is 1/sqrt(fan-in) normal. The BN
    scales that close a block's branches (``bn2``, ``proj_bn``) are drawn
    about half the others, as a trained ResNet's are small: with scales
    near 1 every block doubles the activations' variance, the logits reach
    a spread of hundreds, and all but a few classes fall under the
    comparison's floor."""
    shapes = layout(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shp) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if isinstance(shp, int):
                k1, k2, k3, k4 = jax.random.split(k, 4)
                lo = 0.25 if name.endswith(("bn2", "proj_bn")) else 0.5
                out[name] = {
                    "scale": jax.random.uniform(k1, (shp,), jnp.float32,
                                                lo, 3 * lo),
                    "bias": 0.1 * jax.random.normal(k2, (shp,)),
                    "mean": 0.1 * jax.random.normal(k3, (shp,)),
                    "var": jax.random.uniform(k4, (shp,), jnp.float32,
                                              0.5, 1.5)}
            elif name == "fc_b":
                out[name] = 0.1 * jax.random.normal(k, shp)
            else:
                fan_in = int(np.prod(shp[:-1]))
                out[name] = (jax.random.normal(k, shp)
                             * np.sqrt(2.0 / fan_in)).astype(jnp.float32)
        return out

    return make(_key(seed))


def _bn(x, p):
    inv = jax.lax.rsqrt(p["var"] + 1e-5)
    return (x - p["mean"]) * inv * p["scale"] + p["bias"]


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def forward(cfg: dict, params: dict, x):
    """(N, H, W, 3) images -> (N, classes) log-probabilities (float32)."""
    h = x.astype(jnp.float32)
    h = jax.nn.relu(_bn(_conv(h, params["stem_conv"], 2),
                        params["stem_bn"]))
    if cfg["image_size"] >= 64:
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, n in enumerate(cfg["stage_sizes"]):
        for bi in range(n):
            pre = f"s{si}b{bi}_"
            stride = 2 if (bi == 0 and si > 0) else 1
            y = jax.nn.relu(_bn(_conv(h, params[pre + "conv1"], stride),
                                params[pre + "bn1"]))
            y = _bn(_conv(y, params[pre + "conv2"], 1),
                    params[pre + "bn2"])
            res = h
            if pre + "proj" in params:
                res = _bn(_conv(h, params[pre + "proj"], stride),
                          params[pre + "proj_bn"])
            h = jax.nn.relu(y + res)
    h = jnp.mean(h, axis=(1, 2))
    logits = h @ params["fc_w"] + params["fc_b"]
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(cfg_items, params, x, precision):
    with jax.default_matmul_precision(precision):
        return forward(dict(cfg_items), params, x)


def _frozen(cfg: dict) -> tuple:
    keys = ("image_size", "stage_sizes", "stage_widths")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keys)


def floor_log(p: np.ndarray) -> np.ndarray:
    return np.log(np.maximum(p, LOG_FLOOR))


def log_probs(cfg: dict, params: dict, x: np.ndarray,
              control: bool = False) -> np.ndarray:
    """Reference log-probabilities of one batch at the configuration's
    precision (``control``: at ``"high"``), floored as served replies are
    (float64 on the host)."""
    precision = "high" if control else cfg["matmul_precision"]
    lp = np.asarray(_forward(_frozen(cfg), params, jnp.asarray(x),
                             precision), np.float64)
    return np.maximum(lp, np.log(LOG_FLOOR))

"""Plain reference for ``qwen2-1.5b.json``: weights from a seed, and the
Qwen2 decoder (arXiv:2407.10671) in ``jax.numpy``: RMSNorm, grouped-query
attention with QKV bias and rotate-half RoPE, SwiGLU, tied embeddings.

It imports nothing of the program. Weights are made in bfloat16, the type
they are served in; ``score`` reads them as float32 and computes under
``"highest"`` precision, one sequence at a time and one layer at a time
under ``lax.scan``. ``control=True`` computes the same positions in
float8 (e4m3, one scale per weight tensor and per activation row), the
step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def layout(cfg: dict) -> dict:
    """Parameter names and shapes, as the program's paged engine reads
    them (layers stacked on a leading axis)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, V = cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": (V, d), "final_norm": (d,), "ln1": (L, d), "ln2": (L, d),
           "wq": (L, d, H, D), "wk": (L, d, Hkv, D), "wv": (L, d, Hkv, D),
           "wo": (L, H, D, d), "mlp_wi_gate": (L, d, f),
           "mlp_wi_up": (L, d, f), "mlp_wo": (L, f, d)}
    if cfg["qkv_bias"]:
        out.update(bq=(L, H, D), bk=(L, Hkv, D), bv=(L, Hkv, D))
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    return out


def _std(name: str, shp: tuple, cfg: dict) -> float:
    """1/sqrt(fan-in) of a weight: the contraction axes of its einsum."""
    if name == "wo":
        return (shp[1] * shp[2]) ** -0.5
    if name == "mlp_wo":
        return shp[1] ** -0.5
    return cfg["hidden_size"] ** -0.5


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight, on the device, in one jitted call, in bfloat16."""
    shapes = layout(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shp) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shp, jnp.float32)
            if name in ("final_norm", "ln1", "ln2"):
                v = 1.0 + 0.1 * z
            elif name in ("bq", "bk", "bv"):
                v = 0.1 * z
            else:
                v = z * _std(name, shp, cfg)
            out[name] = v.astype(dt)
        return out

    return make(_key(seed))


# ---------------------------------------------------------------- forward
def _q8(a, axis=None):
    """Round to float8 e4m3 with one scale per tensor (axis None) or per
    slice along ``axis``; returns float32."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control: bool, spec: str):
    if control:
        x, w = _q8(x, axis=-1), _q8(w)
    return jnp.einsum(spec, x, w, precision="highest")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: dict, control: bool, x, p):
    S = x.shape[0]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, p["wq"], control, "sd,dhk->shk")
    k = _mm(h, p["wk"], control, "sd,dhk->shk")
    v = _mm(h, p["wv"], control, "sd,dhk->shk")
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(S, Hkv, H // Hkv, D)
    s = jnp.einsum("qhgd,khd->hgqk", q, k, precision="highest") / D ** 0.5
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None], s,
                  -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", a, v,
                   precision="highest").reshape(S, H * D)
    x = x + _mm(o, p["wo"].reshape(H * D, -1), control, "sd,de->se")
    h = _rms(x, p["ln2"], eps)
    g = _mm(h, p["mlp_wi_gate"], control, "sd,df->sf")
    u = _mm(h, p["mlp_wi_up"], control, "sd,df->sf")
    return x + _mm(jax.nn.silu(g) * u, p["mlp_wo"], control, "sf,fd->sd")


_PER_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "mlp_wi_gate", "mlp_wi_up", "mlp_wo")


def logits(cfg: dict, params: dict, tokens, control: bool = False):
    """(S,) token ids -> (S, vocab) float32 logits, causal."""
    emb = params["embed"].astype(jnp.float32)
    x = emb[tokens]
    layers = {k: v for k, v in params.items() if k in _PER_LAYER}
    x, _ = jax.lax.scan(lambda c, p: (_layer(cfg, control, c, p), None),
                        x, layers)
    x = _rms(x, params["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    head = emb.T if cfg["tie_word_embeddings"] else \
        params["lm_head"].astype(jnp.float32)
    return _mm(x, head, control, "sd,dv->sv")


@functools.partial(jax.jit, static_argnums=(0, 4))
def _score(cfg_items, params, tokens, targets, control):
    cfg = dict(cfg_items)
    ref = logits(cfg, params, tokens)
    best = jnp.max(ref, axis=-1)
    at_target = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    if not control:
        return best, at_target
    pick = jnp.argmax(logits(cfg, params, tokens, control=True), axis=-1)
    return best, jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
         "qkv_bias", "tie_word_embeddings", "rms_norm_eps", "rope_theta")


def score(cfg: dict, params: dict, prompt: np.ndarray, served: np.ndarray,
          length: int, control: bool = False) -> np.ndarray:
    """Gap, at each served token, between the reference's best logit and
    the reference's logit of that token (``control``: of the token that
    the float8 computation puts first), teacher-forced on the prompt and
    the served tokens. The sequence is padded to ``length`` so that every
    request runs one compiled program; causal attention keeps the padding
    out of the positions read."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n, lo = len(served), len(prompt) - 1
    if len(seq) > length:
        raise ValueError(f"sequence {len(seq)} longer than {length}")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    tgt = np.zeros(length, np.int32)
    tgt[lo:lo + n] = served
    best, got = _score(tuple((k, cfg[k]) for k in _KEYS), params,
                       jnp.asarray(toks), jnp.asarray(tgt), control)
    return np.asarray(best - got, np.float64)[lo:lo + n]

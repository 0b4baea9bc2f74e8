"""GQA attention with full/sliding-window variants and seq-sharded KV decode.

Full-sequence attention computes scores in a small static number of query
chunks (flash-style at the XLA level: peak memory drops by the chunk count
while FLOPs stay statically counted for the roofline). Decode attends one new
token against a (possibly ring-buffered) KV cache whose sequence dim may be
sharded over the ``model`` mesh axis — XLA inserts the partial-softmax
collectives (flash-decode-style sequence parallelism).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import mesh_axis_size, shard
from repro.models.common import ParamSpec, apply_rope, rms_norm

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array            # (L, B, S_cache, Hkv, D) — rope-applied keys
    v: jax.Array            # (L, B, S_cache, Hkv, D)


def attn_specs(cfg: ModelConfig) -> dict:
    """Stacked (num_layers leading dim) attention parameter specs."""
    L, d = cfg.num_layers, cfg.d_model
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    # init_params takes shape[-2] as the fan-in; these projections contract
    # over d (wq/wk/wv) and H*D (wo), so the scales below restore a
    # 1/sqrt(true fan-in) std. Without them the q/k projections come out
    # sqrt(d/H) times too large, every softmax is near one-hot, and a
    # random 28-layer model's logits depend chaotically on rounding.
    p = {
        "wq": ParamSpec((L, d, H, D), dt, ("layers", "fsdp", "heads", "head_dim"),
                        "normal", (H / d) ** 0.5),
        "wk": ParamSpec((L, d, Hkv, D), dt, ("layers", "fsdp", "kv_heads", "head_dim"),
                        "normal", (Hkv / d) ** 0.5),
        "wv": ParamSpec((L, d, Hkv, D), dt, ("layers", "fsdp", "kv_heads", "head_dim"),
                        "normal", (Hkv / d) ** 0.5),
        "wo": ParamSpec((L, H, D, d), dt, ("layers", "heads", "head_dim", "fsdp"),
                        "normal", H ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((L, H, D), dt, ("layers", "heads", "head_dim"), "zeros")
        p["bk"] = ParamSpec((L, Hkv, D), dt, ("layers", "kv_heads", "head_dim"), "zeros")
        p["bv"] = ParamSpec((L, Hkv, D), dt, ("layers", "kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((L, D), dt, ("layers", "head_dim"), "ones")
        p["k_norm"] = ParamSpec((L, D), dt, ("layers", "head_dim"), "ones")
    return p


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """KV-cache shape specs. Sliding-window archs keep a ring buffer."""
    s = min(seq_len, cfg.sliding_window) if cfg.attention == "sliding" else seq_len
    shp = (cfg.num_layers, batch, s, cfg.num_kv_heads, cfg.head_dim)
    axes = ("layers", "batch", "seq", "kv_heads", "head_dim")
    return {"k": ParamSpec(shp, cfg.dtype, axes, "zeros"),
            "v": ParamSpec(shp, cfg.dtype, axes, "zeros")}


def _project(x, w, b):
    y = jnp.einsum("bsd,dhk->bshk", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """Shared projection + qk-norm + RoPE for both full and decode paths."""
    q = _project(x, p["wq"], p.get("bq"))
    k = _project(x, p["wk"], p.get("bk"))
    v = _project(x, p["wv"], p.get("bv"))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped_scores(q, k):
    """q: (B,Sq,Hkv,G,D)  k: (B,Skv,Hkv,D) -> (B,Hkv,G,Sq,Skv) fp32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _attend_full(cfg: ModelConfig, p: dict, q, k, v, out_dtype):
    """Causal (optionally sliding-window) attention over a full sequence.

    Query-chunked: `n_chunks` static chunks bound peak score memory; sliding
    window additionally slices the KV span statically per chunk.
    """
    B, S, H, D = q.shape
    Hkv = cfg.num_kv_heads
    G = H // Hkv
    # layout note (§Perf H6, REFUTED): forcing pure heads-TP here (q by
    # kv_heads when divisible) measured +17% collective and +25% HBM bytes
    # on moonshot train_4k — the seq-sharded-q mixed layout lets XLA keep
    # the scores seq-local and only reshard K once. Keep q by seq.
    q = shard(q.reshape(B, S, Hkv, G, D),
              "batch", "seq", "kv_heads", None, None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    n_chunks = max(1, S // 8192) if S >= 16384 else 1
    cs = S // n_chunks
    scale = 1.0 / (D ** 0.5)
    outs = []
    for ci in range(n_chunks):
        q0 = ci * cs
        qc = jax.lax.slice_in_dim(q, q0, q0 + cs, axis=1)
        if cfg.attention == "sliding":
            k0 = max(0, q0 - cfg.sliding_window)   # KV span: window before chunk
        else:
            k0 = 0
        k1 = q0 + cs
        kc = jax.lax.slice_in_dim(k, k0, k1, axis=1)
        vc = jax.lax.slice_in_dim(v, k0, k1, axis=1)
        s_ = _grouped_scores(qc, kc) * scale           # (B,Hkv,G,cs,k1-k0)
        qpos = jnp.arange(q0, q0 + cs)[:, None]
        kpos = jnp.arange(k0, k1)[None, :]
        mask = kpos <= qpos
        if cfg.attention == "sliding":
            mask &= kpos > qpos - cfg.sliding_window
        s_ = jnp.where(mask, s_, NEG_INF)
        a = jax.nn.softmax(s_, axis=-1).astype(out_dtype)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", a, vc))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H, D)
    o = shard(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshd,hdk->bsk", o, p["wo"])


def _attend_flash(cfg: ModelConfig, p: dict, q, k, v, out_dtype):
    """Registry flash-attention path (pallas on TPU, interpret-mode on
    CPU) — the same handler the RCTC
    lowering dispatches as ``Op.ATTENTION``. Opt in via AEG_ATTN_IMPL=flash
    — the jnp path remains the default because interpret-mode pallas_call
    is slow to trace at dry-run scale."""
    from repro.kernels import registry
    o = registry.call("attention", q, k, v, causal=True)
    B, S, H, D = o.shape
    o = shard(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshd,hdk->bsk", o.astype(out_dtype), p["wo"])


def _attn_impl() -> str:
    import os
    return os.environ.get("AEG_ATTN_IMPL", "jnp")


def full_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    q, k, v = _qkv(cfg, p, x, positions)
    if _attn_impl() == "flash" and cfg.attention == "full":
        return _attend_flash(cfg, p, q, k, v, x.dtype)
    return _attend_full(cfg, p, q, k, v, x.dtype)


def prefill_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                      positions: jax.Array):
    """Full attention that also returns the (layer-local) KV cache entry."""
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, positions)
    y = _attend_full(cfg, p, q, k, v, x.dtype)
    if cfg.attention == "sliding":
        W = cfg.sliding_window
        if S >= W:
            k, v = k[:, -W:], v[:, -W:]
    return y, (k, v)


def decode_attention_paged(cfg: ModelConfig, p: dict, x: jax.Array,
                           pos: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, tables: jax.Array):
    """One-token decode against a paged KV pool (one layer's slice).

    x: (B,1,d); pos: (B,) logical position of the new token; pool_k/v:
    (num_blocks+1, block_size, Hkv, D) — the last row is the null block;
    tables: (B, W) int32 physical block ids, null-padded. Logical
    position t of lane b lives at (tables[b, t // bs], t % bs).

    The new token's K/V is scattered at its (block, offset) — live lanes
    hold disjoint blocks so the B writes never collide; pad lanes all
    target the null row, whose garbage is only ever gathered back behind
    the NEG_INF mask. Greedy decode stays bit-identical to the dense
    ``decode_attention``: the valid positions carry exactly the same
    scores, and masked lanes contribute exact zeros to the softmax.

    Returns (out (B,1,d), new_pool_k, new_pool_v).
    """
    NBp1, bs, Hkv, D = pool_k.shape
    B, W = tables.shape
    H = cfg.num_heads
    G = H // Hkv
    q, k, v = _qkv(cfg, p, x, pos[:, None])
    blk = jnp.take_along_axis(tables, (pos[:, None] // bs) % W, axis=1)[:, 0]
    off = pos % bs
    pool_k = pool_k.at[blk, off].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[blk, off].set(v[:, 0].astype(pool_v.dtype))

    kg = pool_k[tables].reshape(B, W * bs, Hkv, D)      # gather block axis
    vg = pool_v[tables].reshape(B, W * bs, Hkv, D)
    qg = q.reshape(B, 1, Hkv, G, D)
    s_ = _grouped_scores(qg, kg) / (D ** 0.5)           # (B,Hkv,G,1,W*bs)
    idx = jnp.arange(W * bs)[None, :]
    valid = idx <= pos[:, None]
    s_ = jnp.where(valid[:, None, None, None, :], s_, NEG_INF)
    a = jax.nn.softmax(s_, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", a, vg).reshape(B, 1, H, D)
    y = jnp.einsum("bshd,hdk->bsk", o, p["wo"])
    return y, pool_k, pool_v


def decode_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                     pos: jax.Array, k_cache: jax.Array, v_cache: jax.Array):
    """One-token decode: x (B,1,d), pos (B,), caches (B,S,Hkv,D).

    Returns (out (B,1,d), new_k_cache, new_v_cache). The caches already hold
    `pos` valid tokens; the new token is written at `pos` (mod window for
    sliding archs).
    """
    B, S, Hkv, D = k_cache.shape
    H = cfg.num_heads
    G = H // Hkv
    q, k, v = _qkv(cfg, p, x, pos[:, None])

    slot = pos % S if cfg.attention == "sliding" else pos

    def ins(cache, new):
        # masked elementwise insert instead of dynamic_update_slice: a
        # traced-index scatter into the seq-SHARDED dim makes the SPMD
        # partitioner materialize the full cache per device (measured
        # 2.1 GB/layer on qwen3 decode_32k); the iota-compare form is
        # elementwise, so every device touches only its local shard.
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, S, 1, 1), 1)
        mask = idx == slot[:, None, None, None]
        return jnp.where(mask, new.astype(cache.dtype), cache)

    k_cache = ins(k_cache, k)
    v_cache = ins(v_cache, v)
    k_cache = shard(k_cache, "batch", "seq", "kv_heads", None)
    v_cache = shard(v_cache, "batch", "seq", "kv_heads", None)

    qg = q.reshape(B, 1, Hkv, G, D)
    s_ = _grouped_scores(qg, k_cache) / (D ** 0.5)      # (B,Hkv,G,1,S)
    idx = jnp.arange(S)[None, :]                        # (1,S)
    valid = idx <= pos[:, None]                         # (B,S)
    if cfg.attention == "sliding":
        # ring buffer: once pos >= S the whole window is live
        valid = valid | (pos[:, None] >= S)
    s_ = jnp.where(valid[:, None, None, None, :], s_, NEG_INF)
    a = jax.nn.softmax(s_, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", a, v_cache).reshape(B, 1, H, D)
    y = jnp.einsum("bshd,hdk->bsk", o, p["wo"])
    return y, k_cache, v_cache

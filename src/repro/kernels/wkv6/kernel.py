"""RWKV-6 WKV recurrence (data-dependent decay) — Pallas TPU kernel.

Chunked formulation (see models/rwkv6.py): all exponentials are of
non-positive arguments, so the kernel is overflow-free for arbitrarily
strong decay. Per (batch*head) the (K,K) state lives in VMEM scratch and
persists across the sequential chunk dimension; each chunk stages (C,K)
tiles of r/k/v/logw and computes the (C,C,K) pairwise-decay contraction
entirely in VMEM — the HBM traffic is exactly 4 reads + 1 write of the
(T,K) stream per head, vs O(T*K*K) for a naive recurrence.

Grid: (B*H, n_chunks)   [chunk dim sequential, state carried in scratch]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *,
            chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)            # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)          # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)            # (1, K) bonus

    C, K = chunk, lw.shape[-1]
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum lowering)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
           <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 0))
    p = jax.lax.dot(tri.astype(jnp.float32), lw,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    pprev = p - lw                              # exclusive (p_{t-1})

    # intra-chunk: att[t,j] = sum_i r[t,i] k[j,i] exp(pprev[t,i]-p[j,i]), j<t
    diff = pprev[:, None, :] - p[None, :, :]    # (C,C,K), <=0 for j<=t-1
    # strictly causal mask, built 3-D (Mosaic cannot reshape an i1 vector)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (C, C, K), 1)
            < jax.lax.broadcasted_iota(jnp.int32, (C, C, K), 0))
    e = jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * e, axis=-1)   # (C, C)
    y = jax.lax.dot(att, v, preferred_element_type=jnp.float32)

    # diagonal bonus: y[t] += (r[t] . (u*k[t])) v[t]
    coef = jnp.sum(r * u * k, axis=1, keepdims=True)
    y = y + coef * v

    # inter-chunk: state entering the chunk
    s = s_scr[...]                               # (K, K)
    y = y + jax.lax.dot(r * jnp.exp(pprev), s,
                        preferred_element_type=jnp.float32)

    # state update: S' = exp(p[-1]) * S + sum_t (k[t]*exp(p[-1]-p[t])) v[t]^T
    # (static slice: an integer index lowers to an unsupported dynamic_slice)
    p_last = jax.lax.slice_in_dim(p, C - 1, C, axis=0)          # (1, K)
    kd = k * jnp.exp(p_last - p)
    s_scr[...] = jnp.exp(p_last).T * s + jax.lax.dot(
        kd.T, v, preferred_element_type=jnp.float32)

    o_ref[0] = y.astype(o_ref.dtype)


def wkv6_bhtk(r, k, v, lw, u, *, chunk: int = 64,
              interpret: bool = False) -> jax.Array:
    """r/k/v/lw: (BH, T, K); u: (BH, 1, K). Returns y (BH, T, K).

    ``u`` carries a unit middle axis so its block's last two dims equal
    the array's, as the TPU compiler requires of a (1, K) tile."""
    bh, t, kk = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_c = t // chunk
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, kk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, kk), r.dtype),
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u)

"""Jitted public wrapper for the WKV6 kernel: (B,T,H,K) layout + fallback.

The shape/dtype contract is enforced eagerly; ``interpret`` is resolved
outside the jitted body (kernels/common.resolve_interpret).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (check_float_dtype, check_rank,
                                  resolve_interpret)
from repro.kernels.wkv6.kernel import wkv6_bhtk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv6_jit(r, k, v, lw, u, *, chunk: int, interpret: bool) -> jax.Array:
    b, t, h, kk = r.shape

    def fold(a):
        return a.transpose(0, 2, 1, 3).reshape(b * h, t, kk)

    u_full = jnp.broadcast_to(u[None], (b, h, kk)).reshape(b * h, 1, kk)
    y = wkv6_bhtk(fold(r), fold(k), fold(v), fold(lw), u_full,
                  chunk=chunk, interpret=interpret)
    return y.reshape(b, h, t, kk).transpose(0, 2, 1, 3)


def check_contract(r, k, v, lw, u, *, chunk: int = 64) -> None:
    """Shape/dtype contract shared with the kernel registry."""
    for name, a in (("r", r), ("k", k), ("v", v), ("lw", lw)):
        check_rank("wkv6", name, a, 4)
        check_float_dtype("wkv6", name, a)
        if tuple(a.shape) != tuple(r.shape):
            raise ValueError(
                f"wkv6: operand {name!r} shape {tuple(a.shape)} differs "
                f"from r {tuple(r.shape)}")
    check_rank("wkv6", "u", u, 2)
    check_float_dtype("wkv6", "u", u)
    b, t, h, kk = r.shape
    if tuple(u.shape) != (h, kk):
        raise ValueError(
            f"wkv6: u must be (H,K)=({h},{kk}), got {tuple(u.shape)}")
    if t == 0:
        raise ValueError("wkv6: zero-length sequence (t=0)")
    if h == 0 or kk == 0:
        raise ValueError(f"wkv6: zero-size head layout (h={h}, k={kk})")
    if t % min(int(chunk), t) != 0:
        raise ValueError(
            f"wkv6: chunk={chunk} does not tile seq_len {t} "
            f"(pad the sequence or pick a divisor)")


def wkv6(r, k, v, lw, u, *, chunk: int = 64,
         interpret: bool | None = None) -> jax.Array:
    """r/k/v/lw: (B,T,H,K); u: (H,K). Returns y (B,T,H,K)."""
    check_contract(r, k, v, lw, u, chunk=chunk)
    return _wkv6_jit(r, k, v, lw, u, chunk=int(chunk),
                     interpret=resolve_interpret(interpret))

"""Pure-JAX semantics for every RCB compute op.

One function per opcode; both RHAL drivers (eager CPU-interpret and fused
XLA) dispatch through this table, so the two execution modes are equivalent
by construction — the paper's portability claim, testable.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.rcb import Op


def gemm(a, b, attrs):
    ta, tb = attrs.get("ta", False), attrs.get("tb", False)
    a = a.T if ta else a
    b = b.T if tb else b
    return jnp.matmul(a, b)


def gemm_i8(a, b, attrs):
    return jax.lax.dot(a, b, preferred_element_type=jnp.int32)


def conv2d(x, w, attrs):
    """x: (N,H,W,C), w: (KH,KW,C,O)."""
    stride = tuple(attrs.get("stride", (1, 1)))
    padding = attrs.get("padding", "SAME")
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv2d_i8(x, w, attrs):
    stride = tuple(attrs.get("stride", (1, 1)))
    padding = attrs.get("padding", "SAME")
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


def dense(x, w, b=None, attrs=None):
    y = jnp.matmul(x, w)
    if b is not None:
        y = y + b
    return y


def add(a, b, attrs):
    return a + b


def relu(x, attrs):
    return jnp.maximum(x, 0)


def softmax(x, attrs):
    return jax.nn.softmax(x.astype(jnp.float32),
                          axis=attrs.get("axis", -1)).astype(x.dtype)


def maxpool(x, attrs):
    win = tuple(attrs.get("window", (2, 2)))
    stride = tuple(attrs.get("stride", win))
    pad = attrs.get("padding", "VALID")
    if pad == "SAME":
        pads = jax.lax.padtype_to_pads(
            x.shape, (1, *win, 1), (1, *stride, 1), "SAME")
    else:
        pads = [(0, 0)] * x.ndim
    init = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min)
    return jax.lax.reduce_window(
        x, init, jax.lax.max, (1, *win, 1), (1, *stride, 1), pads)


def avgpool_global(x, attrs):
    """Mean over H and W, summed as a fixed tree of elementwise adds.

    A reduce is summed in whatever order XLA picks, and that order changes
    when a producer (a RELU) fuses into it: ``jnp.mean`` on the staged
    program then differs from the op-by-op run in the last ulp. Adds
    cannot be reordered, so every path (interpreted, linked, fused,
    vmapped) sums alike. Zero padding to a power of two is exact.
    """
    n, h, w, c = x.shape
    s = x.reshape(n, h * w, c)
    width = 1 << (h * w - 1).bit_length()
    s = jnp.pad(s, ((0, 0), (0, width - h * w), (0, 0)))
    while s.shape[1] > 1:
        half = s.shape[1] // 2
        s = s[:, :half] + s[:, half:]
    return s[:, 0] * (1.0 / (h * w))


def scale_shift(x, scale, shift, attrs=None):
    return x * scale + shift


def scale_shift_relu(x, scale, shift, attrs=None):
    """Fused SCALE_SHIFT+RELU vtable slot (core/opt.py peephole rule F1)."""
    return jnp.maximum(x * scale + shift, 0)


def add_relu(a, b, attrs=None):
    """Fused ADD+RELU vtable slot (core/opt.py peephole rule F2)."""
    return jnp.maximum(a + b, 0)


def quantize(x, attrs):
    scale = attrs["scale"]
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def dequantize(x, attrs):
    return x.astype(jnp.float32) * attrs["scale"]


def reshape(x, attrs):
    return jnp.reshape(x, tuple(attrs["shape"]))


def passthrough(x, attrs):
    return x


def rmsnorm(x, w, attrs):
    from repro.models.common import rms_norm
    return rms_norm(x, w, eps=float((attrs or {}).get("eps", 1e-5)))


def rope(x, positions, attrs):
    from repro.models.common import apply_rope
    return apply_rope(x, positions,
                      theta=float((attrs or {}).get("theta", 10000.0)))


def silu_mul(gate, x, attrs=None):
    return jax.nn.silu(gate) * x


# Kernel opcodes dispatch through the registry (kernels/registry.py) so the
# interpreted path, GRAPH_EXEC artifacts and linked handlers share one
# implementation per kernel (fallback ladder included).
OP_KERNELS: dict[Op, str] = {
    Op.ATTENTION: "attention",
    Op.MATMUL_INT8: "matmul_int8",
    Op.SSM_SCAN: "ssm_scan",
    Op.WKV6: "wkv6",
}


def _kernel_fn(name: str) -> Callable:
    def fn(srcs, attrs):
        from repro.kernels import registry
        return registry.call_op(name, srcs, attrs)
    return fn


_TABLE: dict[Op, Callable] = {
    Op.GEMM: lambda srcs, attrs: gemm(srcs[0], srcs[1], attrs),
    Op.GEMM_I8: lambda srcs, attrs: gemm_i8(srcs[0], srcs[1], attrs),
    Op.CONV2D: lambda srcs, attrs: conv2d(srcs[0], srcs[1], attrs),
    Op.CONV2D_I8: lambda srcs, attrs: conv2d_i8(srcs[0], srcs[1], attrs),
    Op.DENSE: lambda srcs, attrs: dense(*srcs, attrs=attrs),
    Op.ADD: lambda srcs, attrs: add(srcs[0], srcs[1], attrs),
    Op.RELU: lambda srcs, attrs: relu(srcs[0], attrs),
    Op.SOFTMAX: lambda srcs, attrs: softmax(srcs[0], attrs),
    Op.MAXPOOL: lambda srcs, attrs: maxpool(srcs[0], attrs),
    Op.AVGPOOL_GLOBAL: lambda srcs, attrs: avgpool_global(srcs[0], attrs),
    Op.SCALE_SHIFT: lambda srcs, attrs: scale_shift(*srcs, attrs=attrs),
    Op.SCALE_SHIFT_RELU: lambda srcs, attrs: scale_shift_relu(*srcs,
                                                              attrs=attrs),
    Op.ADD_RELU: lambda srcs, attrs: add_relu(srcs[0], srcs[1], attrs),
    Op.QUANTIZE: lambda srcs, attrs: quantize(srcs[0], attrs),
    Op.DEQUANT: lambda srcs, attrs: dequantize(srcs[0], attrs),
    Op.RESHAPE: lambda srcs, attrs: reshape(srcs[0], attrs),
    Op.PASSTHROUGH: lambda srcs, attrs: passthrough(srcs[0], attrs),
    Op.RMSNORM: lambda srcs, attrs: rmsnorm(srcs[0], srcs[1], attrs),
    Op.ROPE: lambda srcs, attrs: rope(srcs[0], srcs[1], attrs),
    Op.SILU_MUL: lambda srcs, attrs: silu_mul(srcs[0], srcs[1], attrs),
    Op.ATTENTION: _kernel_fn("attention"),
    Op.MATMUL_INT8: _kernel_fn("matmul_int8"),
    Op.SSM_SCAN: _kernel_fn("ssm_scan"),
    Op.WKV6: _kernel_fn("wkv6"),
}


def compute(op: Op, srcs, attrs):
    """Execute one compute opcode on already-bound operands."""
    fn = _TABLE.get(op)
    if fn is None:
        raise NotImplementedError(f"no semantics for {op!r}")
    return fn(srcs, attrs)


def lookup(op: Op) -> Callable:
    """Resolve one opcode to its handler ``fn(srcs, attrs)`` ahead of time.

    The program linker (core/linker.py) calls this once per op at link time
    so the hot dispatch loop never touches the table again.
    """
    fn = _TABLE.get(op)
    if fn is None:
        raise NotImplementedError(f"no semantics for {op!r}")
    return fn

"""Distributed-optimization tricks: compressed gradient all-reduce.

``compressed_psum`` runs inside ``shard_map`` over the data axis and
implements three policies:

  * none    — fp32 psum (baseline)
  * bf16    — cast-to-bf16 psum (2x wire traffic reduction)
  * int8_ef — symmetric int8 quantization with error feedback: the
    quantization residual is carried locally and added to the next round's
    gradient, keeping SGD unbiased in the long run (1-bit-Adam family).

At 1000+ nodes DP gradients cross DCN between pods; compression there is the
difference between compute-bound and comms-bound scaling. The dry-run mesh
keeps fp32 reductions (XLA-inserted); this module is the opt-in fast path,
unit-tested on a host mesh in tests/test_distributed.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _psum(x, axis):
    return jax.lax.psum(x, axis_name=axis)


def compressed_psum(grad: jax.Array, axis: str, method: str = "bf16",
                    error: Optional[jax.Array] = None):
    """All-reduce-mean one gradient tensor across `axis` with compression.

    Returns (reduced_grad fp32, new_error). Call inside shard_map.
    """
    n = jax.lax.axis_size(axis)
    g = grad.astype(jnp.float32)
    if method == "none":
        return _psum(g, axis) / n, error
    if method == "bf16":
        r = _psum(g.astype(jnp.bfloat16), axis).astype(jnp.float32) / n
        return r, error
    if method == "int8_ef":
        if error is not None:
            g = g + error
        # shared scale must be the fleet-wide MAX (mean would clip shards
        # holding larger gradients)
        scale = jax.lax.pmax(jnp.max(jnp.abs(g)), axis) + 1e-12
        q = jnp.clip(jnp.round(g / scale * 127.0), -127, 127)
        deq_local = q * (scale / 127.0)
        new_error = g - deq_local                                 # feedback
        total = _psum(q.astype(jnp.int32), axis).astype(jnp.float32)
        return total * (scale / 127.0) / n, new_error
    raise ValueError(f"unknown compression {method!r}")


def compressed_psum_tree(grads, axis: str, method: str = "bf16",
                         errors=None):
    """Tree version; threads per-leaf error-feedback state."""
    leaves, treedef = jax.tree.flatten(grads)
    errs = (jax.tree.leaves(errors) if errors is not None
            else [None] * len(leaves))
    out, new_errs = [], []
    for g, e in zip(leaves, errs):
        r, ne = compressed_psum(g, axis, method, e)
        out.append(r)
        new_errs.append(ne if ne is not None else jnp.zeros_like(g))
    return treedef.unflatten(out), treedef.unflatten(new_errs)


def make_dp_train_step(loss_fn, optimizer_update, mesh, axis: str = "data",
                       method: str = "int8_ef"):
    """Data-parallel train step with compressed gradient exchange.

    ``loss_fn(params, batch) -> scalar``; params replicated, batch sharded
    on `axis`. Demonstrates the shard_map composition used between pods.
    """
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P()), check_vma=False)
    def step(params, batch, errors):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, new_errors = compressed_psum_tree(grads, axis, method, errors)
        new_params = optimizer_update(params, grads)
        return new_params, new_errors

    return step

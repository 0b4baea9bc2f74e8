"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

The assigned production meshes dedicate their axes to DP/FSDP x TP, so the
dry-run table does not use PP; this module provides the stage-parallel
schedule for deployments that add a "stage" axis (e.g. (pp, data, model)
within a pod, or pp across pods over DCN). Microbatches stream through
stages with ``ppermute`` hops; bubble fraction is the usual
(S-1)/(M+S-1).

Semantics test (tests/test_distributed.py): a 4-stage pipeline over a host
mesh must reproduce the single-device stacked forward exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_forward(stage_fn, mesh, axis: str = "stage"):
    """Build fn(stage_params, microbatches) -> outputs.

    ``stage_params``: pytree with leading stage dim (sharded over `axis`).
    ``microbatches``: (M, mb, ...) batch-major microbatch stack (replicated).
    ``stage_fn(params_i, x) -> y`` with y.shape == x.shape.
    """
    n_stage = mesh.shape[axis]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(), check_vma=False)
    def run(stage_params, mbs):
        params_local = jax.tree.map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis)
        M = mbs.shape[0]
        T = M + n_stage - 1
        x_shape = mbs.shape[1:]
        state = jnp.zeros(x_shape, mbs.dtype)      # stage input register
        outs = jnp.zeros_like(mbs)

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (if any); others take the wire
            feed = mbs[jnp.minimum(t, M - 1)]
            x = jnp.where(idx == 0, feed, state)
            y = stage_fn(params_local, x)
            # push to next stage over the ring
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stage) for i in range(n_stage)])
            # last stage commits microbatch (t - (n_stage-1)) when valid
            commit = t - (n_stage - 1)
            valid = jnp.logical_and(idx == n_stage - 1,
                                    jnp.logical_and(commit >= 0, commit < M))
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(commit, 0), 0),
                lambda o: o, outs)
            return nxt, outs

        _, outs = jax.lax.fori_loop(0, T, tick, (state, outs))
        # everyone but the last stage holds zeros; psum broadcasts the result
        outs = jnp.where(idx == n_stage - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return run

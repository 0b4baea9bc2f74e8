"""Multi-device semantics (compression, pipeline, dp step) — these spawn a
subprocess with 8 forced host devices so the main test process keeps its
single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _run(script: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO_SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_compressed_psum_matches_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.collectives import compressed_psum
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    g = jnp.asarray(np.random.RandomState(0).randn(8, 64), jnp.float32)
    exact = jnp.mean(g, axis=0)
    for method, tol in [("none", 1e-6), ("bf16", 2e-2), ("int8_ef", 3e-2)]:
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
        def red(x, method=method):
            r, _ = compressed_psum(x[0], "data", method)
            return r[None]
        out = red(g)[0]
        err = float(jnp.max(jnp.abs(out - exact)))
        assert err < tol, (method, err)
    print("ok")
    """)


def test_int8_error_feedback_converges():
    """With error feedback, the mean of repeated compressed reductions of a
    CONSTANT gradient converges to the true mean (bias -> 0)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.collectives import compressed_psum
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    g = jnp.asarray(np.random.RandomState(1).randn(8, 32), jnp.float32)
    exact = jnp.mean(g, axis=0)
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
    def red(x, e):
        r, ne = compressed_psum(x[0], "data", "int8_ef", e[0])
        return r[None], ne[None]
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(exact)
    n = 12
    for _ in range(n):
        r, err = red(g, err)
        acc = acc + r[0]
    bias = float(jnp.max(jnp.abs(acc / n - exact)))
    one = float(jnp.max(jnp.abs(red(g, jnp.zeros_like(g))[0][0] - exact)))
    assert bias < one * 0.6, (bias, one)   # feedback beats one-shot
    print("ok")
    """)


def test_pipeline_matches_stacked_forward():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.distributed.pipeline import pipeline_forward
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("stage",))
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(4, 16, 16) * 0.3, jnp.float32)
    mbs = jnp.asarray(rng.randn(6, 8, 16), jnp.float32)
    def stage_fn(w, x):
        return jnp.tanh(x @ w)
    run = pipeline_forward(stage_fn, mesh)
    out = run(ws, mbs)
    ref = mbs
    for i in range(4):
        ref = jnp.tanh(ref @ ws[i])
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    print("ok")
    """)


def test_production_rules_compile_small_model():
    """The RBL rule engine drives a real pjit end-to-end on an 8-device
    (2 data x 4 model) mesh: lower, compile AND execute a train step."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.distributed.sharding import axis_rules
    from repro.launch.steps import make_train_step, input_specs
    from repro.models import transformer as tf
    from repro.models.common import init_params, shape_structs
    from repro.optim.adamw import adamw_init_specs
    import dataclasses
    cfg = get_config("qwen2-1.5b-smoke")
    cfg = dataclasses.replace(cfg, d_model=128, d_ff=256, num_heads=8,
                              num_kv_heads=4, head_dim=16, vocab_size=512)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    with axis_rules(mesh, "train"):
        specs = tf.model_specs(cfg)
        params = init_params(jax.random.PRNGKey(0), specs)
        opt = init_params(jax.random.PRNGKey(1),
                          adamw_init_specs(specs))
        params = jax.device_put(params, jax.tree.map(
            lambda s: s.sharding, shape_structs(specs)))
        step = make_train_step(cfg)
        rng = np.random.RandomState(0)
        batch = {"inputs": jnp.asarray(rng.randint(0, 512, (4, 32))),
                 "targets": jnp.asarray(rng.randint(0, 512, (4, 32)))}
        with mesh:
            p2, o2, m = jax.jit(step)(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
    print("ok")
    """)

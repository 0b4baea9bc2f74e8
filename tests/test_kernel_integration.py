"""The Pallas flash-attention kernel wired into the real model stack must
reproduce the jnp attention path (full model forward, interpret mode)."""
import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def test_model_forward_flash_vs_jnp():
    # subprocess so the env toggle can't leak into other tests
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    script = textwrap.dedent("""
    import os
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.common import init_params

    cfg = get_config("qwen3-14b-smoke")        # full attention, GQA, qk_norm
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    x = jnp.asarray(np.random.RandomState(0)
                    .randint(0, cfg.vocab_size, (2, 64)))

    os.environ["AEG_ATTN_IMPL"] = "jnp"
    ref, _, _ = tf.forward_full(cfg, params, x)

    os.environ["AEG_ATTN_IMPL"] = "flash"
    out, _, _ = tf.forward_full(cfg, params, x)

    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                - out.astype(jnp.float32))))
    assert err < 5e-4, err
    print("ok", err)
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def test_registry_stats_count_the_implementation_that_ran():
    """``stats`` counts a call under the implementation that produced it:
    non-causal attention on ragged tiles is refused by the spec and runs
    the ref oracle (counted ``attention_ref``); causal attention on the
    same shapes runs the Pallas kernel (``attention_pallas``)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.registry import KernelRegistry, _build_default_registry

    reg = _build_default_registry()
    assert isinstance(reg, KernelRegistry)
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 40, 2, 16), jnp.float32)
               for _ in range(3))
    params = {"block_q": 16, "block_k": 16}        # 40 % 16: ragged
    out = reg.call("attention", q, k, v, causal=False, params=params)
    ref = reg.call("attention", q, k, v, causal=False, impl="ref")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert reg.stats == {"attention_ref": 2}
    reg.call("attention", q, k, v, causal=True, params=params)
    assert reg.stats == {"attention_ref": 2, "attention_pallas": 1}

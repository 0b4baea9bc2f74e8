"""Compile the served programs for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, kernels that ask for too much fast memory, programs
that do not fit the device. Each test lowers one program at the widths
the chip serves (qwen2-1.5b, ResNet-18 at 224x224) for one chip of a
described ``v5e:2x2`` topology, compiles it, and checks that the Pallas
kernels really became TPU kernels (``tpu_custom_call``) and that the
program fits the chip's 16 GB. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.resnet18 import ResNetConfig
from repro.core import linker, rbl, rctc, rhal, rimfs
from repro.core.executor import Executor
from repro.launch.steps import make_paged_decode_step, make_paged_prefill_step
from repro.models import resnet as rn
from repro.models import transformer as tf
from repro.models.common import is_spec

HBM_BYTES = 16e9                      # one TPU v5e chip
QWEN = "qwen2-1.5b"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _compile(lowered, kernel: bool):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"needs {total / 1e9:.2f} GB of 16 GB"
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    return mem


# ------------------------------------------------------------------ kernels
def test_wkv6_compiles(one_chip):
    from repro.kernels.wkv6.ops import _wkv6_jit
    r = _sds((1, 2048, 32, 64), "bfloat16", one_chip)
    u = _sds((32, 64), "bfloat16", one_chip)
    _compile(_wkv6_jit.lower(r, r, r, r, u, chunk=64, interpret=False),
             kernel=True)


def test_ssm_scan_compiles(one_chip):
    from repro.kernels.ssm_scan.ops import _ssm_scan_jit
    da = _sds((1, 2048, 3072, 16), "float32", one_chip)
    c = _sds((1, 2048, 16), "float32", one_chip)
    _compile(_ssm_scan_jit.lower(da, da, c, chunk=16, d_block=256,
                                 interpret=False), kernel=True)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import _flash_attention_jit
    cfg = get_config(QWEN)
    s = 2048
    q = _sds((1, s, cfg.num_heads, cfg.head_dim), "bfloat16", one_chip)
    kv = _sds((1, s, cfg.num_kv_heads, cfg.head_dim), "bfloat16", one_chip)
    _compile(_flash_attention_jit.lower(q, kv, kv, causal=True, block_q=128,
                                        block_k=128, interpret=False),
             kernel=True)


def test_int8_matmul_compiles(one_chip):
    from repro.kernels.int8_matmul.ops import _int8_matmul_jit
    cfg = get_config(QWEN)
    m, k, n = 2048, cfg.d_model, cfg.d_ff
    _compile(_int8_matmul_jit.lower(
        _sds((m, k), "int8", one_chip), _sds((k, n), "int8", one_chip),
        _sds((n,), "float32", one_chip), block_m=128, block_n=128,
        block_k=128, out_dtype=jnp.dtype("float32"), interpret=False),
        kernel=True)


# ----------------------------------------------------------- served programs
def _qwen_args(one_chip, num_blocks: int, block_size: int):
    cfg = get_config(QWEN)
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                          tf.model_specs(cfg), is_leaf=is_spec)
    pool = _sds((cfg.num_layers, num_blocks + 1, block_size,
                 cfg.num_kv_heads, cfg.head_dim), cfg.dtype, one_chip)
    return cfg, params, pool


def test_qwen2_paged_decode_step_compiles(one_chip):
    """Window-8 decode over 8 lanes against a pool of 1024 blocks of 16."""
    cfg, params, pool = _qwen_args(one_chip, 1024, 16)
    b, span = 8, 32
    batch = {"tokens": _sds((b,), "int32", one_chip),
             "pos": _sds((b,), "int32", one_chip),
             "tables": _sds((b, span), "int32", one_chip)}
    step = make_paged_decode_step(cfg, window=8)
    mem = _compile(jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pool, pool, batch), kernel=False)
    assert mem.alias_size_in_bytes > 0          # the pool is donated


def test_qwen2_paged_prefill_compiles(one_chip):
    """Prefill of 8 prompts of 512 tokens into the same pool."""
    cfg, params, pool = _qwen_args(one_chip, 1024, 16)
    b, s = 8, 512
    batch = {"inputs": _sds((b, s), "int32", one_chip),
             "tables": _sds((b, s // 16), "int32", one_chip)}
    step = make_paged_prefill_step(cfg)
    _compile(jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pool, pool, batch), kernel=False)


def test_resnet18_batched_bucket_compiles(one_chip):
    """The served ResNet-18 at 224x224: the RCB program staged and vmapped
    over batch bucket 8, as ``Executor.run_batched`` compiles it."""
    cfg = ResNetConfig()
    # the values do not matter to the compiler, only the shapes
    params = jax.tree.map(lambda s: np.ones(s.shape, np.float32),
                          rn.resnet_specs(cfg), is_leaf=is_spec)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    bound = rbl.bind(prog, rimfs=rimfs.mount(image))
    staged = linker.stage_callable(
        linker.link(bound, rhal.make_trace_driver()))
    bucket = 8
    inputs = {n: _sds((bucket,) + tuple(t.shape), t.dtype, one_chip)
              for n, t in prog.tensors.items() if t.kind == "input"}
    weights = {n: _sds(np.shape(b), np.asarray(b).dtype, one_chip)
               for n, b in Executor().weights_from(bound).items()}
    _compile(jax.jit(jax.vmap(staged, in_axes=(0, None))).lower(
        inputs, weights), kernel=False)

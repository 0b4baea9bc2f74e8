"""Launch-layer units: collective parser, roofline terms, input specs."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.configs import SHAPES, applicable_shapes, get_config
from repro.launch.dryrun import parse_collectives
from repro.launch.roofline import TPU_HBM_BW, TPU_PEAK_FLOPS, analyze, \
    model_flops_per_device

HLO = """
HloModule test
%add (a: f32[], b: f32[]) -> f32[] { ... }
ENTRY %main {
  %p0 = f32[16,512]{1,0} parameter(0)
  %p1 = bf16[8,128]{1,0} parameter(1)
  %ar = f32[16,512]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = bf16[64,128]{1,0} all-gather(%p1), dimensions={0}
  %ars = f32[16,512]{1,0} all-reduce-start(%p0), to_apply=%add
  %ard = f32[16,512]{1,0} all-reduce-done(%ars)
  %cp = bf16[8,128]{1,0} collective-permute(%p1), source_target_pairs={{0,1}}
}
"""


def test_parse_collectives_symbol_table():
    out = parse_collectives(HLO)
    assert out["bytes"]["all-reduce"] == 2 * 16 * 512 * 4   # ar + ar-start
    assert out["counts"]["all-reduce"] == 2                 # done not counted
    assert out["bytes"]["all-gather"] == 8 * 128 * 2        # operand bytes
    assert out["bytes"]["collective-permute"] == 8 * 128 * 2
    assert out["total_bytes"] == sum(out["bytes"].values())


def _rec(kind="train", flops=1e13, bts=1e12, coll=1e10, devices=256):
    return {
        "arch": "x", "shape": "s", "mesh": "pod256", "kind": kind,
        "devices": devices, "flops_per_device": flops,
        "bytes_per_device": bts, "collective_bytes_per_device": coll,
        "model": {"params": 1e9, "active_params": 1e9,
                  "global_batch": 256, "seq_len": 4096},
    }


def test_roofline_terms_and_dominance():
    r = analyze(_rec())
    assert r["compute_s"] == pytest.approx(1e13 / TPU_PEAK_FLOPS)
    assert r["memory_s"] == pytest.approx(1e12 / TPU_HBM_BW)
    assert r["dominant"] == "memory"
    assert 0 < r["roofline_fraction"] < 1


def test_model_flops_train_vs_decode():
    train = model_flops_per_device(_rec("train"))
    # 6*N*D/devices
    assert train == pytest.approx(6 * 1e9 * 256 * 4096 / 256)
    dec = model_flops_per_device(_rec("decode"))
    assert dec == pytest.approx(2 * 1e9 * 256 / 256)


def test_input_specs_shapes_every_cell():
    from repro.launch.steps import input_specs
    for arch in ("qwen3-14b", "rwkv6-1.6b", "pixtral-12b"):
        cfg = get_config(arch)
        for sname in applicable_shapes(cfg):
            sh = SHAPES[sname]
            spec = input_specs(cfg, sh)
            if sh.kind == "train":
                assert spec["targets"].shape == (sh.global_batch, sh.seq_len)
            if cfg.input_kind == "embeddings":
                assert spec["inputs"].shape[-1] == cfg.d_model
            if sh.kind == "decode":
                assert spec["inputs"].shape[int(
                    cfg.input_kind == "tokens")] == 1 or \
                    spec["inputs"].shape[1] == 1
                assert spec["pos"].shape == (sh.global_batch,)


def test_applicable_shapes_policy():
    assert "long_500k" in applicable_shapes(get_config("rwkv6-1.6b"))
    assert "long_500k" in applicable_shapes(get_config("hymba-1.5b"))
    assert "long_500k" not in applicable_shapes(get_config("qwen3-14b"))
    for a in ("qwen3-14b", "rwkv6-1.6b"):
        assert {"train_4k", "prefill_32k", "decode_32k"} <= \
            set(applicable_shapes(get_config(a)))


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, decides where the persistent
    cache lives; otherwise it is <repo>/.jax_cache, the same path in every
    run (the path is part of each entry's key)."""
    import jax
    from pathlib import Path
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    root = Path(compile_cache.__file__).resolve().parents[3]
    assert (root / "pyproject.toml").is_file()
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # left alone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root /
                                                           ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

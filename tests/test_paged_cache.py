"""Paged KV cache: allocation/lifetime invariants + attention equivalence."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.serving.paged_cache import OutOfBlocksError, PagedKVCache, \
    paged_decode_attention


def _cache(blocks=8, bs=4, layers=2, hkv=2, d=8):
    return PagedKVCache(num_layers=layers, num_blocks=blocks, block_size=bs,
                        num_kv_heads=hkv, head_dim=d)


def test_allocation_and_release_roundtrip():
    c = _cache()
    c.allocate(1, tokens=10)            # ceil(10/4) = 3 blocks
    assert len(c.blocks_for(1)) == 3
    assert c.free_blocks() == 5
    assert c.release(1) == 3
    assert c.free_blocks() == 8
    assert c.blocks_for(1) == []


def test_pool_exhaustion_raises():
    c = _cache(blocks=2, bs=4)
    c.allocate(1, tokens=8)
    c.allocate(2)
    with pytest.raises(OutOfBlocksError):
        c._grow(2, 1)


def test_append_gather_matches_contiguous(rng):
    c = _cache(blocks=16, bs=4, layers=3, hkv=2, d=8)
    c.allocate(7)
    ref_k, ref_v = [], []
    for t in range(11):                  # crosses block boundaries
        lk = rng.randn(3, 2, 8).astype(np.float32)
        lv = rng.randn(3, 2, 8).astype(np.float32)
        c.append(7, jnp.asarray(lk), jnp.asarray(lv))
        ref_k.append(lk)
        ref_v.append(lv)
    for layer in range(3):
        k, v = c.gather(7, layer)
        np.testing.assert_allclose(
            np.asarray(k), np.stack([r[layer] for r in ref_k]), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(v), np.stack([r[layer] for r in ref_v]), atol=1e-6)


def test_paged_attention_matches_dense(rng):
    c = _cache(blocks=16, bs=4, layers=1, hkv=2, d=8)
    c.allocate(0)
    ks, vs = [], []
    for _ in range(9):
        lk = rng.randn(1, 2, 8).astype(np.float32)
        lv = rng.randn(1, 2, 8).astype(np.float32)
        c.append(0, jnp.asarray(lk), jnp.asarray(lv))
        ks.append(lk[0])
        vs.append(lv[0])
    q = jnp.asarray(rng.randn(4, 8), jnp.float32)       # H=4, G=2
    o = paged_decode_attention(c, 0, 0, q)
    # dense reference
    K = np.stack(ks)
    V = np.stack(vs)
    qg = np.asarray(q).reshape(2, 2, 8)
    s = np.einsum("hgd,nhd->hgn", qg, K) / np.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("hgn,nhd->hgd", p, V).reshape(4, 8)
    np.testing.assert_allclose(np.asarray(o), ref, atol=1e-5)


def test_gather_empty_respects_pool_dtype():
    """Regression: the zero-length gather returned hard-coded float32
    empties — downstream concatenation silently upcast bf16/f16 pools."""
    c = PagedKVCache(num_layers=1, num_blocks=4, block_size=4,
                     num_kv_heads=2, head_dim=8, dtype="bfloat16")
    c.allocate(0)
    k, v = c.gather(0, 0)
    assert k.shape == (0, 2, 8) and v.shape == (0, 2, 8)
    assert k.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16


def test_zero_length_attention_is_defined_error(rng):
    """Regression: attention over zero stored tokens softmaxed an empty
    axis into NaNs; it must be a ValueError, not NaN propagation."""
    c = _cache(blocks=4, bs=4, layers=1)
    c.allocate(0)
    q = jnp.asarray(rng.randn(4, 8), jnp.float32)
    with pytest.raises(ValueError, match="zero-length"):
        paged_decode_attention(c, 0, 0, q)
    # unallocated sequence ids fail the same way (no KeyError leak)
    with pytest.raises(ValueError, match="zero-length"):
        paged_decode_attention(c, 99, 0, q)


def test_null_block_is_reserved_and_pads_tables():
    """The null row sits past the allocatable range (accounting is
    unchanged) and pads both axes of device table arrays."""
    c = _cache(blocks=8, bs=4)
    assert c.null_block == 8
    assert c.k.shape[1] == 9                 # num_blocks + 1 physical rows
    assert c.free_blocks() == 8              # null row never allocatable
    c.allocate(1, tokens=6)                  # 2 blocks
    t = c.table_array([1, 2], width=4, rows=3)
    assert t.shape == (3, 4) and t.dtype == np.int32
    assert list(t[0][:2]) == c.blocks_for(1)
    assert (t[0][2:] == c.null_block).all()  # width padding
    assert (t[1] == c.null_block).all()      # unallocated seq -> all null
    assert (t[2] == c.null_block).all()      # rows padding
    assert list(c.lengths_array([1, 2], rows=3)) == [0, 0, 0]


def test_failed_reservation_rolls_back():
    """An allocate() that exhausts the pool mid-reservation must not leak
    a half-grown table."""
    c = _cache(blocks=3, bs=4)
    c.allocate(1, tokens=8)                  # 2 blocks
    with pytest.raises(OutOfBlocksError):
        c.allocate(2, tokens=12)             # needs 3, only 1 free
    assert 2 not in c.tables and 2 not in c.lengths
    assert c.free_blocks() == 1              # the partial grow rolled back


def test_engine_exhaustion_lifecycle_chaos(rng):
    """ISSUE 8 satellite: fill the pool through the engine, observe shed
    verdicts (never OutOfBlocksError), release on completion, and verify
    freed blocks are reused with no leaked table entries across
    chaos-style random admit/release rounds."""
    import jax
    from repro.configs import get_config
    from repro.models import transformer as tf_mod
    from repro.models.common import init_params
    from repro.serving.engine import Request
    from repro.serving.paged_engine import PagedServingEngine
    from repro.serving.scheduler import DeadlineScheduler

    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf_mod.model_specs(cfg))
    eng = PagedServingEngine(cfg, params, max_batch=2, max_seq=32,
                             block_size=4, num_blocks=6,
                             scheduler=DeadlineScheduler())
    total = eng.cache.num_blocks
    served = shed = 0
    rid = 0
    for round_ in range(4):
        reqs = []
        for _ in range(int(rng.randint(1, 5))):
            plen = int(rng.randint(2, 9))
            reqs.append(Request(
                rid=rid, prompt=rng.randint(0, cfg.vocab_size, (plen,))
                .astype(np.int32), max_new=int(rng.randint(1, 7))))
            rid += 1
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        for r in reqs:
            assert r.done
            if r.shed:
                shed += 1
                assert "out of KV blocks" in r.verdict
                assert r.out_tokens == []        # zero compute spent
            else:
                served += 1
                assert len(r.out_tokens) == r.max_new + 1
        # drained => every block released, no leaked table entries
        assert eng.cache.tables == {} and eng.cache.lengths == {}
        assert eng.cache.free_blocks() == total
    assert served > 0        # freed blocks were reused across rounds


@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 9)),
                min_size=1, max_size=24))
@settings(max_examples=40, deadline=None)
def test_property_no_block_leaks_or_double_use(ops):
    """Interleaved allocate/grow/release never leaks or double-books a
    physical block."""
    c = _cache(blocks=12, bs=2)
    for seq, tokens in ops:
        try:
            if seq in c.tables:
                c.release(seq)
            else:
                c.allocate(seq, tokens=tokens)
        except OutOfBlocksError:
            pass
        # invariants
        held = [b for t in c.tables.values() for b in t]
        assert len(held) == len(set(held))              # no double-booking
        assert len(held) + c.free_blocks() == 12        # no leaks
        assert set(held).isdisjoint(c._free)

"""RBL sharding resolution: the shape-aware logical->physical rule engine."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import RULE_SETS, logical_to_pspec

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_train_batch_uses_pod_and_data():
    spec = logical_to_pspec((256, 4096), ("batch", None),
                            RULE_SETS["train"], MESH2)
    assert spec == P(("pod", "data"))


def test_single_pod_falls_back_to_data():
    spec = logical_to_pspec((256, 4096), ("batch", None),
                            RULE_SETS["train"], MESH1)
    assert spec == P("data")


def test_indivisible_heads_replicate():
    # qwen3: 40 heads % 16 != 0 -> replicated, seq takes model instead
    spec = logical_to_pspec((16, 4096, 40, 128),
                            ("batch", "seq", "heads", None),
                            RULE_SETS["train"], MESH1)
    assert spec == P("data", "model")        # heads entry dropped


def test_positional_priority_seq_before_heads():
    # dims resolve left->right: seq grabs "model" first; heads (32, also
    # divisible) then finds model used -> replicated. Both layouts keep the
    # causal softmax collective-free; positional priority keeps resolution
    # deterministic.
    spec = logical_to_pspec((16, 4096, 32, 128),
                            ("batch", "seq", "heads", None),
                            RULE_SETS["train"], MESH1)
    assert spec == P("data", "model")


def test_vocab_32001_replicates():
    # hymba vocab 32001 % 16 != 0 -> vocab dim replicated; "embed" has no
    # rule in the train set -> the table ends up fully replicated (correct:
    # a 98 MB table is cheap; correctness over cleverness)
    spec = logical_to_pspec((32001, 1600), ("vocab", "embed"),
                            RULE_SETS["train"], MESH1)
    assert spec == P()


def test_batch1_decode_seq_grabs_data_model():
    spec = logical_to_pspec((40, 1, 524288, 8, 128),
                            ("layers", "batch", "seq", "kv_heads", None),
                            RULE_SETS["decode"], MESH1)
    assert spec == P(None, None, ("data", "model"))


def test_decode_batch_and_seq():
    spec = logical_to_pspec((40, 128, 32768, 8, 128),
                            ("layers", "batch", "seq", "kv_heads", None),
                            RULE_SETS["decode"], MESH2)
    # batch -> (pod,data); seq -> ("data","model") blocked (data used) ->
    # "model"; kv_heads 8 % 16 -> replicated
    assert spec == P(None, ("pod", "data"), "model")


_LOGICAL = ["batch", "seq", "embed", "heads", "kv_heads", "mlp", "experts",
            "vocab", "fsdp", "state", "layers", None]


@given(st.lists(st.tuples(st.sampled_from(_LOGICAL),
                          st.integers(1, 4096)), min_size=1, max_size=5),
       st.sampled_from(["train", "prefill", "decode"]))
@settings(max_examples=200, deadline=None)
def test_property_resolver_invariants(dims, mode):
    """For ANY shape/axes combination: every mesh axis is used at most once
    and every sharded dim is divisible by its mesh-axes product."""
    axes = tuple(a for a, _ in dims)
    shape = tuple(s for _, s in dims)
    spec = logical_to_pspec(shape, axes, RULE_SETS[mode], MESH2)
    sizes = {"pod": 2, "data": 16, "model": 16}
    used = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            continue
        group = (entry,) if isinstance(entry, str) else tuple(entry)
        used.extend(group)
        total = int(np.prod([sizes[a] for a in group]))
        assert dim % total == 0
    assert len(used) == len(set(used))


def test_shard_noop_outside_context():
    import jax.numpy as jnp
    from repro.distributed.sharding import shard
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(shard(x, "batch", None), x)

"""Differential conformance matrix: the SAME RCB program must produce
bit-identical outputs through every execution path the runtime offers —

    run_interpreted (per-op decode + host sync, the OS-mediated baseline)
    run             (linked thunks, compiled dispatch)
    fuse            (one staged XLA program, the baremetal analogue)
    run_partitioned (per-tile-group stages pipelined over a TileMesh)

— and partitioned execution must be invariant to the tile-group count
(1 / 2 / 4). This is the interpreter/compiled-path boundary contract
OS-free runtimes live or die by (TFLM's conformance-testing lesson), over
a corpus spanning conv / matmul / quant / DMA / ALLOC-FREE mixes and the
ResNet-18 case study.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import rbl, rctc, rhal, rimfs
from repro.core.executor import Executor
from repro.core.rcb import Op, RCB, RCBOp, RCBProgram, TensorDesc

TILE_COUNTS = (1, 2, 4)

# Batched and coalesced execution (``run_batched``, the server's batch
# window) vmaps the program over a leading request axis. XLA may then sum
# a convolution in another order than the serial program does, so a lane
# agrees with its serial run to a few float32 ulp (max |diff| 2.98e-8 on
# conv_relu_softmax under XLA's CPU backend), not bit for bit. The
# interpreted == linked == fused identity stays exact: those paths run
# one executable shape.
BATCHED_RTOL = 1e-5
BATCHED_ATOL = 1e-6


def assert_batched_close(ref, got, err_msg: str = "") -> None:
    """A batched or coalesced output against its serial reference."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=BATCHED_RTOL, atol=BATCHED_ATOL,
                               err_msg=err_msg)


def _np(v):
    return np.asarray(jax.block_until_ready(v))


def _quant_mix_program():
    """QUANTIZE/DEQUANT + ALLOC/FREE + explicit DMA in one stream."""
    t = {
        "x": TensorDesc("x", (8, 8), "float32", "input"),
        "w": TensorDesc("w", (8, 8), "float32", "weight"),
        "xd": TensorDesc("xd", (8, 8), "float32", "scratch"),
        "g": TensorDesc("g", (8, 8), "float32", "scratch"),
        "q": TensorDesc("q", (8, 8), "int8", "scratch"),
        "dq": TensorDesc("dq", (8, 8), "float32", "scratch"),
        "s": TensorDesc("s", (8, 8), "float32", "scratch"),
        "a": TensorDesc("a", (8, 8), "float32", "scratch"),
        "output": TensorDesc("output", (8, 8), "float32", "output"),
    }
    blocks = [
        RCB(0, "layer", (), (
            RCBOp(Op.DMA_H2D, ("xd",), ("x",)),
            RCBOp(Op.GEMM, ("g",), ("xd", "w")),
        )),
        RCB(1, "layer", (0,), (
            RCBOp(Op.QUANTIZE, ("q",), ("g",), {"scale": 0.05}),
            RCBOp(Op.DEQUANT, ("dq",), ("q",), {"scale": 0.05}),
        )),
        RCB(2, "layer", (1,), (
            RCBOp(Op.ALLOC, ("s",), (), {"shape": [8, 8],
                                         "dtype": "float32"}),
            RCBOp(Op.ADD, ("a",), ("dq", "s")),
            RCBOp(Op.FREE, ("s",)),
            RCBOp(Op.RELU, ("output",), ("a",)),
            RCBOp(Op.FENCE),
        )),
    ]
    prog = RCBProgram("quant_mix", t, blocks)
    prog.validate()
    return prog


def _corpus(rng):
    """(name, program, weight files, inputs) for the conformance matrix."""
    n = 16
    cases = []
    cases.append((
        "matmul_dma",
        rctc.compile_matmul(n, with_dma=True),
        {"b": rng.randn(n, n).astype(np.float32)},
        {"a": rng.randn(n, n).astype(np.float32)},
    ))
    cases.append((
        "conv_relu_softmax",
        rctc.compile_conv_relu_softmax(),
        {"w_conv": rng.randn(3, 3, 3, 9).astype(np.float32)},
        {"input": rng.randn(1, 8, 8, 3).astype(np.float32)},
    ))
    K = 4
    cases.append((
        "dma_pipeline",
        rctc.compile_dma_pipeline(K, n),
        {"b": rng.randn(n, n).astype(np.float32)},
        {f"in{i}": rng.randn(n, n).astype(np.float32) for i in range(K)},
    ))
    cases.append((
        "transfer_stream",
        rctc.compile_transfer_pipeline(K, 256),
        {},
        {f"in{i}": rng.randn(256).astype(np.float32) for i in range(K)},
    ))
    cases.append((
        "gemm_chain",
        rctc.compile_gemm_chain(5, n),
        rctc.gemm_chain_weights(5, n),
        {"input": rng.randn(n, n).astype(np.float32)},
    ))
    cases.append((
        "quant_mix",
        _quant_mix_program(),
        {"w": rng.randn(8, 8).astype(np.float32)},
        {"x": rng.randn(8, 8).astype(np.float32)},
    ))
    return cases


def _reference(prog, files, inputs):
    """Single-device interpreted outputs (the conformance reference)."""
    fs = rimfs.mount(rimfs.pack(files)) if files else None
    ex = Executor()
    ref = ex.run_interpreted(rbl.bind(prog, rimfs=fs, inputs=dict(inputs)))
    return fs, ex, {k: _np(v) for k, v in ref.items()}


def _assert_same(ref: dict, got: dict, label: str, batched: bool = False):
    assert set(got) == set(ref), \
        f"{label}: outputs {sorted(got)} != {sorted(ref)}"
    check = assert_batched_close if batched else np.testing.assert_array_equal
    for k in ref:
        check(ref[k], _np(got[k]), err_msg=f"{label}: output {k!r} diverged")


_CASES = None


def _cases():
    global _CASES
    if _CASES is None:
        _CASES = _corpus(np.random.RandomState(0))
    return _CASES


@pytest.mark.parametrize("name", [c[0] for c in _corpus(
    np.random.RandomState(0))])
def test_conformance_linked_and_fused(name):
    name, prog, files, inputs = next(c for c in _cases() if c[0] == name)
    fs, ex, ref = _reference(prog, files, inputs)
    _assert_same(ref, ex.run(rbl.bind(prog, rimfs=fs, inputs=dict(inputs))),
                 f"{name}/linked")
    bound_f = rbl.bind(prog, rimfs=fs)
    fused = ex.fuse(bound_f)
    _assert_same(ref, fused(dict(inputs), ex.weights_from(bound_f)),
                 f"{name}/fused")


@pytest.mark.parametrize("n_groups", TILE_COUNTS)
@pytest.mark.parametrize("name", [c[0] for c in _corpus(
    np.random.RandomState(0))])
def test_conformance_partitioned(name, n_groups):
    name, prog, files, inputs = next(c for c in _cases() if c[0] == name)
    fs, ex, ref = _reference(prog, files, inputs)
    mesh = rhal.TileMesh(n_groups)
    bound = rbl.bind(prog, rimfs=fs, inputs=dict(inputs))
    got = ex.run_partitioned(bound, rimfs=fs, mesh=mesh)
    _assert_same(ref, got, f"{name}/partitioned@{n_groups}")
    part = bound._partitions[mesh.n_groups]
    # the mesh's movement accounting covers exactly the cut-edge table
    assert mesh.moved_bytes() == part.cut_bytes()


@pytest.mark.parametrize("hw", [(1, 1), (3, 5), (7, 7), (8, 8)])
def test_avgpool_global_sums_alike_fused_and_alone(hw):
    """A RELU fused into global average pooling by one staged executable
    gives the same bits as the two ops run one by one, and the mean agrees
    with ``jnp.mean``."""
    from repro.core import oplib
    x = np.random.RandomState(sum(hw)).randn(2, *hw, 9).astype(np.float32)
    alone = _np(oplib.avgpool_global(oplib.relu(jnp.asarray(x), {}), {}))
    fused = _np(jax.jit(lambda a: oplib.avgpool_global(
        oplib.relu(a, {}), {}))(x))
    np.testing.assert_array_equal(fused, alone)
    np.testing.assert_allclose(alone, np.maximum(x, 0).mean(axis=(1, 2)),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("n_groups", TILE_COUNTS)
def test_conformance_resnet18(n_groups):
    """The paper's case study through all four paths at every tile count."""
    from repro.models import resnet as rn
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    fs = rimfs.mount(image)
    rng = np.random.RandomState(1)
    x = rng.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)
    ex = Executor()
    ref = {k: _np(v) for k, v in ex.run_interpreted(
        rbl.bind(prog, rimfs=fs, inputs={"input": x})).items()}
    _assert_same(ref, ex.run(rbl.bind(prog, rimfs=fs,
                                      inputs={"input": x})),
                 "resnet/linked")
    bound_f = rbl.bind(prog, rimfs=fs)
    fused = ex.fuse(bound_f)
    _assert_same(ref, fused({"input": x}, ex.weights_from(bound_f)),
                 "resnet/fused")
    bound_p = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    mesh = rhal.TileMesh(n_groups)
    _assert_same(ref, ex.run_partitioned(bound_p, rimfs=fs, mesh=mesh),
                 f"resnet/partitioned@{n_groups}")
    if n_groups > 1:
        part = bound_p._partitions[mesh.n_groups]
        assert part.edges, "ResNet partition must have cut edges"
        assert mesh.moved_bytes() == part.cut_bytes()
        # every tile group that ran compute got its own residency plan
        plans = [t.residency(mesh.group(t.gid).driver)
                 for t in part.tiles]
        assert all(p is not None for p in plans)
        assert all(p.high_water >= 0 for p in plans)


def test_partitioned_reuses_bound_weights_without_rimfs():
    """Regression: a BoundProgram whose weights already resolved at bind
    time must run partitioned WITHOUT re-supplying the image — the tile
    re-binds reuse the original bind's weight buffers."""
    rng = np.random.RandomState(3)
    prog = rctc.compile_gemm_chain(4, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(4, 8)))
    x = rng.randn(8, 8).astype(np.float32)
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    ref = {k: _np(v) for k, v in ex.run(bound).items()}
    got = ex.run_partitioned(bound, mesh=rhal.TileMesh(2))   # no rimfs=
    _assert_same(ref, got, "bound-weights/partitioned@2")


def test_tile_bind_cache_stays_bounded():
    """Regression: orchestrating the same BoundProgram over many FRESH
    meshes (post-failure replacement) must not retain every discarded
    mesh's bindings — the per-tile bind cache evicts."""
    from repro.core.partition import _BIND_CACHE_CAP
    prog = rctc.compile_gemm_chain(3, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(3, 8)))
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    ex = Executor()
    ref = {k: _np(v) for k, v in ex.run(bound).items()}
    for _ in range(_BIND_CACHE_CAP + 4):
        got = ex.run_partitioned(bound, rimfs=fs, mesh=rhal.TileMesh(2))
        _assert_same(ref, got, "fresh-mesh loop")
    part = bound._partitions[2]
    assert all(len(t._bound) <= _BIND_CACHE_CAP for t in part.tiles)


def test_partition_is_deterministic():
    """Re-partitioning yields the identical cut-edge table (the partition
    is static data, like every other plan in the runtime)."""
    from repro.core import partition as partition_mod
    prog = rctc.compile_gemm_chain(6, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(6, 8)))
    bound = rbl.bind(prog, rimfs=fs)
    p1 = partition_mod.partition(bound, 3)
    p2 = partition_mod.partition(bound, 3)
    assert p1.edges == p2.edges
    assert [t.program.name for t in p1.tiles] == \
        [t.program.name for t in p2.tiles]
    for a, b in zip(p1.tiles, p2.tiles):
        assert a.cut_ins == b.cut_ins and a.cut_outs == b.cut_outs


# ---------------------------------------------------------------------------
# Batched execution (run_batched): the batch axis must be invisible
# ---------------------------------------------------------------------------

BATCH_NS = (1, 3, 4, 6, 11)       # covers exact buckets AND pad-to-bucket


def _rand_inputs_like(inputs: dict, rng) -> dict:
    return {k: rng.randn(*np.asarray(v).shape).astype(
        np.asarray(v).dtype) for k, v in inputs.items()}


@pytest.mark.parametrize("n", BATCH_NS)
@pytest.mark.parametrize("name", ["conv_relu_softmax", "gemm_chain"])
def test_batched_matches_serial(name, n):
    """run_batched over N random inputs == N serial run / run_interpreted
    calls within the batched tolerance — including N that are not bucket
    sizes (the pad-to-bucket + slice-back path)."""
    from repro.core import linker
    name, prog, files, inputs = next(c for c in _cases() if c[0] == name)
    rng = np.random.RandomState(100 + n)
    batch = [_rand_inputs_like(inputs, rng) for _ in range(n)]
    fs = rimfs.mount(rimfs.pack(files)) if files else None
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    assert linker.batch_analysis(bound).batchable
    outs = ex.run_batched(bound, batch)
    assert ex.batch_stats["batchable"] and len(outs) == n
    assert sum(ex.batch_stats["buckets"]) - ex.batch_stats["padded"] == n
    for req, got in zip(batch, outs):
        ref = ex.run_interpreted(rbl.bind(prog, rimfs=fs,
                                          inputs=dict(req)))
        _assert_same({k: _np(v) for k, v in ref.items()}, got,
                     f"{name}/batched@{n} vs interpreted", batched=True)
        ref2 = ex.run(bound, inputs=dict(req), rimfs=fs)
        _assert_same({k: _np(v) for k, v in ref2.items()}, got,
                     f"{name}/batched@{n} vs linked", batched=True)


@pytest.mark.parametrize("n", (1, 5, 8))
def test_batched_resnet18_matches_serial(n):
    """The paper's case study through the batch-axis path (the benchmark
    gate's correctness side)."""
    from repro.models import resnet as rn
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    fs = rimfs.mount(image)
    rng = np.random.RandomState(n)
    batch = [{"input": rng.rand(1, cfg.image_size, cfg.image_size, 3)
              .astype(np.float32)} for _ in range(n)]
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    outs = ex.run_batched(bound, batch)
    assert ex.batch_stats["batchable"]
    for req, got in zip(batch, outs):
        ref = {k: _np(v) for k, v in ex.run(bound, inputs=req).items()}
        _assert_same(ref, got, f"resnet/batched@{n}", batched=True)


@pytest.mark.parametrize("name", ["matmul_dma", "dma_pipeline",
                                  "transfer_stream", "quant_mix"])
def test_non_batchable_split_phase_dma_falls_back(name):
    """Programs with host-split-phase DMA (prefetch/drain schedules) must
    NOT stage under vmap — run_batched falls back to serial linked
    execution with identical results, and reports why."""
    from repro.core import linker
    name, prog, files, inputs = next(c for c in _cases() if c[0] == name)
    rng = np.random.RandomState(7)
    batch = [_rand_inputs_like(inputs, rng) for _ in range(3)]
    fs = rimfs.mount(rimfs.pack(files)) if files else None
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    verdict = linker.batch_analysis(bound)
    assert not verdict.batchable and "DMA" in verdict.reason
    outs = ex.run_batched(bound, batch, rimfs=fs)
    assert not ex.batch_stats["batchable"]
    assert ex.batch_stats["buckets"] == []       # nothing staged
    for req, got in zip(batch, outs):
        ref = ex.run_interpreted(rbl.bind(prog, rimfs=fs,
                                          inputs=dict(req)), rimfs=fs)
        _assert_same({k: _np(v) for k, v in ref.items()}, got,
                     f"{name}/fallback")


def test_non_batchable_graph_exec_falls_back():
    """GRAPH_EXEC artifacts are opaque host callables — the analysis must
    reject them and the fallback must still run them correctly."""
    from repro.core import linker
    from repro.core.rcb import RCBOp
    t = {
        "x": TensorDesc("x", (4, 4), "float32", "input"),
        "y": TensorDesc("y", (4, 4), "float32", "scratch"),
        "output": TensorDesc("output", (4, 4), "float32", "output"),
    }
    prog = RCBProgram("ge", t, [RCB(0, "layer", (), (
        RCBOp(Op.GRAPH_EXEC, ("y",), ("x",), {"artifact": "double"}),
        RCBOp(Op.RELU, ("output",), ("y",)),
    ))], {"double": lambda x: x * 2.0})
    prog.validate()
    rng = np.random.RandomState(0)
    batch = [{"x": rng.randn(4, 4).astype(np.float32)} for _ in range(3)]
    ex = Executor()
    bound = rbl.bind(prog)
    verdict = linker.batch_analysis(bound)
    assert not verdict.batchable and "GRAPH_EXEC" in verdict.reason
    outs = ex.run_batched(bound, batch)
    assert not ex.batch_stats["batchable"]
    for req, got in zip(batch, outs):
        np.testing.assert_array_equal(
            np.maximum(req["x"] * 2.0, 0), _np(got["output"]))


def test_batched_callable_cache_shared_across_binds():
    """The bucket cache is keyed (program CRC, bucket): a re-bind of the
    same program must reuse the staged executable, not re-trace."""
    prog = rctc.compile_gemm_chain(3, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(3, 8)))
    ex = Executor()
    b1, b2 = rbl.bind(prog, rimfs=fs), rbl.bind(prog, rimfs=fs)
    f1 = ex._batched_callable(b1, 4)
    f2 = Executor()._batched_callable(b2, 4)     # fresh executor too
    assert f1 is f2
    assert ex._batched_callable(b1, 2) is not f1  # per-bucket staging


def test_fuse_cached_on_bound_program():
    """Satellite: fuse() must return the SAME jitted callable for
    repeated calls (keyed by donate_weights) instead of re-linking and
    re-tracing per call."""
    prog = rctc.compile_gemm_chain(3, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(3, 8)))
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    f1 = ex.fuse(bound)
    assert ex.fuse(bound) is f1
    assert Executor().fuse(bound) is f1          # cache rides the bound
    fd = ex.fuse(bound, donate_weights=True)
    assert fd is not f1 and ex.fuse(bound, donate_weights=True) is fd
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    ref = {k: _np(v) for k, v in ex.run(
        rbl.bind(prog, rimfs=fs, inputs={"input": x})).items()}
    _assert_same(ref, f1({"input": x}, ex.weights_from(bound)),
                 "fuse-cache")


# ---------------------------------------------------------------------------
# Streaming pipeline fill (execute_stream)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", (True, False))
@pytest.mark.parametrize("n_groups", (1, 2, 4))
def test_stream_matches_serial_in_order(n_groups, fused):
    """execute_stream over M inputs yields, in submission order, outputs
    bit-identical to M serial executions — at every group count, in both
    fused-stage and linked-stage mode, including M smaller than the
    pipeline depth."""
    from repro.core import partition as partition_mod
    prog = rctc.compile_gemm_chain(5, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(5, 8)))
    rng = np.random.RandomState(2)
    xs = [{"input": rng.randn(8, 8).astype(np.float32)}
          for _ in range(7)]
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    refs = [{k: _np(v) for k, v in ex.run(bound, inputs=x).items()}
            for x in xs]
    mesh = rhal.TileMesh(n_groups)
    part = partition_mod.partition(bound, n_groups)
    for depth in (1, 4):
        got = list(partition_mod.execute_stream(
            part, mesh, iter(xs), rimfs=fs, depth=depth, fused=fused))
        assert len(got) == len(xs)
        for i, (ref, out) in enumerate(zip(refs, got)):
            _assert_same(ref, out,
                         f"stream@{n_groups}/depth{depth}/sample{i}")


def test_stream_resnet18_matches_serial():
    from repro.core import partition as partition_mod
    from repro.models import resnet as rn
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    fs = rimfs.mount(image)
    rng = np.random.RandomState(5)
    xs = [{"input": rng.rand(1, cfg.image_size, cfg.image_size, 3)
           .astype(np.float32)} for _ in range(6)]
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)
    refs = [{k: _np(v) for k, v in ex.run(bound, inputs=x).items()}
            for x in xs]
    mesh = rhal.TileMesh(2)
    part = partition_mod.partition(bound, 2)
    stats: dict = {}
    got = list(partition_mod.execute_stream(part, mesh, iter(xs),
                                            rimfs=fs, depth=4,
                                            stats=stats))
    for i, (ref, out) in enumerate(zip(refs, got)):
        _assert_same(ref, out, f"resnet-stream/sample{i}")
    assert stats["samples"] == len(xs)
    assert all(b >= 0 for b in stats["busy"].values())


def test_stream_without_rimfs_reuses_bound_weights():
    """Stream mode must work from a weights-resolved bind with no image
    round-trip, like execute()."""
    from repro.core import partition as partition_mod
    prog = rctc.compile_gemm_chain(4, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(4, 8)))
    rng = np.random.RandomState(3)
    xs = [{"input": rng.randn(8, 8).astype(np.float32)} for _ in range(4)]
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs)      # weights resolved HERE
    refs = [{k: _np(v) for k, v in ex.run(bound, inputs=x).items()}
            for x in xs]
    part = partition_mod.partition(bound, 2)
    got = list(partition_mod.execute_stream(part, rhal.TileMesh(2),
                                            iter(xs)))   # no rimfs=
    for ref, out in zip(refs, got):
        _assert_same(ref, out, "stream/no-rimfs")


def test_stream_propagates_tile_failure():
    """No silent drops: a dead group surfaces as TileFailure (stream mode
    documents no-requeue; elasticity stays with execute())."""
    from repro.core import partition as partition_mod
    from repro.core.rhal import TileFailure
    prog = rctc.compile_gemm_chain(4, 8)
    fs = rimfs.mount(rimfs.pack(rctc.gemm_chain_weights(4, 8)))
    xs = [{"input": np.random.RandomState(1).randn(8, 8)
           .astype(np.float32)} for _ in range(4)]
    bound = rbl.bind(prog, rimfs=fs)
    mesh = rhal.TileMesh(2)
    part = partition_mod.partition(bound, 2)
    list(partition_mod.execute_stream(part, mesh, iter(xs[:1]),
                                      rimfs=fs))         # healthy warm-up
    mesh.kill(1)
    with pytest.raises(TileFailure):
        # fused stages bypass per-op vtable dispatch, but the cut-edge
        # stream into the dead consumer group still touches its driver
        list(partition_mod.execute_stream(part, mesh, iter(xs),
                                          rimfs=fs))


# ---------------------------------------------------------------------------
# Hypothesis-generated programs
# ---------------------------------------------------------------------------


def _random_program(draw_ops):
    """Random straight-line program over (4, 6) f32 buffers (the
    test_executor_property generator, reused for the partition
    matrix): every intermediate symbol is a potential cut edge."""
    tensors = {
        "in0": TensorDesc("in0", (4, 6), "float32", "input"),
        "w0": TensorDesc("w0", (6, 6), "float32", "weight"),
    }
    syms = ["in0"]
    ops = []
    for i, choice in enumerate(draw_ops):
        src = syms[choice % len(syms)]
        dst = f"t{i}"
        kind = choice % 4
        tensors[dst] = TensorDesc(dst, (4, 6), "float32", "scratch")
        if kind == 0:
            ops.append(RCBOp(Op.RELU, (dst,), (src,)))
        elif kind == 1:
            ops.append(RCBOp(Op.SOFTMAX, (dst,), (src,), {"axis": -1}))
        elif kind == 2:
            other = syms[(choice // 4) % len(syms)]
            ops.append(RCBOp(Op.ADD, (dst,), (src, other)))
        else:
            ops.append(RCBOp(Op.GEMM, (dst,), (src, "w0")))
        syms.append(dst)
    out = syms[-1]
    tensors[out] = TensorDesc(out, (4, 6), "float32", "output")
    prog = RCBProgram("rand", tensors,
                      [RCB(0, "layer", (), tuple(ops))])
    prog.validate()
    return prog


@given(st.lists(st.integers(0, 1000), min_size=2, max_size=16),
       st.sampled_from(TILE_COUNTS))
@settings(max_examples=25, deadline=None)
def test_property_partitioned_matches_linked(draw_ops, n_groups):
    prog = _random_program(draw_ops)
    rng = np.random.RandomState(0)
    fs = rimfs.mount(rimfs.pack(
        {"w0": rng.randn(6, 6).astype(np.float32)}))
    x = rng.randn(4, 6).astype(np.float32)
    ex = Executor()
    ref = {k: _np(v) for k, v in ex.run(
        rbl.bind(prog, rimfs=fs, inputs={"in0": x})).items()}
    bound = rbl.bind(prog, rimfs=fs, inputs={"in0": x})
    got = ex.run_partitioned(bound, rimfs=fs,
                             mesh=rhal.TileMesh(n_groups))
    _assert_same(ref, got, f"rand/partitioned@{n_groups}")


# ---------------------------------------------------------------------------
# Kernel conformance matrix (DESIGN.md §13: registry handlers)
# ---------------------------------------------------------------------------
#
# Every kernel × {pallas-interpret, ref} × {fp32, bf16} over deliberately
# awkward shapes (odd head_dim, GQA grouping, ragged sequence lengths): the
# registry's fallback ladder must agree with the pure-jnp reference within
# dtype tolerance, and kernel opcodes dispatched through link_compute must
# match the same registry call made eagerly.

import jax.numpy as jnp

from repro.kernels import registry as kreg

_KTOL = {"float32": 5e-4, "bfloat16": 3e-2}


def _kernel_args(kernel, dtype, shape_tag, rng):
    dt = jnp.dtype(dtype)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), dt)

    if kernel == "attention":
        # odd head_dim / GQA grouping / ragged (non-multiple-of-block) seq
        b, s, h, hkv, d = {
            "odd_head": (2, 16, 4, 4, 12),
            "gqa": (2, 16, 6, 2, 16),
            "ragged": (1, 13, 4, 2, 16),
        }[shape_tag]
        return (arr(b, s, h, d), arr(b, s, hkv, d), arr(b, s, hkv, d)), \
            {"causal": True}
    if kernel == "matmul_int8":
        m, k, n = {"odd_head": (8, 24, 16), "gqa": (16, 32, 8),
                   "ragged": (8, 16, 24)}[shape_tag]
        x = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
        w = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
        scale = jnp.asarray(np.abs(rng.randn(n)) + 0.1, jnp.float32)
        return (x, w, scale), {"out_dtype": dtype}
    if kernel == "ssm_scan":
        b, t, di, n = {"odd_head": (2, 8, 6, 3), "gqa": (1, 16, 8, 4),
                       "ragged": (2, 13, 4, 4)}[shape_tag]
        da = -jnp.abs(arr(b, t, di, n))
        return (da, arr(b, t, di, n), arr(b, t, n)), {}
    if kernel == "wkv6":
        b, t, h, k = {"odd_head": (2, 8, 2, 6), "gqa": (1, 16, 3, 8),
                      "ragged": (2, 13, 2, 8)}[shape_tag]
        lw = -jnp.abs(arr(b, t, h, k)).clip(0.05, 3.0)
        return (arr(b, t, h, k), arr(b, t, h, k), arr(b, t, h, k), lw,
                arr(h, k)), {}
    raise AssertionError(kernel)


@pytest.mark.parametrize("shape_tag", ["odd_head", "gqa", "ragged"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", list(kreg.KERNEL_NAMES))
def test_kernel_matrix_pallas_matches_ref(kernel, dtype, shape_tag):
    rng = np.random.RandomState(7)
    args, kwargs = _kernel_args(kernel, dtype, shape_tag, rng)
    ref = kreg.call(kernel, *args, impl="ref", **kwargs)
    got = kreg.call(kernel, *args, impl="pallas", **kwargs)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6
    assert err / scale < _KTOL[dtype], \
        f"{kernel}/{dtype}/{shape_tag}: rel err {err / scale:.3e}"


@pytest.mark.parametrize("kernel,opcode", [
    ("attention", Op.ATTENTION), ("matmul_int8", Op.MATMUL_INT8),
    ("ssm_scan", Op.SSM_SCAN), ("wkv6", Op.WKV6)])
def test_linked_kernel_op_matches_eager_registry(kernel, opcode):
    """Op.X through Executor.run's link_compute handler == registry.call."""
    rng = np.random.RandomState(3)
    args, kwargs = _kernel_args(kernel, "float32", "gqa", rng)
    eager = kreg.call(kernel, *args, **kwargs)
    t = {}
    srcs = []
    for i, a in enumerate(args):
        nm = f"in{i}"
        t[nm] = TensorDesc(nm, tuple(a.shape), str(a.dtype), "input")
        srcs.append(nm)
    t["out"] = TensorDesc("out", tuple(eager.shape), str(eager.dtype),
                          "output")
    attrs = {"causal": True} if kernel == "attention" else {}
    prog = RCBProgram(f"k_{kernel}", t, [RCB(0, "layer", (), (
        RCBOp(opcode, ("out",), tuple(srcs), attrs),
        RCBOp(Op.FENCE),
    ))])
    prog.validate()
    ex = Executor()
    ins = {f"in{i}": a for i, a in enumerate(args)}
    for label, out in (
            ("interp", ex.run_interpreted(rbl.bind(prog,
                                                   inputs=dict(ins)))),
            ("linked", ex.run(rbl.bind(prog, inputs=dict(ins))))):
        np.testing.assert_allclose(
            _np(out["out"]), _np(eager), rtol=0, atol=1e-6,
            err_msg=f"{kernel}/{label} diverged from registry.call")


# ---------------------------------------------------------------------------
# Per-layer LM lowering conformance (three families through the engine)
# ---------------------------------------------------------------------------

_LM_CONFIGS = ("qwen2-1.5b", "rwkv6-1.6b", "hymba-1.5b")


def _lm_program(name, B=2, S=8):
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.common import init_params
    cfg = get_config(name + "-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    logits, _, _ = tf.forward_full(cfg, params, tokens)
    prog, image = rctc.compile_transformer_block(cfg, params, B, S)
    glob, _ = tf.split_params(params)
    ins = {"hidden": tf.embed_inputs(cfg, glob, tokens)}
    if "positions" in prog.tensors:
        ins["positions"] = np.broadcast_to(
            np.arange(S, dtype=np.int32)[None], (B, S)).copy()
    return cfg, prog, image, ins, _np(logits)


@pytest.mark.parametrize("name", _LM_CONFIGS)
def test_lm_block_program_matches_eager(name):
    """compile_transformer_block → linked kernel ops == eager forward_full
    (fp32 smoke configs: tight tolerance)."""
    cfg, prog, image, ins, ref_logits = _lm_program(name)
    # the mixers must be exposed as kernel opcodes, not a monolithic artifact
    kinds = {op.op for blk in prog.blocks for op in blk.ops}
    want = {"dense": Op.ATTENTION, "ssm": Op.WKV6,
            "hybrid": Op.SSM_SCAN}[cfg.family]
    assert want in kinds
    fs = rimfs.mount(image)
    ex = Executor()
    for label, runner in (("interp", ex.run_interpreted), ("linked", ex.run)):
        out = runner(rbl.bind(prog, rimfs=fs, inputs=dict(ins)))["logits"]
        np.testing.assert_allclose(
            _np(out), ref_logits, rtol=0, atol=5e-4,
            err_msg=f"{name}/{label} logits diverged from eager model")


def test_lm_block_program_through_platform_engine():
    """Provision → bind → linked dispatch through the RTPM platform — the
    serving-engine path for per-layer programs."""
    from repro.core.rtpm import Platform
    cfg, prog, image, ins, ref_logits = _lm_program("qwen2-1.5b")
    plat = Platform()
    plat.provision(image=image, program=prog)
    ex = Executor()
    out = ex.run(plat.bind(inputs=dict(ins)))["logits"]
    np.testing.assert_allclose(_np(out), ref_logits, rtol=0, atol=5e-4)


def test_autotune_cache_reloads_at_provision_with_zero_trials():
    """Tune → pack winners into the image → fresh provision reloads them:
    the second provision's autotune does ZERO sweep trials."""
    from repro.core.rtpm import Platform
    rng = np.random.RandomState(11)
    args, kwargs = _kernel_args("ssm_scan", "float32", "ragged", rng)
    kreg.reset()
    try:
        params1, trials1 = kreg.autotune("ssm_scan", *args, **kwargs)
        assert trials1 > 0, "first provision must sweep"
        image = kreg.pack_image()
        kreg.reset()
        assert kreg.REGISTRY.sweep_trials == 0
        plat = Platform()
        plat.provision(image=image)          # reload path under test
        params2, trials2 = kreg.autotune("ssm_scan", *args, **kwargs)
        assert trials2 == 0, "second provision re-swept the space"
        assert kreg.REGISTRY.sweep_trials == 0
        assert params2 == params1
    finally:
        kreg.reset()


def test_mamba_routes_through_ssm_kernel(monkeypatch):
    """Regression: AEG_SSM_IMPL=kernel sends mamba_mix through the registry
    ssm_scan handler and matches the jnp associative-scan path."""
    from repro.configs import get_config
    from repro.models import mamba as mam
    from repro.models.common import init_params
    cfg = get_config("hymba-1.5b-smoke")
    specs = mam.mamba_specs(cfg)
    params = init_params(jax.random.PRNGKey(0), specs)
    pl = jax.tree.map(lambda a: a[0], params)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 12, cfg.d_model), jnp.float32)
    h0 = jnp.asarray(rng.randn(2, cfg.d_model, cfg.ssm_state), jnp.float32)
    monkeypatch.delenv("AEG_SSM_IMPL", raising=False)
    y_jnp, h_jnp = mam.mamba_mix(cfg, pl, x, h0)
    monkeypatch.setenv("AEG_SSM_IMPL", "kernel")
    y_k, h_k = mam.mamba_mix(cfg, pl, x, h0)
    np.testing.assert_allclose(_np(y_k), _np(y_jnp), rtol=0, atol=5e-5)
    np.testing.assert_allclose(_np(h_k), _np(h_jnp), rtol=0, atol=5e-5)


def test_rwkv_routes_through_wkv_kernel(monkeypatch):
    """AEG_WKV_IMPL=kernel sends time_mix through the registry wkv6 handler
    (with the nonzero-s0 correction) and matches the chunked-scan path."""
    from repro.configs import get_config
    from repro.models import rwkv6 as rwkv
    from repro.models.common import init_params
    cfg = get_config("rwkv6-1.6b-smoke")
    params = init_params(jax.random.PRNGKey(0), rwkv.rwkv_specs(cfg))
    pl = jax.tree.map(lambda a: a[0], params)
    rng = np.random.RandomState(6)
    B, T, d = 2, 12, cfg.d_model
    K = cfg.rwkv_head_dim
    H = d // K
    x = jnp.asarray(rng.randn(B, T, d), jnp.float32)
    ts = jnp.asarray(rng.randn(B, d), jnp.float32)
    s0 = jnp.asarray(rng.randn(B, H, K, K), jnp.float32)
    monkeypatch.delenv("AEG_WKV_IMPL", raising=False)
    y_jnp, _, s_jnp = rwkv.time_mix(cfg, pl, x, ts, s0)
    monkeypatch.setenv("AEG_WKV_IMPL", "kernel")
    y_k, _, s_k = rwkv.time_mix(cfg, pl, x, ts, s0)
    np.testing.assert_allclose(_np(y_k), _np(y_jnp), rtol=0, atol=5e-4)
    np.testing.assert_allclose(_np(s_k), _np(s_jnp), rtol=0, atol=5e-4)

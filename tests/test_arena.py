"""DeviceArena + residency plan: offset discipline, alignment, coalescing,
high-water accounting, and the linker's static transfer schedule."""
import numpy as np
import pytest

import jax

from repro.core import linker, rbl, rctc, rhal, rimfs
from repro.core.executor import Executor
from repro.core.rhal import ArenaError, DeviceArena

from hypothesis import given, settings, strategies as st


# ---------------------------------------------------------------------------
# Arena unit tests
# ---------------------------------------------------------------------------

def test_arena_alignment_and_high_water():
    a = DeviceArena(1 << 16, debug=True)
    o1 = a.alloc(1)                       # rounds up to one 128B lane
    o2 = a.alloc(129)                     # rounds up to 256
    assert o1 % 128 == 0 and o2 % 128 == 0
    assert a.bytes_in_use == 128 + 256
    assert a.high_water == 384
    a.free(o1)
    assert a.bytes_in_use == 256
    assert a.high_water == 384            # high-water is sticky


def test_arena_free_returns_range_and_coalesces():
    a = DeviceArena(1024, debug=True)
    offs = [a.alloc(128) for _ in range(8)]      # slab now full
    with pytest.raises(ArenaError, match="exhausted"):
        a.alloc(1)
    for o in offs[2:5]:                   # free a middle run
        a.free(o)
    # coalesced: one 384B hole serves a 384B request
    o = a.alloc(384)
    assert o == offs[2]
    a.free(o)
    for o in (offs[0], offs[1], offs[5], offs[6], offs[7]):
        a.free(o)
    assert a.bytes_in_use == 0
    assert a._free == [(0, 1024)]         # fully re-coalesced


def test_arena_double_free_raises():
    a = DeviceArena(1024)
    o = a.alloc(128)
    a.free(o)
    with pytest.raises(ArenaError, match="unallocated"):
        a.free(o)
    with pytest.raises(ArenaError, match="unallocated"):
        a.free(999)


def test_eager_driver_free_returns_offsets(rng):
    """The satellite bugfix: HalDriver.free must actually return the
    buffer's range to the arena free-list (it used to only count)."""
    drv = rhal.make_eager_driver(debug_arena=True)
    base = drv.arena.bytes_in_use
    bufs = [drv.alloc((64, 64), "float32") for _ in range(4)]
    assert drv.arena.bytes_in_use == base + 4 * 64 * 64 * 4
    for b in bufs:
        drv.free(b)
    assert drv.arena.bytes_in_use == base        # all ranges returned
    drv.arena.check()                            # invariants hold (debug)


def test_freed_scratch_read_before_free_does_not_leak():
    """Regression: a scratch that is READ and then explicitly FREEd must
    reach the FREE thunk as a real buffer (not reference-dropped at last
    read), so its arena range is returned — repeated executions keep
    bytes_in_use flat instead of leaking one range per run."""
    from repro.core.rcb import Op, RCB, RCBOp, RCBProgram, TensorDesc
    t = {
        "x": TensorDesc("x", (32,), "float32", "input"),
        "s": TensorDesc("s", (32,), "float32", "scratch"),
        "y": TensorDesc("y", (32,), "float32", "output"),
    }
    ops = [RCBOp(Op.ALLOC, ("s",), (), {"shape": [32],
                                        "dtype": "float32"}),
           RCBOp(Op.ADD, ("y",), ("x", "s")),     # s's last read
           RCBOp(Op.FREE, ("s",))]                # then the explicit FREE
    prog = RCBProgram("leak", t, [RCB(0, "layer", (), tuple(ops))])
    drv = rhal.make_eager_driver(debug_arena=True)
    ex = Executor(driver=drv)
    x = np.ones(32, np.float32)
    base = drv.arena.bytes_in_use
    bound = rbl.bind(prog, inputs={"x": x})
    for _ in range(5):
        out = ex.run(bound, inputs={"x": x})
        assert "y" in out
        assert drv.arena.bytes_in_use == base     # linked: no leak
    for _ in range(5):
        ex.run_interpreted(bound, inputs={"x": x})
        assert drv.arena.bytes_in_use == base     # interpreted: no leak


def test_blocking_driver_plan_advertises_no_overlap(rng):
    """A driver without async DMA slots executes everything blocking —
    its LinkedProgram's plan must not report split-phase bytes."""
    import dataclasses
    drv = rhal.make_eager_driver()
    drv = dataclasses.replace(drv, dma_async=None, dma_wait=None,
                              dma_async_batch=None)
    K, n = 2, 8
    prog = rctc.compile_dma_pipeline(K, n)
    fs = rimfs.mount(rimfs.pack({"b": rng.randn(n, n)
                                 .astype(np.float32)}))
    ins = {f"in{i}": rng.randn(n, n).astype(np.float32) for i in range(K)}
    linked = linker.link(rbl.bind(prog, rimfs=fs, inputs=ins), drv)
    assert linked.residency.bytes_overlapped == 0
    assert linked.residency.prefetch_syms == ()
    assert linked.prologue == () and linked.epilogue == ()
    assert linked.residency.bytes_moved == 2 * K * n * n * 4


def test_alloc_free_ops_roundtrip_through_arena():
    """Explicit ALLOC/FREE RCB ops drive the arena through the vtable."""
    from repro.core.rcb import Op, RCB, RCBOp, RCBProgram, TensorDesc
    t = {
        "x": TensorDesc("x", (4,), "float32", "input"),
        "s": TensorDesc("s", (32, 32), "float32", "scratch"),
        "y": TensorDesc("y", (4,), "float32", "output"),
    }
    ops = [RCBOp(Op.ALLOC, ("s",), (), {"shape": [32, 32],
                                        "dtype": "float32"}),
           RCBOp(Op.FREE, ("s",)),
           RCBOp(Op.PASSTHROUGH, ("y",), ("x",))]
    prog = RCBProgram("af", t, [RCB(0, "layer", (), tuple(ops))])
    drv = rhal.make_eager_driver(debug_arena=True)
    ex = Executor(driver=drv)
    base = drv.arena.bytes_in_use
    out = ex.run(rbl.bind(prog, inputs={"x": np.ones(4, np.float32)}))
    assert "y" in out
    assert drv.arena.bytes_in_use == base        # ALLOC's range was freed


# ---------------------------------------------------------------------------
# Split-phase DMA ticket protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("direction", ["h2d", "d2h"])
def test_dma_ticket_double_wait_raises_eager(direction):
    """Regression (satellite fix): a DmaTicket could be redeemed twice
    silently — on a raw-pointer backend the descriptor is recycled at
    wait, so the second wait would observe another transfer's state."""
    drv = rhal.make_eager_driver()
    host = np.ones(32, np.float32)
    buf = host if direction == "h2d" \
        else drv.wait_dma(drv.initiate_dma(host, "h2d"))
    t = drv.dma_async(buf, direction)
    drv.dma_wait(t)
    with pytest.raises(rhal.DmaError, match="redeemed"):
        drv.dma_wait(t)


def test_dma_ticket_double_wait_raises_trace():
    drv = rhal.make_trace_driver()
    t = drv.dma_async(np.ones(8, np.float32), "h2d")
    drv.dma_wait(t)
    with pytest.raises(rhal.DmaError, match="redeemed"):
        drv.dma_wait(t)


def test_dma_batch_tickets_each_redeem_once():
    drv = rhal.make_eager_driver()
    hosts = [np.full(16, i, np.float32) for i in range(3)]
    tickets = drv.dma_async_batch(hosts, "h2d")
    for t in tickets:
        drv.dma_wait(t)
    for t in tickets:
        with pytest.raises(rhal.DmaError, match="redeemed"):
            drv.dma_wait(t)


# ---------------------------------------------------------------------------
# Residency plan
# ---------------------------------------------------------------------------

def _aligned(n):
    return (n + 127) // 128 * 128


def test_plan_dma_pipeline_schedule(rng):
    K, n = 4, 16
    prog = rctc.compile_dma_pipeline(K, n)
    fs = rimfs.mount(rimfs.pack({"b": rng.randn(n, n)
                                 .astype(np.float32)}))
    ins = {f"in{i}": rng.randn(n, n).astype(np.float32) for i in range(K)}
    bound = rbl.bind(prog, rimfs=fs, inputs=ins)
    plan = linker.plan_residency(bound)
    # every H2D is prefetchable (sources live at entry), every D2H drains
    assert len(plan.prefetch_syms) == K
    assert len(plan.drain_syms) == K
    assert plan.bytes_moved == 2 * K * n * n * 4       # K h2d + K d2h
    assert plan.bytes_overlapped == plan.bytes_moved   # 100% split-phase
    # steady-state residency: weight + one dev + one acc buffer
    blk = _aligned(n * n * 4)
    assert plan.high_water == 3 * blk
    # dead dev/acc ranges are donated to later stages
    assert len(plan.donated) >= 1
    # offsets aligned and pairwise disjoint while simultaneously live is
    # guaranteed by the arena; spot-check alignment here
    assert all(o % 128 == 0 for o in plan.offsets.values())


def test_plan_high_water_matches_arena_replay(rng):
    """Replaying the plan's event schedule on a fresh arena reproduces the
    precomputed high-water mark exactly (the plan IS an arena trace)."""
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    from repro.models import resnet as rn
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    bound = rbl.bind(prog, rimfs=rimfs.mount(image))
    plan = linker.plan_residency(bound)
    assert plan.high_water > 0
    # replay: identical walk, fresh arena -> identical peak
    replay = linker.plan_residency(bound)
    assert replay.high_water == plan.high_water
    assert replay.offsets == plan.offsets


def test_linked_pipeline_outputs_bit_identical(rng):
    K, n = 3, 8
    prog = rctc.compile_dma_pipeline(K, n)
    fs = rimfs.mount(rimfs.pack({"b": rng.randn(n, n)
                                 .astype(np.float32)}))
    ins = {f"in{i}": rng.randn(n, n).astype(np.float32) for i in range(K)}
    ex = Executor()
    o_i = ex.run_interpreted(rbl.bind(prog, rimfs=fs, inputs=dict(ins)))
    o_l = ex.run(rbl.bind(prog, rimfs=fs, inputs=dict(ins)))
    for k in o_i:
        np.testing.assert_array_equal(
            np.asarray(o_i[k]),
            np.asarray(jax.block_until_ready(o_l[k])))


# ---------------------------------------------------------------------------
# Property tests (hypothesis)
# ---------------------------------------------------------------------------

@given(st.lists(
    st.tuples(st.booleans(), st.integers(1, 4096)),
    min_size=1, max_size=60))
@settings(max_examples=60, deadline=None)
def test_property_arena_no_overlap_aligned(events):
    """Random alloc/free sequences: live ranges never overlap, every
    offset stays 128 B-aligned, and usage accounting balances."""
    a = DeviceArena(1 << 20, debug=True)   # debug: invariants per op
    live: list[int] = []
    expect_in_use = 0
    peak = 0
    for is_alloc, size in events:
        if is_alloc or not live:
            try:
                off = a.alloc(size)
            except ArenaError:
                continue
            assert off % 128 == 0
            live.append(off)
            expect_in_use += _aligned(size)
        else:
            off = live.pop(size % len(live))
            expect_in_use -= a._live[off]
            a.free(off)
        peak = max(peak, expect_in_use)
        # no two live ranges overlap (debug check() also asserts this)
        ranges = a.live_ranges()
        for (o1, s1), (o2, s2) in zip(ranges, ranges[1:]):
            assert o1 + s1 <= o2
    assert a.bytes_in_use == expect_in_use
    assert a.high_water == peak            # matches replayed peak

@given(st.integers(1, 6), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_property_plan_peak_matches_closed_form(stages, scale):
    """For the stage pipeline the precomputed high-water mark equals
    the closed-form steady-state residency: weight + dev + acc."""
    n = 8 * scale
    prog = rctc.compile_dma_pipeline(stages, n)
    rng = np.random.RandomState(0)
    fs = rimfs.mount(rimfs.pack({"b": rng.randn(n, n)
                                 .astype(np.float32)}))
    ins = {f"in{i}": rng.randn(n, n).astype(np.float32)
           for i in range(stages)}
    plan = linker.plan_residency(rbl.bind(prog, rimfs=fs, inputs=ins))
    # steady state: weight + one dev + one acc block, regardless of
    # stage count — dead stage-k ranges are donated to stage k+1
    assert plan.high_water == 3 * _aligned(n * n * 4)

"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. "eager"/"interpreted" is the
OS-mediated analogue (per-op decode + dispatch + host sync, like Vitis AI's
kernel-crossing path); "linked" is the compiled dispatch path (pre-resolved
thunks, core/linker.py); "fused" is the baremetal analogue (one XLA program
per RCB stream). The paper reports ratios, not absolutes (§5.1) — the
derived column carries the ratio each table is about.

Alongside the CSV, every row lands in ``BENCH_core.json``
(name -> {us_per_call, derived}) so the perf trajectory is machine-checkable
across PRs.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import gc
import json
import pickle
import re
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import opt, partition, rbl, rctc, rhal, rimfs
from repro.core.executor import Executor
from repro.core.rcb import Op, RCBProgram
from repro.core.rtpm import Platform
from repro.launch import compile_cache
from repro.models import resnet as rn

ROWS: list[str] = []
RESULTS: dict[str, dict] = {}
PREVIOUS: dict[str, dict] = {}        # prior BENCH_core.json (trend rows)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    RESULTS[name] = {"us_per_call": round(us_per_call, 2),
                     "derived": derived}
    print(row)


def _time(fn, n: int, warmup: int = 3) -> list:
    for _ in range(warmup):
        fn()
    xs = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        xs.append(time.perf_counter() - t0)
    return xs


def _time_steady(fn, n: int, warmup: int) -> list:
    """Steady-state latency samples (the table3 methodology fix).

    JIT warm-up iterations are run and DISCARDED before sampling starts
    (they previously leaked into the CV), and the GC is parked during the
    window so collection pauses don't masquerade as runtime variance.
    ``fn`` must synchronize per iteration (block_until_ready inside) so a
    sample is one real end-to-end latency, not an async enqueue.
    """
    for _ in range(warmup):
        fn()
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        xs = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            xs.append(time.perf_counter() - t0)
    finally:
        if was_enabled:
            gc.enable()
    return xs


def _cv(xs, trim: float = 0.05) -> float:
    """Trimmed CV%: drop the top/bottom ``trim`` fraction (host-contention
    outliers; the paper likewise discards warm-up/outlier iterations)."""
    xs = sorted(xs)
    k = max(1, int(len(xs) * trim))
    xs = xs[k:-k]
    return statistics.stdev(xs) / statistics.fmean(xs) * 100


# ---------------------------------------------------------------------------
# Table 1: per-transfer overhead vs block size (fixed total volume)
# ---------------------------------------------------------------------------

def table1_transfer_overhead(total_mb: float = 2.0) -> None:
    """Per-transfer overhead, per-op dispatch vs control-as-data chain.

    The same n-block transfer stream runs (a) op-at-a-time through the
    eager driver — each block pays the dispatch+sync fixed cost (the
    OS-mediated/ioctl analogue) — and (b) as ONE fused RCB chain — the
    control for all n transfers flattened into a single dispatch (the
    baremetal analogue). Paper Table 1: 7.0x/5.4x/3.0x/2.2x at
    1/4/16/32 KB, decaying as the fixed cost amortizes."""
    rng = np.random.RandomState(0)
    total = int(total_mb * (1 << 20))
    speedups = []
    for kb in (1, 4, 16, 32):
        block = kb << 10
        n = min(256, max(8, total // block))
        floats = block // 4
        prog = rctc.compile_passthrough((floats,))
        bound = rbl.bind(prog, inputs={})
        ex = Executor()
        xs = {f"in{i}": rng.randn(floats).astype(np.float32)
              for i in range(n)}

        def eager():
            for i in range(n):
                ex.run_interpreted(bound, inputs={"input": xs[f"in{i}"]})

        # control-as-data lets the runtime flatten the n-transfer stream
        # into ONE descriptor (paper §5.3: fusion/buffering/batching):
        stacked = np.stack([xs[f"in{i}"] for i in range(n)])
        sprog = rctc.compile_passthrough((n, floats))
        fused = ex.fuse(rbl.bind(sprog, inputs={}))

        def fused_stream():
            jax.block_until_ready(fused({"input": stacked}, {}))

        te = min(_time(eager, 5, warmup=1))
        tf_ = min(_time(fused_stream, 5, warmup=1))
        s = te / tf_
        speedups.append(s)
        emit(f"table1/block_{kb}kb", te / n * 1e6,
             f"speedup={s:.2f}x (eager us/transfer shown)")
    emit("table1/regime", 0.0,
         "small-block advantage "
         + ("CONFIRMED" if speedups[0] > speedups[-1] else "NOT-CONFIRMED")
         + f"; speedups={['%.2f' % s for s in speedups]}")


# ---------------------------------------------------------------------------
# Tables 4/5: matmul + passthrough kernel breakdowns
# ---------------------------------------------------------------------------

def table45_kernel_breakdowns(rng=None) -> None:
    rng = rng or np.random.RandomState(0)
    a = rng.randn(64, 64).astype(np.float32)
    b = rng.randn(64, 64).astype(np.float32)
    prog = rctc.compile_matmul(64, with_dma=True)
    fs = rimfs.mount(rimfs.pack({"b": b}))
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs, inputs={"a": a})

    # eager with per-op traces (paper: 1000 iterations)
    n = 300
    ex.op_traces.clear()
    for _ in range(n):
        ex.run(bound, trace_ops=True)
    by_op: dict = {}
    for t in ex.op_traces:
        by_op.setdefault(t.op, []).append(t.seconds)
    h2d_us = statistics.fmean(by_op[Op.DMA_H2D][n // 10:]) * 1e6
    d2h_us = statistics.fmean(by_op[Op.DMA_D2H][n // 10:]) * 1e6
    gemm_us = statistics.fmean(by_op[Op.GEMM][n // 10:]) * 1e6
    emit("table4/eager_input_transfer", h2d_us, "per-op DMA h2d")
    emit("table4/eager_output_transfer", d2h_us, "per-op DMA d2h")
    emit("table4/eager_kernel_exec", gemm_us, "per-op dispatch")

    bound2 = rbl.bind(prog, rimfs=fs)
    fused = ex.fuse(bound2)
    w = ex.weights_from(bound2)
    t_f = min(_time(lambda: jax.block_until_ready(fused({"a": a}, w)), 30))
    t_e = min(_time(lambda: ex.run_interpreted(bound), 30))
    # fused movement cost = (with-DMA fused) - (no-DMA fused): the compute
    # is identical, the difference is the streamed transfer cost
    prog0 = rctc.compile_matmul(64, with_dma=False)
    b0 = rbl.bind(prog0, rimfs=fs)
    f0 = ex.fuse(b0)
    t_f0 = min(_time(lambda: jax.block_until_ready(f0({"a": a}, w)), 30))
    move_e = h2d_us + d2h_us
    move_f = max((t_f - t_f0) * 1e6, 0.5)
    emit("table4/fused_total", t_f * 1e6,
         f"total_speedup={t_e/t_f:.2f}x; data_movement~"
         f"{move_e/move_f:.1f}x (paper: 3.3x movement, 1.0x kernel)")

    # passthrough: a 32-block transfer stream (pure data movement)
    n, floats = 32, 4096
    prog_p = rctc.compile_passthrough((floats,))
    bp = rbl.bind(prog_p, inputs={})
    xs = {f"in{i}": rng.randn(floats).astype(np.float32) for i in range(n)}

    def p_eager():
        for i in range(n):
            ex.run_interpreted(bp, inputs={"input": xs[f"in{i}"]})

    stacked = np.stack([xs[f"in{i}"] for i in range(n)])
    sp = rctc.compile_passthrough((n, floats))
    fp = ex.fuse(rbl.bind(sp, inputs={}))

    t_pe = min(_time(p_eager, 10))
    t_pf = min(_time(lambda: jax.block_until_ready(
        fp({"input": stacked}, {})), 10))
    emit("table5/passthrough_eager", t_pe / n * 1e6, "us per transfer")
    emit("table5/passthrough_fused", t_pf / n * 1e6,
         f"total_speedup={t_pe/t_pf:.2f}x (paper: 3.0x)")

    # pure data movement with REAL overlap numbers: the same n-block
    # transfer stream as explicit DMA ops, blocking per-op vs the
    # residency plan (batched prefetch prologue + drain epilogue)
    stream = rctc.compile_transfer_pipeline(n, floats)
    feeds = {f"in{i}": xs[f"in{i}"] for i in range(n)}
    bs_int = rbl.bind(stream, inputs=dict(feeds))
    bs_lnk = rbl.bind(stream, inputs=dict(feeds))

    def s_linked():
        jax.block_until_ready(list(ex.run(bs_lnk).values()))

    t_si = min(_time(lambda: ex.run_interpreted(bs_int), 10))
    t_sl = min(_time(s_linked, 10))
    plan = bs_lnk._linked.residency
    emit("table5/stream_perop_dma", t_si / n * 1e6,
         "us per transfer, blocking initiate+wait per block")
    emit("table5/stream_pipelined", t_sl / n * 1e6,
         f"speedup={t_si/t_sl:.2f}x vs per-op (paper: 3.0x); "
         f"moved={plan.bytes_moved}B overlapped={plan.bytes_overlapped}B "
         f"({plan.bytes_overlapped/plan.bytes_moved:.0%} split-phase)")


def table4_dma_pipeline(stages: int = 16, n: int = 64, iters: int = 25,
                        rng=None) -> None:
    """Data-movement overhead: blocking per-op DMA vs the residency plan.

    The same H2D->GEMM->D2H stage pipeline runs (a) interpreted — every
    transfer pays initiate+wait with a host sync (the seed's per-op DMA
    path) — and (b) linked — every H2D issues split-phase in the batched
    prefetch prologue and every D2H drains at the epilogue. Movement
    overhead per mode is isolated by subtracting the identical-compute
    no-DMA variant. Paper Table 4: 3-7x data-movement reduction."""
    rng = rng or np.random.RandomState(0)
    fs = rimfs.mount(rimfs.pack({"b": rng.randn(n, n).astype(np.float32)}))
    ins = {f"in{i}": rng.randn(n, n).astype(np.float32)
           for i in range(stages)}
    ex = Executor()

    def bind(with_dma):
        return rbl.bind(rctc.compile_dma_pipeline(stages, n,
                                                  with_dma=with_dma),
                        rimfs=fs, inputs=dict(ins))

    b_int, b_int0, b_lnk, b_lnk0 = (bind(True), bind(False),
                                    bind(True), bind(False))
    o_int = ex.run_interpreted(b_int)
    o_lnk = ex.run(b_lnk)
    identical = all(np.array_equal(np.asarray(o_int[k]),
                                   np.asarray(jax.block_until_ready(
                                       o_lnk[k]))) for k in o_int)

    def sync_run(b):
        jax.block_until_ready(list(ex.run(b).values()))

    t_i = min(_time(lambda: ex.run_interpreted(b_int), iters))
    t_i0 = min(_time(lambda: ex.run_interpreted(b_int0), iters))
    t_l = min(_time(lambda: sync_run(b_lnk), iters))
    t_l0 = min(_time(lambda: sync_run(b_lnk0), iters))
    move_i = max((t_i - t_i0) * 1e6, 0.5)
    move_l = max((t_l - t_l0) * 1e6, 0.5)
    plan = b_lnk._linked.residency
    emit("table4/movement_perop_dma", move_i,
         f"{stages}-stage pipeline, blocking initiate+wait per transfer")
    emit("table4/movement_pipelined", move_l,
         f"reduction={move_i/move_l:.1f}x vs per-op (target >= 3x, "
         f"paper: 3-7x); bit_identical={identical}")
    emit("table4/movement_overlap_bytes", 0.0,
         f"planned moved={plan.bytes_moved}B "
         f"overlapped={plan.bytes_overlapped}B "
         f"({plan.bytes_overlapped/plan.bytes_moved:.0%} split-phase); "
         f"arena_high_water={plan.high_water}B")


def residency_reuse_bench(rng=None) -> None:
    """Zero re-upload residency: repeated binds + engine constructions
    over one RIMFS image move bytes exactly once (driver DMA counters)."""
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=1)
    fs = rimfs.mount(image)
    driver = rhal.make_eager_driver()
    x = rng.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)

    t0 = time.perf_counter()
    b1 = rbl.bind(prog, rimfs=fs, driver=driver, inputs={"input": x})
    t_first = time.perf_counter() - t0
    first_bytes = driver.stats.get("dma_bytes", 0)
    t0 = time.perf_counter()
    for _ in range(10):
        rbl.bind(prog, rimfs=fs, driver=driver, inputs={"input": x})
        rbl.rebind(b1)
    t_re = (time.perf_counter() - t0) / 20
    re_bytes = driver.stats.get("dma_bytes", 0) - first_bytes
    ex = Executor(driver=driver)
    out = np.asarray(jax.block_until_ready(ex.run(b1)["output"]))
    assert out.shape[0] == 1
    emit("residency/first_bind", t_first * 1e6,
         f"uploads {first_bytes}B once (batched split-phase)")
    emit("residency/rebind", t_re * 1e6,
         f"re-uploaded_bytes={re_bytes} over 20 re-binds "
         f"(target: 0); speedup={t_first/max(t_re, 1e-9):.0f}x")


# ---------------------------------------------------------------------------
# Table 2: resource utilization + time-to-network-ready
# ---------------------------------------------------------------------------

def table2_resource_utilization(rng=None) -> None:
    # full-size ResNet-18 weights (the paper's 12.63 MB is the INT8 image;
    # fp32 folded is ~46 MB) so fixed overheads are in realistic proportion
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18", fromlist=["CONFIG"]).CONFIG
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=1)

    # image size: RIMFS vs OS-stack analogue (pickle of the weight dict)
    blob_os = pickle.dumps({k: np.asarray(v) for k, v in folded.items()})
    emit("table2/image_rimfs_bytes", 0.0, f"{len(image)}")
    emit("table2/image_pickle_bytes", 0.0,
         f"{len(blob_os)}; ratio={len(blob_os)/len(image):.2f}x")

    # runtime memory overhead: RIMFS index vs full deserialization copies
    fs = rimfs.mount(image)
    emit("table2/runtime_overhead_rimfs", 0.0,
         f"{fs.overhead_bytes()}B "
         f"({fs.overhead_bytes()/fs.total_bytes():.2%})")

    # time-to-service: zero-copy mount+bind vs deserialize+copy+stage.
    # (CRC verification is per-message on the wire in the paper; at mount
    # time it is on-demand, so the boot path stays O(header).)
    def aeg_boot():
        plat = Platform()
        plat.provision(image=image, program_bytes=prog.encode(),
                       verify=False)
        plat.bind()

    def os_boot():
        # OS-stack analogue: full deserialization + per-tensor copies +
        # device staging of every tensor
        w = pickle.loads(blob_os)
        w = {k: jnp.asarray(np.array(v, copy=True)) for k, v in w.items()}
        jax.block_until_ready(list(w.values()))

    t_aeg = min(_time(aeg_boot, 10))
    t_os = min(_time(os_boot, 10))
    emit("table2/time_to_service_aeg", t_aeg * 1e6, "")
    emit("table2/time_to_service_os", t_os * 1e6,
         f"ratio={t_os/t_aeg:.1f}x (paper: 350-745x vs Linux boot)")


# ---------------------------------------------------------------------------
# Table 3 / Fig 3: ResNet-18 inference latency, CV, per-device efficiency
# ---------------------------------------------------------------------------

def table3_resnet_inference(rng=None, iters: int = 200) -> None:
    """Latency + CV, steady-state methodology (the PR 2 fix).

    Both modes sample through ``_time_steady``: JIT warm-up iterations are
    discarded BEFORE sampling (previously they leaked into the fused CV —
    22.23%, *worse* than eager: a harness artifact), every iteration ends
    at ``block_until_ready``, the GC is parked, and the CV is trimmed. A
    noise-floor row (CV of a trivial pre-compiled dispatch under the same
    estimator) quantifies the host's irreducible scheduling jitter, so a
    fused CV at the floor reads as "the runtime adds no variance of its
    own" — the paper's determinism property, environment-bounded."""
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=1)
    fs = rimfs.mount(image)
    ex = Executor()
    x = rng.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)

    # noise floor: a trivial already-compiled dispatch, same estimator
    tiny = jax.jit(lambda v: v * 2.0)
    tx = jnp.ones((8, 8), jnp.float32)
    floor = _cv(_time_steady(
        lambda: jax.block_until_ready(tiny(tx)), iters, warmup=30))
    emit("table3/noise_floor", 0.0,
         f"cv={floor:.2f}% (host dispatch jitter under the same "
         f"estimator; CVs below are environment-bounded)")

    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    lat_e = _time_steady(lambda: ex.run_interpreted(bound), iters,
                         warmup=30)

    bound2 = rbl.bind(prog, rimfs=fs)
    fused = ex.fuse(bound2)
    w = ex.weights_from(bound2)
    lat_f = _time_steady(
        lambda: jax.block_until_ready(fused({"input": x}, w)), iters,
        warmup=30)

    cv_e, cv_f = _cv(lat_e), _cv(lat_f)
    mu_e, mu_f = statistics.fmean(lat_e), statistics.fmean(lat_f)
    emit("table3/eager_latency", mu_e * 1e6, f"cv={cv_e:.2f}%")
    emit("table3/fused_latency", mu_f * 1e6, f"cv={cv_f:.2f}%")
    prev = PREVIOUS.get("table3/fused_latency", {}).get("derived", "")
    m = re.search(r"cv=([\d.]+)%", prev)
    emit("table3/cv_trend", 0.0,
         f"fused_cv prev={m.group(1) + '%' if m else 'n/a'} "
         f"now={cv_f:.2f}% floor={floor:.2f}% (steady-state fix: "
         f"warmup discarded, per-iter sync, gc off, 5% trim)")
    # compute efficiency := throughput per device (1 device on this box)
    emit("table3/efficiency_ratio", 0.0,
         f"fused/eager={(1/mu_f)/(1/mu_e):.2f}x (paper: 9.2x per tile); "
         f"cv_ratio={cv_e/max(cv_f,1e-9):.1f}x (paper: 21x)")


# ---------------------------------------------------------------------------
# Batched compiled execution (bucketed batch-axis programs)
# ---------------------------------------------------------------------------

def batched_execution_bench(iters: int = 10, rng=None) -> None:
    """Throughput of ``Executor.run_batched`` per batch bucket vs the
    batch-1 serial linked loop, on the ResNet-18 program.

    Each bucket stages the fused program ONCE under ``jax.vmap`` (inputs
    mapped over a leading axis, weights broadcast; compile cache keyed
    (program CRC, bucket)); the gate is bit-identical per-lane outputs
    AND >= 3x request throughput at bucket 8 — the dispatch fixed cost
    is paid once per bucket instead of once per request."""
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    fs = rimfs.mount(image)
    driver = rhal.make_eager_driver()
    ex = Executor(driver=driver)
    # bind THROUGH the driver: weights pin device-side once (residency),
    # so neither path re-uploads per dispatch
    bound = rbl.bind(prog, rimfs=fs, driver=driver)
    chunks = 4                 # sustained: chunks-per-bucket measurement
    top = Executor.BATCH_BUCKETS[-1]
    xs = [{"input": rng.rand(1, cfg.image_size, cfg.image_size, 3)
           .astype(np.float32)} for _ in range(chunks * top)]
    refs = [np.asarray(jax.block_until_ready(
        ex.run(bound, inputs=x)["output"])) for x in xs]

    def serial_batch(k: int) -> None:
        # the per-request serving unit batching replaces: one linked
        # dispatch + host materialization of the reply tensors (serial
        # dispatches cannot overlap — each reply synchronizes)
        for x in xs[:k]:
            np.asarray(jax.block_until_ready(
                ex.run(bound, inputs=x)["output"]))

    serial_min = None
    for bucket in Executor.BATCH_BUCKETS:
        n = chunks * bucket
        batch = xs[:n]
        outs = ex.run_batched(bound, batch, max_bucket=bucket)   # warm
        assert ex.batch_stats["buckets"] == [bucket] * chunks
        identical = all(np.array_equal(np.asarray(o["output"]), refs[j])
                        for j, o in enumerate(outs))
        serial_batch(4)
        # serial and batched measured INTERLEAVED so container load
        # drift hits both sides; the paired ratio is the robust stat (a
        # tight same-call serial loop alone runs unrealistically hot)
        ratios, t1s, tbs = [], [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            serial_batch(4)
            t1 = (time.perf_counter() - t0) / 4
            t0 = time.perf_counter()
            ex.run_batched(bound, batch, max_bucket=bucket)
            tb = (time.perf_counter() - t0) / n
            ratios.append(t1 / tb)
            t1s.append(t1)
            tbs.append(tb)
        serial_min = min(t1s) if serial_min is None \
            else min(serial_min, min(t1s))
        per_req = min(tbs)
        emit(f"batched/bucket_{bucket}", per_req * 1e6,
             f"thpt={1 / per_req:.1f}req/s "
             f"vs_batch1_serial={statistics.median(ratios):.2f}x paired"
             + (" (target >= 3x)" if bucket == 8 else "")
             + f" [min-based {min(t1s) / per_req:.2f}x]; "
             f"sustained over {chunks} chunks; "
             f"bit_identical={identical}")
    emit("batched/batch1_serial", serial_min * 1e6,
         "the linked batch-1 dispatch+materialize unit the bucket rows "
         "amortize (measured interleaved with the batched runs)")
    # pad-to-bucket path: 6 requests ride one 8-bucket (2 padded lanes)
    outs = ex.run_batched(bound, xs[:6])
    identical = all(np.array_equal(np.asarray(o["output"]), refs[j])
                    for j, o in enumerate(outs))
    emit("batched/pad_n6", 0.0,
         f"buckets={ex.batch_stats['buckets']} "
         f"padded={ex.batch_stats['padded']} (slice-back); "
         f"bit_identical={identical}")


# ---------------------------------------------------------------------------
# Partitioned multi-tile scaling (paper Fig 3: tile-array deployment)
# ---------------------------------------------------------------------------

def partition_scaling_bench(rng=None, iters: int = 10,
                            stream_samples: int = 48) -> None:
    """Multi-tile scaling, both deployment shapes: the **latency-mode**
    rows (one sample through all stages back-to-back — per-stage
    occupancy shows exactly why adding groups LOSES throughput: every
    group idles while the others run) and the **stream** rows
    (``execute_stream`` software-pipelines a batch of inputs so the
    array stays full; gate: steady-state throughput >= 1.0x the
    single-device linked loop at depth >= 4).

    On this box every tile group is modeled on the same host device, so
    the latency rows also account the cost side of the paper's
    multi-tile story: cut-edge count, inter-tile movement bytes per
    execution (per directed edge), and per-group arena high-water, with
    bit-identical outputs as the gate."""
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    fs = rimfs.mount(image)
    x = rng.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)
    ex = Executor()

    bound_l = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    t_single = min(_time(lambda: jax.block_until_ready(
        ex.run(bound_l)["output"]), iters))
    ref = np.asarray(jax.block_until_ready(ex.run(bound_l)["output"]))
    emit("partition/single_linked", t_single * 1e6,
         f"throughput={1/t_single:.1f}/s (the 1-device baseline)")

    def occupancy_str(busy_by_gid: dict, wall: float,
                      label: str = "occ") -> str:
        occ = [busy_by_gid.get(g, 0.0) / wall
               for g in sorted(busy_by_gid)]
        out = (f"{label}=[" + ",".join(f"{o:.0%}" for o in occ) + "]")
        if label == "occ":
            out += f" bubble={max(0.0, 1.0 - sum(occ)):.0%}"
        return out

    for n_groups in (1, 2, 4, 8):
        mesh = rhal.TileMesh(n_groups)
        bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
        out = ex.run_partitioned(bound, rimfs=fs, mesh=mesh)   # warm/link
        identical = np.array_equal(
            ref, np.asarray(jax.block_until_ready(out["output"])))
        before = mesh.moved_bytes()
        ex.run_partitioned(bound, rimfs=fs, mesh=mesh)
        per_exec = mesh.moved_bytes() - before
        t_p = min(_time(lambda: jax.block_until_ready(
            ex.run_partitioned(bound, rimfs=fs, mesh=mesh)["output"]),
            iters))
        part = bound._partitions[mesh.n_groups]
        # per-stage occupancy of one timed execution: in latency mode a
        # group is busy only while ITS stage runs, so the occupancy sum
        # falls as 1/groups — the bubble the stream rows close
        stage_times: list = []
        t0 = time.perf_counter()
        jax.block_until_ready(partition.execute(
            part, mesh, rimfs=fs, stage_times=stage_times)["output"])
        wall = time.perf_counter() - t0
        busy: dict = {}
        for gid, sec in stage_times:
            busy[gid] = busy.get(gid, 0.0) + sec
        per_edge = sorted(
            (f"{s}->{d}:{st['bytes'] // st['transfers']}B"
             for (s, d), st in mesh.edge_stats.items()), )
        plans = [t.residency(mesh.group(t.gid).driver)
                 for t in part.tiles]
        high = max((p.high_water for p in plans if p is not None),
                   default=0)
        thpt = 1 / t_p
        emit(f"partition/groups_{n_groups}", t_p * 1e6,
             f"thpt={thpt:.1f}/s per_tile={thpt / n_groups:.1f}/s "
             f"vs_single={thpt * t_single:.2f}x (latency mode); "
             f"{occupancy_str(busy, wall)}; "
             f"cut_edges={len(part.edges)} moved_per_exec={per_exec}B "
             f"[{','.join(per_edge) or 'none'}]; "
             f"max_group_high_water={high}B; bit_identical={identical}")

    # ------------------------- streaming pipeline fill (execute_stream)
    M = stream_samples
    xs = [rng.rand(1, cfg.image_size, cfg.image_size, 3)
          .astype(np.float32) for _ in range(M)]
    refs = [np.asarray(jax.block_until_ready(
        ex.run(bound_l, inputs={"input": xi})["output"])) for xi in xs]
    depth = 4
    for n_groups in (2, 4):
        mesh = rhal.TileMesh(n_groups)
        bound = rbl.bind(prog, rimfs=fs)
        part = partition.partition(bound, n_groups)
        outs = [np.asarray(jax.block_until_ready(o["output"]))
                for o in partition.execute_stream(
                    part, mesh, ({"input": xi} for xi in xs),
                    rimfs=fs, depth=depth)]
        identical = all(np.array_equal(a, b) for a, b in zip(outs, refs))
        stats: dict = {}

        def run_stream():
            for o in partition.execute_stream(
                    part, mesh, ({"input": xi} for xi in xs),
                    rimfs=fs, depth=depth, stats=stats):
                np.asarray(jax.block_until_ready(o["output"]))

        # single-linked re-measured INTERLEAVED with the stream runs so
        # container load drift hits both sides of the gate ratio (same
        # pairing the batched rows use)
        run_stream()                                   # warm
        ratios, t1s, tss = [], [], []
        for _ in range(max(4, iters)):
            t0 = time.perf_counter()
            for xi in xs[:4]:
                np.asarray(jax.block_until_ready(
                    ex.run(bound_l, inputs={"input": xi})["output"]))
            t1s.append((time.perf_counter() - t0) / 4)
            t0 = time.perf_counter()
            run_stream()
            tss.append((time.perf_counter() - t0) / M)
            ratios.append(t1s[-1] / tss[-1])
        t_s = min(tss)
        emit(f"partition/stream_groups_{n_groups}", t_s * 1e6,
             f"thpt={1 / t_s:.1f}/s "
             f"vs_single={statistics.median(ratios):.2f}x paired "
             f"[min-based {min(t1s) / t_s:.2f}x] "
             f"(steady-state target >= 1.0x at depth {depth}"
             + (", GATE" if n_groups == 2 else "") + "); "
             # fused stages dispatch asynchronously, so per-group busy
             # time measures HOST dispatch share, not device utilization
             # (the latency-mode rows' occ/bubble column is the
             # utilization view — their linked stages sync per stage)
             f"{occupancy_str(stats['busy'], tss[-1] * M, 'host_disp')}; "
             f"samples={M} fused_stages={stats['fused']}; "
             f"bit_identical={identical}")


# ---------------------------------------------------------------------------
# Serving concurrency (protocol v2 pipelining + single-dispatcher batching)
# ---------------------------------------------------------------------------

def serving_concurrency_bench(per_client: int = 6, pipeline: int = 3) -> None:
    """Aggregate serving throughput at 1/4/8 concurrent pipelined
    connections against ONE dispatcher-owned device, with a bit-identical
    gate: every concurrent response must equal the serial reference for
    the same input. The dispatcher now coalesces backlogged same-program
    requests into batched dispatches, so aggregate throughput is expected
    to RISE with fan-in instead of flattening; the ``serving_batched``
    row pins the 8-client number against the PR 4 (per-request dispatch)
    baseline. Every row also reports p50/p99 per-request latency —
    batching is not allowed to buy throughput with unobserved tails."""
    import threading

    from repro.serving.server import Client, InferenceServer

    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    server = InferenceServer(max_queue=512)
    addr = server.start()
    try:
        c0 = Client(addr)
        c0.provision(image, prog.encode())
        rng = np.random.RandomState(0)
        max_clients = 8
        xs = {(c, i): rng.rand(1, cfg.image_size, cfg.image_size, 3)
              .astype(np.float32)
              for c in range(max_clients) for i in range(per_client)}
        refs = {k: c0.infer(input=v)["output"] for k, v in xs.items()}

        def pct(lat: list, q: float) -> float:
            lat = sorted(lat)
            return lat[min(len(lat) - 1, int(q * len(lat)))]

        t_base = None
        thpt_8 = lat_8 = None
        for n_clients in (1, 4, 8):
            results: dict = {}
            latencies: list = []
            lat_lock = threading.Lock()

            def run_client(cid: int) -> None:
                cl = Client(addr)
                lats = []
                try:
                    for base in range(0, per_client, pipeline):
                        sent = {}
                        rids = []
                        for i in range(base, min(base + pipeline,
                                                 per_client)):
                            rid = cl.infer_async(input=xs[(cid, i)])
                            sent[rid] = time.perf_counter()
                            rids.append((i, rid))
                        for i, rid in rids:
                            results[(cid, i)] = cl.result(rid)["output"]
                            lats.append(time.perf_counter() - sent[rid])
                finally:
                    cl.close()
                with lat_lock:
                    latencies.extend(lats)

            threads = [threading.Thread(target=run_client, args=(c,))
                       for c in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            n = n_clients * per_client
            identical = len(results) == n and all(
                np.array_equal(results[k], refs[k]) for k in results)
            thpt = n / dt
            if t_base is None:
                t_base = thpt
            lat_str = (f"p50={pct(latencies, 0.5)*1e3:.1f}ms "
                       f"p99={pct(latencies, 0.99)*1e3:.1f}ms")
            if n_clients == 8:
                thpt_8, lat_8 = thpt, lat_str
            emit(f"serving_concurrency/clients_{n_clients}", dt / n * 1e6,
                 f"agg_thpt={thpt:.1f}req/s vs_1client={thpt/t_base:.2f}x "
                 f"(pipeline depth {pipeline}); {lat_str}; "
                 f"bit_identical={identical}")
        tel = c0.telemetry()["serving"]
        bt = tel.get("batched", {})
        emit("serving_concurrency/dispatcher", 0.0,
             f"processed={tel['processed']} rejected={tel['rejected']} "
             f"shed={tel['shed']} "
             f"batched_dispatches={bt.get('dispatches', 0)} "
             f"batched_requests={bt.get('requests', 0)} "
             f"max_batch={bt.get('max_batch', 0)} "
             f"queue_wait_p95={tel['queue_wait'].get('p95', 0)*1e3:.2f}ms")
        # aggregate 8-client throughput vs the committed PR 4 baseline
        # (per-request dispatch): the coalescing win, trend-tracked
        prev = PREVIOUS.get("serving_concurrency/clients_8",
                            {}).get("derived", "")
        m = re.search(r"agg_thpt=([\d.]+)req/s", prev)
        base = float(m.group(1)) if m else None
        emit("serving_batched/clients_8", 0.0,
             f"agg_thpt={thpt_8:.1f}req/s vs_pr4_baseline="
             + (f"{thpt_8 / base:.2f}x (prev {base:.1f}req/s)" if base
                else "n/a (no prior row)")
             + f"; {lat_8}; coalesced={bt.get('requests', 0)} reqs in "
             f"{bt.get('dispatches', 0)} batched dispatches")
        c0.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Pallas kernels: linked opcode vs GRAPH_EXEC artifact, per family (ISSUE 9)
# ---------------------------------------------------------------------------

def kernel_microbench(rng=None, iters: int = 10) -> None:
    """kernels/* rows: each Pallas kernel dispatched as its linked RCB
    opcode (Op.ATTENTION/MATMUL_INT8/SSM_SCAN/WKV6 through the RHAL
    ``link_compute`` registry handler) vs the SAME registry math wrapped
    as one monolithic GRAPH_EXEC artifact — the pre-registry lowering.
    Both run through ``Executor.run`` on an identically shaped one-op
    program, so the delta is pure dispatch-path cost; the derived column
    carries the ratio plus a match gate (``compare.py
    check_kernel_gates``, warn-only). The interpret-mode wrapper rows
    stay as raw-latency trend lines."""
    rng = rng or np.random.RandomState(0)
    from repro.core.rcb import RCB, RCBOp, TensorDesc
    from repro.kernels import registry as kreg
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.int8_matmul.ops import int8_matmul
    q = jnp.asarray(rng.randn(1, 128, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
    t = min(_time(lambda: flash_attention(q, k, v).block_until_ready(), 5))
    emit("kernels/flash_attention_interpret", t * 1e6, "vs ref in tests")
    xi = jnp.asarray(rng.randint(-127, 128, (128, 128)), jnp.int8)
    wi = jnp.asarray(rng.randint(-127, 128, (128, 128)), jnp.int8)
    s = jnp.asarray(rng.rand(128).astype(np.float32))
    t = min(_time(lambda: int8_matmul(xi, wi, s).block_until_ready(), 5))
    emit("kernels/int8_matmul_interpret", t * 1e6, "vs ref in tests")

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    lw = -jnp.abs(arr(1, 32, 2, 16)).clip(0.05, 3.0)
    cases = {
        "attention": (Op.ATTENTION, (arr(1, 64, 4, 32), arr(1, 64, 2, 32),
                                     arr(1, 64, 2, 32)), {"causal": True}),
        "matmul_int8": (Op.MATMUL_INT8, (xi, wi, s),
                        {"out_dtype": "float32"}),
        "ssm_scan": (Op.SSM_SCAN, (-jnp.abs(arr(1, 32, 8, 4)),
                                   arr(1, 32, 8, 4), arr(1, 32, 4)), {}),
        "wkv6": (Op.WKV6, (arr(1, 32, 2, 16), arr(1, 32, 2, 16),
                           arr(1, 32, 2, 16), lw, arr(2, 16)), {}),
    }
    ex = Executor()
    for name, (opcode, args, attrs) in cases.items():
        ref = jax.block_until_ready(kreg.call_op(name, args, attrs))
        tensors = {f"in{i}": TensorDesc(f"in{i}", tuple(a.shape),
                                        str(a.dtype), "input")
                   for i, a in enumerate(args)}
        tensors["out"] = TensorDesc("out", tuple(ref.shape),
                                    str(ref.dtype), "output")
        srcs = tuple(f"in{i}" for i in range(len(args)))
        ins = {f"in{i}": np.asarray(a) for i, a in enumerate(args)}
        prog_k = RCBProgram(f"bench_k_{name}", dict(tensors), [RCB(
            0, "layer", (), (RCBOp(opcode, ("out",), srcs, attrs),
                             RCBOp(Op.FENCE)))])
        prog_g = RCBProgram(f"bench_g_{name}", dict(tensors), [RCB(
            0, "layer", (), (RCBOp(Op.GRAPH_EXEC, ("out",), srcs,
                                   {"artifact": name}),
                             RCBOp(Op.FENCE)))],
            artifacts={name: jax.jit(
                lambda *xs, _n=name, _a=attrs: kreg.call_op(_n, xs, _a))})
        b_k = rbl.bind(prog_k, inputs=dict(ins))
        b_g = rbl.bind(prog_g, inputs=dict(ins))
        o_k = np.asarray(jax.block_until_ready(ex.run(b_k)["out"]))
        o_g = np.asarray(jax.block_until_ready(ex.run(b_g)["out"]))
        match = np.allclose(o_k, o_g, rtol=0, atol=1e-6)
        t_g = min(_time(lambda: jax.block_until_ready(
            ex.run(b_g)["out"]), iters))
        t_k = min(_time(lambda: jax.block_until_ready(
            ex.run(b_k)["out"]), iters))
        emit(f"kernels/{name}_graph_exec", t_g * 1e6,
             "monolithic artifact dispatch (pre-registry lowering)")
        emit(f"kernels/{name}_linked", t_k * 1e6,
             f"vs_graph_exec={t_g / t_k:.2f}x; match={match}; "
             f"bit_identical={np.array_equal(o_k, o_g)}; "
             f"params={kreg.params_for(name, args)}")


# ---------------------------------------------------------------------------
# Core dispatch spine: linked vs interpreted, v1 vs v2 wire, peephole pass
# ---------------------------------------------------------------------------

def core_dispatch_bench(rng=None, iters: int = 30) -> None:
    """The two hottest runtime fixed costs, before/after this PR's compiled
    path: per-op dispatch (interpreted decode loop vs linked thunks) and
    program load (JSON-v1 vs packed-v2 decode)."""
    rng = rng or np.random.RandomState(0)
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    folded = rn.fold_bn(params)
    raw, image = rctc.compile_resnet18(cfg, folded, batch=1,
                                       optimize=False)
    optd, _ = rctc.compile_resnet18(cfg, folded, batch=1, optimize=True)
    fs = rimfs.mount(image)
    x = rng.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)
    ex = Executor()
    n_ops = opt.op_count(raw)

    # --- dispatch: interpreted baseline vs linked thunk loop (same program)
    bound_i = rbl.bind(raw, rimfs=fs, inputs={"input": x})
    bound_l = rbl.bind(raw, rimfs=fs, inputs={"input": x})
    t_int = min(_time(lambda: ex.run_interpreted(bound_i), iters))
    t_lnk = min(_time(lambda: jax.block_until_ready(
        ex.run(bound_l)["output"]), iters))
    ops_int, ops_lnk = n_ops / t_int, n_ops / t_lnk
    emit("core/dispatch_interpreted_resnet18", t_int * 1e6,
         f"ops_per_sec={ops_int:.0f}")
    emit("core/dispatch_linked_resnet18", t_lnk * 1e6,
         f"ops_per_sec={ops_lnk:.0f}; speedup={ops_lnk/ops_int:.2f}x "
         f"vs interpreted (target >= 2x)")

    # --- bit-identical equivalence across all three modes + peephole
    o_int = np.asarray(ex.run_interpreted(bound_i)["output"])
    o_lnk = np.asarray(jax.block_until_ready(ex.run(bound_l)["output"]))
    bound_o = rbl.bind(optd, rimfs=fs, inputs={"input": x})
    o_opt = np.asarray(jax.block_until_ready(ex.run(bound_o)["output"]))
    bound_f = rbl.bind(optd, rimfs=fs)
    fused = ex.fuse(bound_f)
    o_fus = np.asarray(jax.block_until_ready(
        fused({"input": x}, ex.weights_from(bound_f))["output"]))
    identical = (np.array_equal(o_int, o_lnk)
                 and np.array_equal(o_lnk, o_opt)
                 and np.array_equal(o_lnk, o_fus))
    n_opt = opt.op_count(optd)
    emit("core/peephole_resnet18_opcount", 0.0,
         f"raw={n_ops} optimized={n_opt} "
         f"reduction={(n_ops - n_opt) / n_ops:.1%} (target >= 15%); "
         f"bit_identical={identical} (interpreted/linked/fused)")

    # --- wire format: v1 (per-op JSON) vs v2 (interned symtab + packed)
    cfg_f = __import__("repro.configs.resnet18", fromlist=["CONFIG"]).CONFIG
    params_f = rn.init_resnet(jax.random.PRNGKey(0), cfg_f)
    prog_f, _ = rctc.compile_resnet18(cfg_f, rn.fold_bn(params_f), batch=1,
                                      optimize=False)
    b1 = prog_f.encode(version=1)
    b2 = prog_f.encode(version=2)
    assert RCBProgram.decode(b1).blocks == RCBProgram.decode(b2).blocks
    reps = 200
    te1 = min(_time(lambda: prog_f.encode(version=1), reps))
    te2 = min(_time(lambda: prog_f.encode(version=2), reps))
    td1 = min(_time(lambda: RCBProgram.decode(b1), reps))
    td2 = min(_time(lambda: RCBProgram.decode(b2), reps))
    emit("core/encode_v1", te1 * 1e6,
         f"{len(b1)/te1/1e6:.1f}MB/s size={len(b1)}B")
    emit("core/encode_v2", te2 * 1e6,
         f"{len(b2)/te2/1e6:.1f}MB/s size={len(b2)}B; "
         f"speedup={te1/te2:.2f}x vs v1")
    emit("core/decode_v1", td1 * 1e6, f"{len(b1)/td1/1e6:.1f}MB/s")
    emit("core/decode_v2", td2 * 1e6,
         f"{len(b2)/td2/1e6:.1f}MB/s; speedup={td1/td2:.2f}x vs v1 "
         f"(target >= 3x)")


# ---------------------------------------------------------------------------
# Integrity plane: CRC-verify overhead + corruption-recovery cost
# ---------------------------------------------------------------------------

def integrity_bench(iters: int = 200, rng=None) -> None:
    """Cost of the end-to-end DMA integrity plane (ISSUE 7).

    ``crc_verify_overhead``: the same h2d issue+wait loop with endpoint
    CRC verification on vs off — the steady-state tax every checked
    transfer pays (one host-side crc32 at issue + one at redeem).
    ``corrupt_retry_recovery``: one corrupted delivery detected at
    redeem and healed by the bounded in-place re-issue, with the
    bit-identical gate — the price of a caught fault, not of the
    common path."""
    rng = rng or np.random.RandomState(0)
    x = rng.randn(256, 256).astype(np.float32)      # 256 KB payload

    def roundtrip(drv):
        t = drv.dma_async(x, "h2d")
        jax.block_until_ready(drv.dma_wait(t))

    d_on = rhal.make_eager_driver()
    d_off = rhal.make_eager_driver()
    d_off.integrity.enabled = False
    t_on = min(_time(lambda: roundtrip(d_on), iters, warmup=10))
    t_off = min(_time(lambda: roundtrip(d_off), iters, warmup=10))
    assert d_on.stats.get("dma_crc_checked", 0) > 0
    assert d_off.stats.get("dma_crc_checked", 0) == 0
    emit("integrity/crc_verify_overhead", (t_on - t_off) * 1e6,
         f"checked={t_on*1e6:.2f}us unchecked={t_off*1e6:.2f}us "
         f"overhead={(t_on/t_off - 1)*100:.1f}% per 256KB h2d "
         f"(issue-time stamp + redeem-time verify; dominated by the "
         f"verify readback a real DMA engine computes inline)")

    # one-shot corruption: flip a delivered bit after issue, measure the
    # detect + re-issue + re-verify path at redeem
    drv = rhal.make_eager_driver()
    recs = []
    for _ in range(max(5, iters // 20)):
        t = drv.dma_async(x, "h2d")
        bad = np.array(x, copy=True)
        bad.reshape(-1).view(np.uint8)[0] ^= 0x01
        t.buf = jax.device_put(jnp.asarray(bad))
        t0 = time.perf_counter()
        out = jax.block_until_ready(drv.dma_wait(t))
        recs.append(time.perf_counter() - t0)
        assert np.array_equal(np.asarray(out), x)   # bit-identical heal
    emit("integrity/corrupt_retry_recovery", min(recs) * 1e6,
         f"detect+reissue+verify per caught fault; "
         f"recovered={drv.stats.get('dma_retry_recovered', 0)} "
         f"mismatches={drv.stats.get('dma_crc_mismatch', 0)}; "
         f"bit_identical=True")


# ---------------------------------------------------------------------------
# Fleet operations: scale cycle + hot swap + kill/heal under live traffic
# ---------------------------------------------------------------------------

def _load_chaos():
    """Load tests/chaos.py as a module (it lives outside the package)."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parent.parent / "tests" / \
        "chaos.py"
    spec = importlib.util.spec_from_file_location("chaos_bench", path)
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    return chaos


def fleet_operations_bench(quick: bool = False) -> None:
    """One seeded chaos scenario (tests/chaos.py): a 2 -> peak -> 2 scale
    cycle, a hot weight swap, a forced bad swap and a tile-group kill all
    land mid-traffic; the rows carry the robustness gate — zero failed
    requests, bit-identical responses, bounded p99."""
    chaos = _load_chaos()
    p99_bound_s = 30.0
    if quick:
        rep = chaos.run_chaos(groups=2, seed=7, requests=30, clients=2,
                              scale_peak=4, pace_s=0.01, dma_delay_s=0.1,
                              p99_bound_s=p99_bound_s)
    else:
        rep = chaos.run_chaos(groups=2, seed=7, requests=90, clients=3,
                              scale_peak=8, p99_bound_s=p99_bound_s)
    violations = chaos.check_report(rep)
    bit_identical = rep["mismatches"] == 0 and rep["ok"] == rep["sent"]
    tm = rep["timings"]
    emit("fleet/scale_cycle", rep["p50_s"] * 1e6,
         f"failed_requests={rep['failed']} "
         f"p99={rep['p99_s'] * 1e3:.1f}ms "
         f"p99_bound={rep['p99_bound_s'] * 1e3:.0f}ms "
         f"bit_identical={bit_identical} "
         f"up={tm['scale_up'] * 1e3:.1f}ms "
         f"down={tm['scale_down'] * 1e3:.1f}ms "
         f"violations={len(violations)}")
    emit("fleet/weight_swap", tm["swap_good"] * 1e6,
         f"result={rep['good_swap']} (probe + atomic flip, "
         f"zero dropped requests)")
    emit("fleet/chaos_kill", tm["kill_to_heal"] * 1e6,
         f"kill->heal_complete under traffic; "
         f"final_groups={rep['n_groups_final']}")
    emit("fleet/bad_swap_rollback", tm["swap_bad"] * 1e6,
         f"result={rep['bad_swap']} (conformance probe caught the "
         f"wrong weights; old binding kept serving)")


def rollout_control_bench(quick: bool = False) -> None:
    """Safe-rollout & overload control plane rows (ISSUE 10).

    ``fleet/canary_overhead``: per-request cost of a fully-sampled
    canary — fraction=1.0 + sample=1.0 means EVERY request dual-runs
    primary + shadow and bit-compares, the worst-case tax; production
    fractions pay it on the routed slice only. ``fleet/partial_reshape_
    ms``: kill -> splice latency of replacing ONE tile group in place,
    gated on zero survivor DMA bytes. ``overload/recovery_time``: the
    rollout chaos scenario's burst -> ladder -> rung-0 walk-back, with
    the scenario's full invariant checklist folded into the derived
    column (compare.py check_rollout_gates, warn-only)."""
    from repro.core.fleet import FleetController
    from repro.serving.server import Client, InferenceServer

    depth, n = (4, 16) if quick else (8, 24)
    prog = rctc.compile_gemm_chain(depth, n)
    files = rctc.gemm_chain_weights(depth, n)
    image = rimfs.pack(files)
    server = InferenceServer(mesh=rhal.TileMesh(4))
    addr = server.start()
    client = Client(addr)
    try:
        client.provision(image, prog.encode())
        fleet = FleetController(server)
        x = np.random.RandomState(0).randn(n, n).astype(np.float32)
        ref = client.infer(input=x)
        iters = 8 if quick else 16
        t_plain = min(_time(lambda: client.infer(input=x), iters,
                            warmup=2))
        assert fleet.canary(image, fraction=1.0,
                            label="bench") == "started"
        t_can = min(_time(lambda: client.infer(input=x), iters,
                          warmup=2))
        fleet.abort_canary(reason="bench")
        out = client.infer(input=x)
        identical = all(np.array_equal(ref[k], out[k]) for k in ref)
        emit("fleet/canary_overhead", (t_can - t_plain) * 1e6,
             f"dual_run={t_can / t_plain:.2f}x vs primary-only per "
             f"request (fraction=1.0, sample=1.0: every request "
             f"bit-compared); bit_identical={identical}")

        mesh = server.mesh
        times = []
        zero_bytes = True
        for i in range(3 if quick else 5):
            gid = 1 + (i % (mesh.n_groups - 1))
            survivors = {g: mesh.group(g).driver.stats.get("dma_bytes", 0)
                         for g in mesh.gids if g != gid}
            mesh.kill(gid)
            t0 = time.perf_counter()
            fleet.replace_group(gid, reason="bench")
            times.append(time.perf_counter() - t0)
            zero_bytes &= all(
                mesh.group(g).driver.stats.get("dma_bytes", 0) == b
                for g, b in survivors.items())
        out = client.infer(input=x)
        identical = all(np.array_equal(ref[k], out[k]) for k in ref)
        emit("fleet/partial_reshape_ms", min(times) * 1e6,
             f"{min(times) * 1e3:.1f}ms kill->splice (fsck + spawn + "
             f"one-group prewarm + CRC revalidate + install_group); "
             f"survivors_zero_bytes={zero_bytes}; "
             f"bit_identical={identical}")
    finally:
        client.close()
        server.stop()

    chaos = _load_chaos()
    rep = chaos.run_rollout_chaos(groups=2, seed=7,
                                  requests=60 if quick else 90,
                                  clients=3)
    violations = chaos.check_rollout_report(rep)
    ov = rep["overload"]
    rec = rep["timings"].get("overload_recovery", 0.0)
    emit("overload/recovery_time", rec * 1e6,
         f"burst->rung{ov['max_rung']}->rung{ov['final_rung']} "
         f"recovered={ov['recovered']} breaker={ov['breaker']['state']} "
         f"canary_good={rep.get('canary_good')} "
         f"canary_bad={rep.get('canary_bad')} "
         f"reshape={rep.get('reshape', {}).get('happened')} "
         f"violations={len(violations)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke profile: minimal iteration counts")
    ap.add_argument("--json", default="BENCH_core.json",
                    help="machine-readable results path")
    ap.add_argument("--baseline", default="BENCH_core.json",
                    help="prior results the trend rows compare against "
                         "(kept separate from --json so CI can write "
                         "fresh results without losing the committed "
                         "baseline)")
    args = ap.parse_args()
    compile_cache.enable()
    quick = args.quick or args.smoke
    try:
        with open(args.baseline) as f:
            PREVIOUS.update(json.load(f))          # trend rows
    except (OSError, ValueError):
        pass
    print("name,us_per_call,derived")
    core_dispatch_bench(iters=10 if quick else 30)
    batched_execution_bench(iters=5 if quick else 10)
    table1_transfer_overhead(total_mb=1.0 if quick else 4.0)
    table45_kernel_breakdowns()
    table4_dma_pipeline(iters=10 if quick else 25)
    partition_scaling_bench(iters=5 if quick else 10,
                            stream_samples=24 if quick else 48)
    residency_reuse_bench()
    table2_resource_utilization()
    table3_resnet_inference(iters=50 if quick else 200)
    serving_concurrency_bench(per_client=3 if quick else 6)
    from benchmarks.decode_bench import run_sweep as lm_decode_sweep
    lm_decode_sweep(emit, quick=quick)
    integrity_bench(iters=50 if quick else 200)
    fleet_operations_bench(quick=quick)
    rollout_control_bench(quick=quick)
    kernel_microbench()
    with open(args.json, "w") as f:
        json.dump(RESULTS, f, indent=2, sort_keys=True)
    print(f"# {len(ROWS)} rows -> {args.json}")


if __name__ == "__main__":
    main()

"""The generic RCB executor — cyclic Fetch-Decode-Dispatch.

The executor knows nothing about models: it walks the linear op stream and
invokes RHAL vtable slots. Three modes reproduce the paper's central
comparison on TPU terms:

  * ``interpreted`` — every op is re-decoded through the opcode switch and
    dispatched as its own device computation with a host synchronization
    after it (per-op fixed cost: the OS-mediated / Vitis-AI analogue).
    Per-op wall times are recordable, so this is also the measurement mode.
  * ``linked``  — the default ``run`` path. The program is linked ONCE
    (core/linker.py) into pre-resolved thunks over a dense slot array; the
    dispatch loop is ``for thunk in thunks: thunk(slots, rimfs)`` with
    per-site jitted handlers dispatching asynchronously, syncing only at
    FENCE ops and program exit.
  * ``fused``  — the *same* linked thunks run once under ``jax.jit`` via
    the trace driver, collapsing the whole RCB stream into one XLA
    executable (the baremetal analogue: one dispatch per step, zero host
    round-trips inside).
  * ``partitioned`` — the program is cut into per-tile-group stages
    (core/partition.py) and pipelined over a ``TileMesh`` of independent
    drivers, cut-edge activations streaming split-phase between groups
    (the paper's multi-tile AIE-array deployment shape).

Equivalence of the modes over the whole op vocabulary is enforced by
tests/test_executor.py, tests/test_linker.py and the differential
conformance matrix in tests/test_conformance.py — the paper's "same RCBs
drive different execution environments" portability property.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import linker as linker_mod
from repro.core import rhal as rhal_mod
from repro.core import tracing
from repro.core.rbl import BoundProgram
from repro.core.rbl import explicitly_freed as rbl_explicitly_freed
from repro.core.rcb import Op, RCBProgram
from repro.core.rhal import DmaTicket


@dataclasses.dataclass
class OpTrace:
    block_id: int
    op: Op
    seconds: float


def _probe_update(probe_dev: dict, sym: str, buf) -> None:
    """Device-side abs-max accumulation: no host round-trip per op (the
    old path forced ``np.asarray`` — a full host sync per dispatch)."""
    m = jnp.max(jnp.abs(buf))
    prev = probe_dev.get(sym)
    probe_dev[sym] = m if prev is None else jnp.maximum(prev, m)


def _probe_flush(probe: dict, probe_dev: dict) -> None:
    """Convert accumulated device scalars to host floats ONCE at exit."""
    for sym, m in probe_dev.items():
        probe[sym] = max(probe.get(sym, 0.0), float(m))


class Executor:
    def __init__(self, driver: Optional[rhal_mod.HalDriver] = None,
                 rtpm=None):
        self.driver = driver or rhal_mod.make_eager_driver()
        self.rtpm = rtpm
        self.op_traces: list[OpTrace] = []
        self.batch_stats: dict = {}      # last run_batched outcome report

    # ------------------------------------------------------------- linking
    def link(self, bound: BoundProgram) -> linker_mod.LinkedProgram:
        """Link (and cache on the BoundProgram) against this driver."""
        linked = getattr(bound, "_linked", None)
        if linked is None or linked.driver is not self.driver \
                or linked.program is not bound.program:
            linked = linker_mod.link(bound, self.driver)
            bound._linked = linked
        return linked

    # ------------------------------------------------------------ dispatch
    def _dispatch(self, driver, op, buffers, free_after: Optional[dict],
                  idx: int, rimfs):
        """Decode + dispatch one RCBOp through the vtable (interpreted)."""
        if op.op == Op.NOP or op.op == Op.HALT:
            return
        if op.op == Op.ALLOC:
            buffers[op.dsts[0]] = driver.alloc(tuple(op.attrs["shape"]),
                                               op.attrs["dtype"])
        elif op.op == Op.FREE:
            driver.free(buffers.pop(op.dsts[0], None))
        elif op.op == Op.BIND_CONST:
            buffers[op.dsts[0]] = driver.bind_const(op.attrs["value"])
        elif op.op == Op.DMA_H2D:
            src = op.srcs[0]
            host = buffers.get(src)
            if host is None and rimfs is not None:
                host = rimfs.read(src)
            buffers[op.dsts[0]] = driver.wait_dma(
                driver.initiate_dma(host, "h2d"))
        elif op.op == Op.DMA_D2H:
            buffers[op.dsts[0]] = driver.wait_dma(
                driver.initiate_dma(buffers[op.srcs[0]], "d2h"))
        elif op.op == Op.DMA_D2D:
            buffers[op.dsts[0]] = driver.wait_dma(
                driver.initiate_dma(buffers[op.srcs[0]], "d2d"))
        elif op.op == Op.GRAPH_EXEC:
            fn = self._artifact(op.attrs["artifact"])
            outs = fn(*[buffers[s] for s in op.srcs])
            if len(op.dsts) == 1:
                buffers[op.dsts[0]] = outs
            else:
                for d, o in zip(op.dsts, outs):
                    buffers[d] = o
        elif op.op == Op.COLLECTIVE:
            buffers[op.dsts[0]] = driver.collective(
                op.attrs.get("kind", "all_reduce"), buffers[op.srcs[0]],
                op.attrs)
        elif op.op == Op.FENCE:
            driver.fence(list(buffers.values()))
        elif op.op == Op.POLL:
            driver.poll(buffers.get(op.srcs[0]) if op.srcs else None)
        else:                                    # compute dispatch
            srcs = [buffers[s] for s in op.srcs]
            buffers[op.dsts[0]] = driver.dispatch_compute(op.op, srcs,
                                                          op.attrs)
        # Buffer lifetime management (RBL liveness plan). Scratch is
        # released by reference-drop, not driver.free: eager identity ops
        # (PASSTHROUGH, single-device COLLECTIVE) alias their source, so an
        # eager delete would tear buffers still reachable under another
        # symbol. Symbols with an explicit FREE op are exempt — FREE must
        # see the real buffer to return its arena range. The linked path
        # applies the same policy via its precomputed free-lists.
        if free_after is not None:
            for s in op.srcs:
                if free_after.get(s) == idx and s not in self._explicit_free:
                    t = self._prog.tensors.get(s)
                    if t is not None and t.kind == "scratch":
                        buffers.pop(s, None)

    def _artifact(self, name: str) -> Callable:
        fn = self._prog.artifacts.get(name)
        if fn is None:
            raise KeyError(f"GRAPH_EXEC artifact {name!r} not attached")
        return fn

    # --------------------------------------------------------------- eager
    def run(self, bound: BoundProgram, inputs: Optional[dict] = None,
            rimfs=None, trace_ops: bool = False,
            probe: Optional[dict] = None) -> dict:
        """Execute the program through the linked (compiled-dispatch) path.

        ``probe``: optional dict filled with per-symbol abs-max of every
        produced buffer — used by INT8 calibration (core/quant.py). The
        abs-max accumulates on device; host conversion happens once.

        ``trace_ops=True`` falls back to the interpreted path: per-op wall
        timing needs the per-op host sync that defines that mode.

        The thunks are issued with no host sync of their own (the
        ``aeg.issue`` span, ``core/tracing.py``); the program's FENCE ops
        and the caller's readback wait for the device. The span's
        ``weight_h2d_bytes`` are the bound weights held in host memory,
        which the handlers copy to the device on every execution: 0 for a
        bind against a driver (``rbl.bind(driver=...)``).
        """
        if trace_ops:
            return self.run_interpreted(bound, inputs=inputs, rimfs=rimfs,
                                        trace_ops=True, probe=probe)
        linked = self.link(bound)
        istats0 = None
        if self.rtpm is not None:
            istats0 = {k: self.driver.stats.get(k, 0)
                       for k in ("dma_retry", "dma_crc_mismatch")}
        slots = linked.fresh_slots(bound.buffers, inputs)
        for sym, i in linked.missing_inputs:
            if slots[i] is None:
                raise ValueError(f"missing input {sym!r}")
        probe_dev: Optional[dict] = None
        if probe is not None:
            probe_dev = {}
            for i, buf in enumerate(slots):
                if buf is not None:
                    _probe_update(probe_dev, linked.names[i], buf)
        with tracing.span("aeg.issue", thunks=len(linked.thunks),
                          weight_h2d_bytes=linked.weight_h2d_bytes):
            for pre in linked.prologue:            # prefetch issue phase
                pre(slots, rimfs)
            if probe_dev is None:
                for thunk in linked.thunks:        # THE hot loop
                    thunk(slots, rimfs)
            else:
                for thunk, meta in zip(linked.thunks, linked.metas):
                    thunk(slots, rimfs)
                    for d in meta.dst_slots:
                        buf = slots[d]
                        if buf is not None and type(buf) is not DmaTicket:
                            _probe_update(probe_dev, linked.names[d], buf)
            for epi in linked.epilogue:            # drain redeem phase
                epi(slots, rimfs)
        self.driver._count("dispatch", linked.n_compute)
        plan = linked.residency
        if self.rtpm is not None and plan is not None and plan.bytes_moved:
            self.rtpm.post("dma_complete",
                           {"bytes_moved": plan.bytes_moved,
                            "bytes_overlapped": plan.bytes_overlapped})
        if istats0 is not None:
            # surface integrity-plane activity (corruptions caught and
            # retried in the driver) as telemetry counter deltas
            for key, kind in (("dma_retry", "dma_retry"),
                              ("dma_crc_mismatch", "integrity_error")):
                delta = self.driver.stats.get(key, 0) - istats0[key]
                if delta:
                    self.rtpm.post(kind, {"n": delta, "source": "executor"})
        if probe_dev is not None:
            _probe_flush(probe, probe_dev)
        out = {}
        for name, i in linked.output_slots:
            if slots[i] is not None:
                out[name] = slots[i]
        return out

    # --------------------------------------------------- interpreted baseline
    def run_interpreted(self, bound: BoundProgram,
                        inputs: Optional[dict] = None, rimfs=None,
                        trace_ops: bool = False,
                        probe: Optional[dict] = None) -> dict:
        """Interpret the program op-by-op (eager / OS-mediated analogue).

        Kept as the baseline the benchmarks compare the linked path
        against, and as the per-op measurement mode (``trace_ops``).
        """
        self._prog = bound.program
        self._explicit_free = rbl_explicitly_freed(bound.program)
        buffers = dict(bound.buffers)
        if inputs:
            buffers.update(inputs)
        for sym in bound.missing_inputs:
            if sym not in buffers:
                raise ValueError(f"missing input {sym!r}")
        probe_dev: Optional[dict] = None
        if probe is not None:
            probe_dev = {}
            for sym, buf in buffers.items():
                _probe_update(probe_dev, sym, buf)
        idx = 0
        for block in bound.program.blocks:
            for op in block.ops:
                t0 = time.perf_counter()
                self._dispatch(self.driver, op, buffers, bound.last_use,
                               idx, rimfs)
                if trace_ops:
                    self.op_traces.append(
                        OpTrace(block.block_id, op.op,
                                time.perf_counter() - t0))
                if probe_dev is not None:
                    for dd in op.dsts:
                        if dd in buffers:
                            _probe_update(probe_dev, dd, buffers[dd])
                idx += 1
        if probe_dev is not None:
            _probe_flush(probe, probe_dev)
        return {name: buffers[name]
                for name, t in bound.program.tensors.items()
                if t.kind == "output" and name in buffers}

    # --------------------------------------------------------------- fused
    def fuse(self, bound: BoundProgram, donate_weights: bool = False):
        """Stage the whole program into one jitted callable.

        Returns ``fn(inputs: dict, weights: dict) -> outputs: dict`` — a
        single XLA program per RCB stream (the baremetal analogue). The
        staged function traces the SAME linked thunk form ``run`` executes,
        just through the trace driver.

        The jitted callable is cached on the BoundProgram (keyed by
        ``donate_weights``): re-linking and re-tracing on every call
        silently dominated any serving loop that reached for ``fuse`` —
        repeated calls now return the SAME callable, so XLA's trace cache
        actually gets hit. The cache is invalidated if the bound's
        program object is swapped out from under it.
        """
        self._prog = bound.program
        cache = getattr(bound, "_fused", None)
        if cache is None or cache[0] is not bound.program:
            cache = bound._fused = (bound.program, {})
        fn = cache[1].get(donate_weights)
        if fn is None:
            linked = linker_mod.link(bound, rhal_mod.make_trace_driver())
            staged = linker_mod.stage_callable(linked)
            donate = (1,) if donate_weights else ()
            fn = jax.jit(staged, donate_argnums=donate)
            cache[1][donate_weights] = fn
        return fn

    # -------------------------------------------------------------- batched
    #: Batch-bucket ladder: every batched dispatch stages at one of these
    #: leading-axis sizes, so the number of distinct XLA executables per
    #: program is bounded (len(buckets)), not O(#distinct request counts).
    BATCH_BUCKETS: tuple = (1, 2, 4, 8, 16)

    # (program CRC, bucket) -> AOT-compiled vmapped staged callable.
    # Module-wide on purpose: re-binds, fresh BoundPrograms and every
    # Executor instance of the same program share ONE executable per
    # bucket (the bucket fixes every input aval, so ahead-of-time
    # lower+compile replaces jit's per-call cache probe with a direct
    # executable invocation — MicroTVM-AoT-style, no tracing at dispatch).
    _batch_cache: dict = {}
    _BATCH_CACHE_CAP = 64

    @classmethod
    def aot_cache_get(cls, key):
        """Look up an AOT-compiled executable in the module-wide CRC-keyed
        cache. Keys are (program CRC, shape-descriptor tuple) — the paged
        LM engine keys its prefill/decode executables here so every engine
        over the same service program shares one executable per shape,
        under the same capacity bound as the batched-dispatch entries."""
        return cls._batch_cache.get(key)

    @classmethod
    def aot_cache_put(cls, key, fn) -> None:
        while len(cls._batch_cache) >= cls._BATCH_CACHE_CAP:
            cls._batch_cache.pop(next(iter(cls._batch_cache)))
        cls._batch_cache[key] = fn

    def _batched_callable(self, bound: BoundProgram, bucket: int):
        key = (bound.program.crc(), bucket)
        fn = Executor._batch_cache.get(key)
        if fn is None:
            while len(Executor._batch_cache) >= Executor._BATCH_CACHE_CAP:
                Executor._batch_cache.pop(
                    next(iter(Executor._batch_cache)))
            linked = linker_mod.link(bound, rhal_mod.make_trace_driver())
            staged = linker_mod.stage_callable(linked)
            # inputs map over the leading batch axis, weights broadcast;
            # avals come from the program's tensor descs (inputs) and the
            # bind's resolved buffers (weights) — same-CRC programs have
            # identical descs, so the compiled form is shareable
            in_avals = {
                n: jax.ShapeDtypeStruct((bucket,) + tuple(t.shape),
                                        np.dtype(t.dtype))
                for n, t in bound.program.tensors.items()
                if t.kind == "input"}
            w_avals = {
                n: jax.ShapeDtypeStruct(np.shape(b),
                                        np.asarray(b).dtype if
                                        not hasattr(b, "dtype") else
                                        b.dtype)
                for n, b in self.weights_from(bound).items()}
            fn = jax.jit(jax.vmap(staged, in_axes=(0, None))).lower(
                in_avals, w_avals).compile()
            Executor._batch_cache[key] = fn
        return fn

    def _bucket_for(self, n: int) -> int:
        """Smallest ladder bucket >= n (pad-to-bucket), or the largest
        bucket when n exceeds the ladder (the caller chunks)."""
        for b in self.BATCH_BUCKETS:
            if b >= n:
                return b
        return self.BATCH_BUCKETS[-1]

    def _chunks(self, n: int, max_bucket: Optional[int]) -> list:
        """``(take, bucket)`` per chunk for ``n`` requests: full
        largest-bucket chunks first, then the remainder padded up to the
        smallest covering bucket. A non-ladder ``max_bucket`` stages its
        own chunk size rather than padding past the caller's clamp."""
        top = self.BATCH_BUCKETS[-1] if max_bucket is None \
            else max(1, min(max_bucket, self.BATCH_BUCKETS[-1]))
        out = []
        while n > 0:
            take = min(top, n)
            out.append((take, min(self._bucket_for(take), top)))
            n -= take
        return out

    def prepare_batched(self, bound: BoundProgram, n: int) -> list:
        """Compile every bucket ``run_batched`` would stage for ``n``
        requests, without running anything. A caller that times the
        dispatch (the server's watchdog) calls this first, so a cold
        bucket's compile never counts as execution. Returns the buckets,
        none for a program that is not batchable."""
        if not linker_mod.batch_analysis(bound).batchable:
            return []
        buckets = [bucket for _, bucket in self._chunks(n, None)]
        for bucket in buckets:
            self._batched_callable(bound, bucket)
        return buckets

    def run_batched(self, bound: BoundProgram, inputs_list,
                    rimfs=None, max_bucket: Optional[int] = None) -> list:
        """Execute one program over a batch of independent requests.

        The program is staged ONCE per batch bucket (sizes 1/2/4/8/16,
        via ``jax.vmap`` over a leading axis on the input slots with
        weights broadcast, AOT-compiled) and the request list is chunked
        greedily onto the ladder: full largest-bucket chunks first, then
        the remainder pads up to the smallest covering bucket — padded
        lanes replicate the chunk's last request and are sliced away from
        the results (pad-to-bucket + slice-back). ``max_bucket`` clamps
        the ladder top (e.g. to a serving batch window).

        Execution is two-phase: every chunk is DISPATCHED first (the
        compiled calls are asynchronous), then results materialize in
        request order — so chunk *k*'s host-side stacking and slice-back
        overlap chunk *k−1*'s device compute, and a multi-chunk batch
        runs at sustained pipeline throughput rather than
        dispatch-sync-dispatch. Returns one output dict per request in
        request order, outputs materialized on host (each output tensor
        crosses d2h ONCE per chunk; per-request entries are zero-copy
        views of the batched buffer); per-lane outputs are bit-identical
        to serial ``run`` (tests/test_conformance.py).

        Programs the batch analysis rejects (split-phase DMA, collectives,
        GRAPH_EXEC — see ``linker.batch_analysis``) fall back to serial
        linked execution, same results, no batch amortization.
        ``self.batch_stats`` reports what happened either way.
        """
        reqs = list(inputs_list)
        verdict = linker_mod.batch_analysis(bound)
        self.batch_stats = {"batchable": verdict.batchable,
                            "reason": verdict.reason,
                            "requests": len(reqs), "buckets": [],
                            "padded": 0}
        if not reqs:
            return []
        if not verdict.batchable:
            return [self.run(bound, inputs=req, rimfs=rimfs)
                    for req in reqs]
        prep = getattr(bound, "_batch_prep", None)
        if prep is None or prep[0] is not bound.program:
            weights = self.weights_from(bound)
            prep = bound._batch_prep = (
                bound.program,
                tuple(n for n, t in bound.program.tensors.items()
                      if t.kind == "input"),
                weights, linker_mod.host_bytes(weights.values()))
        _, input_syms, weights, weight_h2d = prep
        # phase 1: stack + dispatch every chunk (no sync anywhere)
        pending: list = []                 # (pos, take, {sym: device out})
        pos = 0
        with tracing.span("aeg.issue") as issue:
            for take, bucket in self._chunks(len(reqs), max_bucket):
                chunk = reqs[pos:pos + take]
                stacked = {}
                for sym in input_syms:
                    vals = []
                    for req in chunk:
                        v = req.get(sym) if req else None
                        if v is None:
                            v = bound.buffers.get(sym)
                        if v is None:
                            raise ValueError(f"missing input {sym!r} in "
                                             f"batched request {pos}")
                        vals.append(np.asarray(v))
                    vals.extend([vals[-1]] * (bucket - take))   # pad lanes
                    stacked[sym] = np.stack(vals)      # host-side: one memcpy
                fn = self._batched_callable(bound, bucket)
                pending.append((pos, take, fn(stacked, weights)))
                self.batch_stats["buckets"].append(bucket)
                self.batch_stats["padded"] += bucket - take
                pos += take
            issue.stats["thunks"] = len(pending)
            # each chunk's call copies the host-resident weights over
            issue.stats["weight_h2d_bytes"] = weight_h2d * len(pending)
        # phase 2: materialize in order — ONE d2h per output tensor per
        # chunk, zero-copy per-lane views (per-lane device slicing would
        # dispatch a device op per request, the exact fixed cost this
        # path amortizes); blocking on chunk k overlaps chunk k+1's
        # in-flight compute
        results: list = [None] * len(reqs)
        with tracing.span("aeg.readback"):
            for cpos, take, outs in pending:
                hosts = {k: np.asarray(v) for k, v in outs.items()}
                for j in range(take):
                    results[cpos + j] = {k: h[j] for k, h in hosts.items()}
        return results

    # --------------------------------------------------------- partitioned
    def run_partitioned(self, bound: BoundProgram,
                        inputs: Optional[dict] = None, rimfs=None,
                        mesh=None, n_groups: int = 2,
                        platform=None) -> dict:
        """Execute over a tile mesh: the program is cut into per-group
        stages (core/partition.py), each stage runs linked on its own
        group's driver, and cut-edge tensors stream split-phase between
        groups — stage *k*'s activations move while stage *k+1* sets up.

        ``mesh`` defaults to a fresh ``TileMesh(n_groups)``; a
        ``platform`` (rtpm.Platform) adds heartbeat-monitored workers and
        stage re-queue on tile failure. The partition is cached on the
        BoundProgram per group count, so repeated executions re-cut
        nothing.
        """
        from repro.core import partition as partition_mod
        if mesh is None:
            mesh = rhal_mod.TileMesh(n_groups)
        part = partition_mod.ensure_partition(bound, mesh.n_groups)
        return partition_mod.execute(part, mesh, inputs=inputs,
                                     rimfs=rimfs, platform=platform)

    # ------------------------------------------------------------- helpers
    def weights_from(self, bound: BoundProgram) -> dict:
        return {n: b for n, b in bound.buffers.items()
                if bound.program.tensors[n].kind == "weight"}

"""The request-stage recorder (core/tracing.py): the spans a served
request leaves, the ring's bounds, and the tracing that went with it —
no per-block host syncs in ``Executor.run``, linked handlers named after
their opcode, ``queue_wait`` timing requests rather than kicks, and the
TELEMETRY reply's ``stages``."""
import socket
import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro.core import rbl, rctc, rhal, rimfs, tracing
from repro.core.executor import Executor
from repro.core.rcb import Op
from repro.core.rtpm import Platform
from repro.serving import protocol as proto
from repro.serving.server import Client, InferenceServer

DEPTH, N = 4, 16
STAGES = ("aeg.recv", "aeg.unpack", "aeg.wait", "aeg.dispatch",
          "aeg.reply")


@pytest.fixture(scope="module")
def chain():
    prog = rctc.compile_gemm_chain(DEPTH, N)
    files = rctc.gemm_chain_weights(DEPTH, N)
    return prog, files


def _start(chain, **kw):
    prog, files = chain
    server = InferenceServer(**kw)
    client = Client(server.start())
    client.provision(rimfs.pack(files), prog.encode())
    return server, client


def _x(seed=0):
    return np.random.RandomState(seed).randn(N, N).astype(np.float32)


def _by_req(since: int) -> dict:
    out: dict = {}
    for s in tracing.spans(since):
        if s.req:
            out.setdefault(s.req, []).append(s)
    return out


def _children(parent: int, since: int) -> dict:
    return {s.name: s for s in tracing.spans(since) if s.parent == parent}


def _gate_dispatcher(server):
    """Hold the dispatcher at its next item, and its idle hook with it."""
    gate, started = threading.Event(), threading.Event()
    inner, idle = server._loop.handler, server._loop.on_idle

    def gated(item):
        started.set()
        gate.wait(30)
        inner(item)

    server._loop.handler = gated
    server._loop.on_idle = lambda: idle() if gate.is_set() else False
    return gate, started


def test_solo_request_leaves_every_stage(chain):
    server, client = _start(chain, batch_window=1)
    try:
        since = time.perf_counter_ns()
        client.infer(input=_x())
        reqs = _by_req(since)
        assert len(reqs) == 1
        (req, spans), = reqs.items()
        names = [s.name for s in sorted(spans, key=lambda s: s.start_ns)]
        assert names == list(STAGES)
        by = {s.name: s for s in spans}
        for a, b in zip(STAGES, STAGES[1:]):
            assert by[a].start_ns <= by[b].start_ns
        assert by["aeg.recv"].end_ns <= by["aeg.unpack"].start_ns
        assert by["aeg.wait"].end_ns <= by["aeg.dispatch"].start_ns
        assert by["aeg.dispatch"].end_ns <= by["aeg.reply"].start_ns
        assert by["aeg.recv"].stats["bytes"] > N * N * 4
        d = by["aeg.dispatch"]
        assert d.stats == {"mode": "solo", "n": 1, "reqs": (req,)}
        kids = _children(d.id, since)
        assert set(kids) == {"aeg.issue", "aeg.readback"}
        issue, back = kids["aeg.issue"], kids["aeg.readback"]
        assert issue.stats["thunks"] > 0
        assert d.start_ns <= issue.start_ns <= issue.end_ns \
            <= back.start_ns <= back.end_ns <= d.end_ns
    finally:
        client.close()
        server.stop()


def test_coalesced_run_lists_every_member(chain):
    server, client = _start(chain, batch_window=8)
    try:
        gate, started = _gate_dispatcher(server)
        since = time.perf_counter_ns()
        rids = [client.infer_async(input=_x(i)) for i in range(3)]
        assert started.wait(10)
        deadline = time.monotonic() + 10
        while server.scheduler.pending() < 3 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        for rid in rids:
            client.result(rid, timeout=30)
        reqs = _by_req(since)
        assert len(reqs) == 3
        batched = [s for s in tracing.spans(since)
                   if s.name == "aeg.dispatch"
                   and s.stats["mode"] == "batched"]
        assert len(batched) == 1
        d = batched[0]
        assert d.stats["n"] == 3 and sorted(d.stats["reqs"]) == sorted(reqs)
        assert set(_children(d.id, since)) == {"aeg.issue", "aeg.readback"}
        for spans in reqs.values():      # every member waited and replied
            assert {s.name for s in spans} == \
                {"aeg.recv", "aeg.unpack", "aeg.wait", "aeg.reply"}
    finally:
        client.close()
        server.stop()


def test_no_span_starts_before_the_header_arrives(chain):
    """The handler's wait for a header, and the dispatcher's idle poll,
    lie in no span: ``aeg.recv`` starts once the header is in and runs
    until the body is."""
    server, client = _start(chain, batch_window=1)
    raw = socket.create_connection(server.address)
    try:
        frame = proto.encode_frame(proto.Msg.INFER_REQUEST,
                                   proto.pack_tensors({"input": _x()}),
                                   request_id=5)
        cut = proto.HEADER.size + proto.EXT.size
        since = time.perf_counter_ns()
        time.sleep(0.2)                  # connected, nothing sent
        t_head = time.perf_counter_ns()
        raw.sendall(frame[:cut])
        time.sleep(0.2)                  # header in, body held back
        raw.sendall(frame[cut:])
        reply = proto.recv_frame_ex(raw)
        assert reply.kind == proto.Msg.INFER_RESPONSE
        (req, spans), = _by_req(since).items()
        assert min(s.start_ns for s in spans) >= t_head
        recv = next(s for s in spans if s.name == "aeg.recv")
        assert recv.stats["rid"] == 5
        assert recv.end_ns - recv.start_ns >= 0.15e9
        assert all(s.start_ns >= t_head for s in tracing.spans(since))
    finally:
        raw.close()
        client.close()
        server.stop()


def test_ring_is_bounded_and_wraps():
    rec = tracing.Recorder(capacity=8)
    ids = [rec.record("s", i, i + 1, req=i, k=i) for i in range(20)]
    got = rec.spans()
    assert [s.id for s in got] == ids[-8:]
    assert [s.start_ns for s in rec.spans(15, 18)] == [15, 16, 17]
    summary = rec.stage_summary()["s"]
    assert summary["n"] == 8 and summary["mean"] == pytest.approx(1e-9)
    with rec.span("outer") as outer:
        with rec.span("inner", req=3) as inner:
            inner.stats["n"] = 1
    spans = {s.name: s for s in rec.spans()}
    assert spans["inner"].parent == outer.id and spans["outer"].parent == 0
    assert spans["inner"].stats == {"n": 1} and spans["outer"].stats is None


def test_a_full_ring_stays_in_its_memory():
    """A ring filled with spans shaped as the server's takes the bytes the
    module states for ``CAPACITY`` spans, and no more once it wraps."""
    rec = tracing.Recorder()
    t = time.perf_counter_ns()
    tracemalloc.start()
    try:
        base = tracemalloc.take_snapshot()
        for i in range(tracing.CAPACITY + 1000):
            rec.record("aeg.recv", t + i, t + i + 5000, req=1 << 20,
                       rid=1 << 20, bytes=2408770)
        used = sum(st.size_diff for st in
                   tracemalloc.take_snapshot().compare_to(base, "filename"))
    finally:
        tracemalloc.stop()
    assert len(rec.spans()) == tracing.CAPACITY
    assert used < 64 << 20


def test_executor_run_makes_no_per_block_host_sync(chain, monkeypatch):
    """With an RTPM attached, ``Executor.run`` syncs no more than without
    one (only the program's own FENCE), and serves the same bits as the
    interpreted reference."""
    from jax._src.array import ArrayImpl
    prog, files = chain
    assert len(prog.blocks) > 2
    fs = rimfs.mount(rimfs.pack(files))
    plat = Platform()
    plat.provision(image=rimfs.pack(files), program=prog)
    x = _x(3)
    syncs = []
    real = ArrayImpl.block_until_ready
    monkeypatch.setattr(ArrayImpl, "block_until_ready",
                        lambda self: syncs.append(1) or real(self))
    outs, counts = [], []
    for ex in (Executor(), Executor(rtpm=plat)):
        bound = plat.bind(inputs={"input": x})
        syncs.clear()
        out = ex.run(bound, rimfs=fs)
        counts.append(len(syncs))
        outs.append({k: np.asarray(v) for k, v in out.items()})
    assert counts[0] == counts[1]
    ref = Executor().run_interpreted(plat.bind(inputs={"input": x}),
                                     rimfs=fs)
    for out in outs:
        for k, v in ref.items():
            assert np.array_equal(out[k], np.asarray(v))


def test_linked_handlers_are_named_after_their_opcode():
    driver = rhal.make_eager_driver()
    x = jax.numpy.ones((4, 4), jax.numpy.float32)
    for op, args in ((Op.ADD, (x, x)), (Op.RELU, (x,)), (Op.GEMM, (x, x))):
        handler = driver.link_compute(op, {})
        text = handler.lower(*args).as_text()
        assert f"@jit_rcb_{op.name.lower()} " in text


def test_queue_wait_times_requests_not_kicks(chain):
    server, client = _start(chain, batch_window=1)
    try:
        qw = server._loop.queue_wait
        before = qw.count()
        since = time.perf_counter_ns()
        for i in range(5):
            client.infer(input=_x(i))
        assert qw.count() - before == 5
        waits = sorted(s.end_ns - s.start_ns for s in tracing.spans(since)
                       if s.name == "aeg.wait")
        assert len(waits) == 5
        window = qw.summary(warmup=before)
        assert window["n"] == 5
        assert window["p50"] == pytest.approx(waits[2] / 1e9)
    finally:
        client.close()
        server.stop()


def test_telemetry_reports_stages(chain):
    server, client = _start(chain, batch_window=1)
    try:
        since = time.perf_counter_ns()
        client.infer(input=_x())
        stages = client.telemetry(since_ns=since)["stages"]
        assert set(STAGES) <= set(stages)
        for name in STAGES:
            st = stages[name]
            assert st["n"] == 1
            assert 0 <= st["p50"] <= st["p95"] <= st["p99"]
        assert "aeg.recv" in client.telemetry()["stages"]
        later = time.perf_counter_ns() + 10 ** 12
        assert client.telemetry(since_ns=later)["stages"] == {}
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("pinned", [False, True], ids=["host", "pinned"])
@pytest.mark.parametrize("path", ["run", "run_batched"])
def test_issue_span_counts_host_weight_bytes(chain, path, pinned):
    """``aeg.issue`` carries the weight bytes the handlers copy to the
    device: all of them, once per batched chunk, for a host-view bind;
    none for a bind against the executor's driver."""
    prog, files = chain
    fs = rimfs.mount(rimfs.pack(files))
    ex = Executor()
    bound = rbl.bind(prog, rimfs=fs,
                     driver=ex.driver if pinned else None)
    xs = [{"input": _x(i)} for i in range(3)]
    since = time.perf_counter_ns()
    if path == "run":
        ex.run(bound, inputs=xs[0], rimfs=fs)
        calls = 1
    else:
        ex.run_batched(bound, xs, rimfs=fs, max_bucket=2)
        calls = 2                        # chunks of 2 and 1 requests
    issue, = [s for s in tracing.spans(since) if s.name == "aeg.issue"]
    want = 0 if pinned else calls * sum(f.nbytes for f in files.values())
    assert issue.stats["weight_h2d_bytes"] == want

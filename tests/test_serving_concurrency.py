"""Concurrent network serving: protocol v2 pipelining, single-dispatcher
ownership, backpressure/shed replies, malformed-frame handling, graceful
drain — the many-clients scenario class.

Determinism contract (ISSUE 4): N client threads x M pipelined requests
against one server produce bit-identical outputs to the same requests run
serially, with zero dropped or garbled frames.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs.resnet18 import CONFIG as RESNET
from repro.core import rctc
from repro.models import resnet as rn
from repro.serving import protocol as proto
from repro.serving.scheduler import DeadlineScheduler
from repro.serving.server import (Client, InferenceServer, RequestShed,
                                  ServerBusy)

from test_conformance import assert_batched_close


@pytest.fixture(scope="module")
def resnet_setup():
    cfg = RESNET.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    prog, image = rctc.compile_resnet18(cfg, rn.fold_bn(params), batch=1)
    return cfg, prog, image


def _input(cfg, seed: int) -> np.ndarray:
    r = np.random.RandomState(seed)
    return r.rand(1, cfg.image_size, cfg.image_size, 3).astype(np.float32)


def _start(prog, image, **kw):
    server = InferenceServer(**kw)
    addr = server.start()
    client = Client(addr)
    client.provision(image, prog.encode())
    return server, addr, client


# ---------------------------------------------------------------- pipelining
def test_pipelined_multiclient_bit_identical(resnet_setup):
    """4 concurrent connections x 3 pipelined requests each == the same 12
    requests run serially, bit for bit. ``batch_window=1`` serves every
    request solo: this tests framing and routing, not coalescing."""
    cfg, prog, image = resnet_setup
    n_clients, per_client = 4, 3
    inputs = {(c, i): _input(cfg, 100 * c + i)
              for c in range(n_clients) for i in range(per_client)}
    server, addr, client = _start(prog, image, batch_window=1)
    try:
        serial = {k: client.infer(input=v)["output"]
                  for k, v in sorted(inputs.items())}

        results: dict = {}
        errors: list = []

        def worker(c: int) -> None:
            cl = Client(addr)
            try:
                rids = [(i, cl.infer_async(input=inputs[(c, i)]))
                        for i in range(per_client)]
                for i, rid in reversed(rids):       # out-of-order collection
                    results[(c, i)] = cl.result(rid)["output"]
            except Exception as e:                  # pragma: no cover
                errors.append(e)
            finally:
                cl.close()

        threads = [threading.Thread(target=worker, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert set(results) == set(inputs)          # zero dropped frames
        for k in inputs:
            np.testing.assert_array_equal(results[k], serial[k])
    finally:
        client.close()
        server.stop()


def test_interleaved_request_ids_one_connection(resnet_setup):
    """One connection pipelines 6 requests and collects the responses in a
    scrambled order — request ids route every response to its waiter
    (``batch_window=1``: served solo, so bit for bit)."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, batch_window=1)
    try:
        xs = [_input(cfg, 50 + i) for i in range(6)]
        refs = [client.infer(input=x)["output"] for x in xs]
        rids = [client.infer_async(input=x) for x in xs]
        order = [3, 0, 5, 1, 4, 2]
        got: dict = {}
        for j in order:
            got[j] = client.result(rids[j])["output"]
        for j in range(6):
            np.testing.assert_array_equal(got[j], refs[j])
    finally:
        client.close()
        server.stop()


def test_midstream_provision_does_not_corrupt_inflight(resnet_setup):
    """A PROVISION racing pipelined INFERs serializes behind the
    dispatcher: in-flight inferences stay bit-identical (``batch_window=1``
    serves them solo) and requests after the re-provision still serve."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, batch_window=1)
    other = Client(addr)
    try:
        xs = [_input(cfg, 200 + i) for i in range(4)]
        refs = [client.infer(input=x)["output"] for x in xs]
        rids = [client.infer_async(input=x) for x in xs[:2]]
        status = other.provision(image, prog.encode())   # mid-stream
        rids += [client.infer_async(input=x) for x in xs[2:]]
        assert status["status"] == "ready"
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(client.result(rid)["output"], ref)
    finally:
        other.close()
        client.close()
        server.stop()


def test_v1_client_backcompat(resnet_setup):
    """A legacy v1 (rid-less) client still provisions and infers."""
    cfg, prog, image = resnet_setup
    server = InferenceServer()
    addr = server.start()
    client = Client(addr, version=1)
    try:
        assert client.provision(image, prog.encode())["status"] == "ready"
        x = _input(cfg, 7)
        out = client.infer(input=x)["output"]
        v2 = Client(addr)
        np.testing.assert_array_equal(out, v2.infer(input=x)["output"])
        v2.close()
        assert "serving" in client.telemetry()
    finally:
        client.close()
        server.stop()


# ----------------------------------------------------- malformed frames
def test_bad_magic_gets_error_reply_and_clean_close(resnet_setup):
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image)
    try:
        s = socket.create_connection(addr)
        s.sendall(b"XXXX" + bytes([int(proto.Msg.INFER_REQUEST)])
                  + struct.pack("<I", 4))
        f = proto.recv_frame_ex(s)
        assert f.kind == proto.Msg.ERROR
        assert "protocol" in proto.unpack_json(f.payload)["error"]
        s.settimeout(5)
        assert s.recv(1) == b""                     # clean close
        s.close()
        # the handler death is contained: the server still serves
        x = _input(cfg, 9)
        assert client.infer(input=x)["output"].shape[0] == 1
    finally:
        client.close()
        server.stop()


def test_corrupted_crc_gets_error_reply_and_clean_close(resnet_setup):
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image)
    try:
        frame = bytearray(proto.encode_frame(proto.Msg.INFER_REQUEST,
                                             b"x" * 64))
        frame[20] ^= 0xFF                           # corrupt the payload
        s = socket.create_connection(addr)
        s.sendall(bytes(frame))
        f = proto.recv_frame_ex(s)
        assert f.kind == proto.Msg.ERROR
        assert "protocol" in proto.unpack_json(f.payload)["error"]
        s.settimeout(5)
        assert s.recv(1) == b""
        s.close()
        x = _input(cfg, 10)
        assert client.infer(input=x)["output"].shape[0] == 1
    finally:
        client.close()
        server.stop()


# ------------------------------------------------------------ length cap
def test_recv_frame_length_cap_rejects_before_allocation():
    a, b = socket.socketpair()
    try:
        b.sendall(proto.HEADER.pack(proto.MAGIC,
                                    int(proto.Msg.INFER_REQUEST),
                                    0xFFFF_FFF0))
        with pytest.raises(proto.ProtocolError, match="MAX_FRAME"):
            proto.recv_frame_ex(a, max_frame=1 << 10)
    finally:
        a.close()
        b.close()


def test_server_enforces_max_frame(resnet_setup):
    cfg, prog, image = resnet_setup
    server = InferenceServer(max_frame=1 << 16)
    addr = server.start()
    try:
        s = socket.create_connection(addr)
        s.sendall(proto.HEADER.pack(proto.MAGIC,
                                    int(proto.Msg.INFER_REQUEST), 1 << 20))
        f = proto.recv_frame_ex(s)
        assert f.kind == proto.Msg.ERROR
        assert "MAX_FRAME" in proto.unpack_json(f.payload)["error"]
        s.settimeout(5)
        assert s.recv(1) == b""
        s.close()
    finally:
        server.stop()


def test_provision_inner_frames_honor_server_cap(resnet_setup, monkeypatch):
    """The inner image/program frames of PROVISION are decoded under the
    server's configured cap, not the module default."""
    cfg, prog, image = resnet_setup
    monkeypatch.setattr(proto, "MAX_FRAME", 1 << 10)   # shrink the default
    server = InferenceServer(max_frame=64 << 20)       # explicit larger cap
    addr = server.start()
    client = Client(addr, max_frame=64 << 20)
    try:
        # image/program are far beyond 1 KiB: only the explicit cap admits
        assert client.provision(image, prog.encode())["status"] == "ready"
    finally:
        client.close()
        server.stop()


def test_route_send_timeout_isolates_slow_reader():
    """A peer that never reads cannot block a sender forever: the route's
    send timeout trips and the route is marked dead."""
    from repro.serving.server import _Route
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        route = _Route(a, send_timeout=0.2)
        ok = route.send(proto.Msg.INFER_RESPONSE, b"x" * (1 << 22))
        assert not ok and not route.alive
        route.close()
    finally:
        a.close()
        b.close()


def test_client_waiters_all_error_on_dead_connection():
    """When the connection dies, parked waiters error out too — nobody
    waits forever on a response that cannot arrive."""
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    client = Client(lst.getsockname())
    conn, _ = lst.accept()
    errors = []

    def wait_on(rid):
        try:
            client.result(rid)
        except (ConnectionError, OSError) as e:
            errors.append(e)

    threads = [threading.Thread(target=wait_on, args=(r,)) for r in (1, 2)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    conn.close()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert len(errors) == 2
    client.close()
    lst.close()


# --------------------------------------------------- client error handling
def test_client_provision_raises_on_error_frame():
    server = InferenceServer()
    addr = server.start()
    client = Client(addr)
    try:
        with pytest.raises(RuntimeError):
            client.provision(b"garbage-image", b"garbage-program")
    finally:
        client.close()
        server.stop()


def test_client_telemetry_raises_on_error_frame():
    server = InferenceServer()
    addr = server.start()
    client = Client(addr)
    try:
        def boom(**kw):
            raise RuntimeError("telemetry exploded")
        server.platform.telemetry.summary = boom
        with pytest.raises(RuntimeError, match="telemetry exploded"):
            client.telemetry()
    finally:
        client.close()
        server.stop()


# ------------------------------------------------------------ backpressure
def _gate_dispatcher(server):
    """Hold the dispatcher worker at its next item (and keep the idle
    hook from draining around the gate); returns (gate, started)."""
    gate, started = threading.Event(), threading.Event()
    inner = server._loop.handler
    idle = server._loop.on_idle

    def gated(item):
        started.set()
        gate.wait(30)
        inner(item)

    server._loop.handler = gated
    server._loop.on_idle = lambda: idle() if gate.is_set() else False
    return gate, started


def test_backpressure_busy_replies(resnet_setup):
    """Bounded admission queue: overflow gets an immediate ERROR/F_BUSY
    instead of unbounded buffering (or a hang)."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, max_queue=1)
    try:
        gate, started = _gate_dispatcher(server)
        x = _input(cfg, 11)
        rid1 = client.infer_async(input=x)      # admitted, kick gated
        assert started.wait(10)
        rid2 = client.infer_async(input=x)      # admission queue full
        rid3 = client.infer_async(input=x)
        with pytest.raises(ServerBusy):
            client.result(rid2)
        with pytest.raises(ServerBusy):
            client.result(rid3)
        gate.set()
        assert client.result(rid1)["output"].shape[0] == 1
        assert client.telemetry()["serving"]["rejected"] >= 2
    finally:
        client.close()
        server.stop()


def test_priority_reorders_backlogged_requests(resnet_setup):
    """With the dispatcher backlogged, a later high-priority request is
    admitted (and executed) before an earlier low-priority one.
    ``batch_window=1`` disables coalescing so the two requests provably
    execute as separate dispatches in EDF order (with the window open
    they would legally ride one batched dispatch instead)."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, max_queue=8, batch_window=1)
    try:
        order = []
        inner_infer = server._infer

        def tracking(tensors):
            order.append(float(np.asarray(tensors["input"]).flat[0]))
            return inner_infer(tensors)

        server._infer = tracking
        gate, started = _gate_dispatcher(server)
        x_low = np.full((1, cfg.image_size, cfg.image_size, 3), 1.0,
                        np.float32)
        x_high = np.full((1, cfg.image_size, cfg.image_size, 3), 2.0,
                         np.float32)
        rid_low = client.infer_async(input=x_low, priority=9)
        assert started.wait(10)                 # worker gated on the kick
        rid_high = client.infer_async(input=x_high, priority=0)
        deadline = time.monotonic() + 10        # both requests enqueued
        while server.scheduler.pending() < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert server.scheduler.pending() == 2
        gate.set()
        client.result(rid_low)
        client.result(rid_high)
        assert order == [2.0, 1.0]              # high priority ran first
    finally:
        client.close()
        server.stop()


def test_backlog_coalesces_into_batched_dispatch(resnet_setup):
    """A backlog of same-program, same-shape INFERs rides ONE batched
    dispatch (Executor.run_batched), with replies scattered back by
    request id and within the batched tolerance of serial execution
    (test_conformance.BATCHED_RTOL). A solo request
    must NOT count as a batched dispatch (the window never waits)."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, max_queue=32)
    try:
        xs = [_input(cfg, 40 + i) for i in range(6)]
        refs = [client.infer(input=x)["output"] for x in xs]
        assert server.batched_stats["dispatches"] == 0   # solos stay solo

        gate, started = _gate_dispatcher(server)
        rids = [client.infer_async(input=x) for x in xs]
        assert started.wait(10)
        deadline = time.monotonic() + 10
        while server.scheduler.pending() < len(xs) - 1 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        outs = [client.result(rid)["output"] for rid in rids]
        for out, ref in zip(outs, refs):
            assert_batched_close(ref, out)
        st = server.batched_stats
        assert st["dispatches"] >= 1 and st["requests"] >= 2
        assert st["max_batch"] <= server.batch_window
        tel = client.telemetry()["serving"]["batched"]
        assert tel["dispatches"] == st["dispatches"]
    finally:
        client.close()
        server.stop()


def test_warm_compiles_every_window_bucket(resnet_setup):
    """``warm()`` compiles every bucket the batch window can form, on the
    dispatcher, so a coalesced backlog afterwards compiles nothing."""
    from repro.core.executor import Executor

    cfg, prog, image = resnet_setup
    crc = prog.crc()
    for key in [k for k in Executor._batch_cache if k[0] == crc]:
        del Executor._batch_cache[key]
    server, addr, client = _start(prog, image, max_queue=32, batch_window=4)
    try:
        assert server.warm() == [2, 4]
        warmed = {k for k in Executor._batch_cache if k[0] == crc}
        assert warmed == {(crc, 2), (crc, 4)}
        xs = [_input(cfg, 80 + i) for i in range(4)]
        gate, started = _gate_dispatcher(server)
        rids = [client.infer_async(input=x) for x in xs]
        assert started.wait(10)
        deadline = time.monotonic() + 10
        while server.scheduler.pending() < len(xs) - 1 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        for rid in rids:
            client.result(rid)
        assert server.batched_stats["dispatches"] >= 1
        assert {k for k in Executor._batch_cache if k[0] == crc} == warmed
    finally:
        client.close()
        server.stop()


def test_coalescing_disabled_over_tile_mesh(resnet_setup):
    """The partitioned path pipelines one sample per stage — a mesh-
    attached server must keep dispatching per-request (and still be
    bit-identical)."""
    from repro.core import rhal

    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image, mesh=rhal.TileMesh(2),
                                  max_queue=32)
    try:
        xs = [_input(cfg, 60 + i) for i in range(3)]
        refs = [client.infer(input=x)["output"] for x in xs]
        gate, started = _gate_dispatcher(server)
        rids = [client.infer_async(input=x) for x in xs]
        assert started.wait(10)
        gate.set()
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(client.result(rid)["output"],
                                          ref)
        assert server.batched_stats["dispatches"] == 0
    finally:
        client.close()
        server.stop()


# ---------------------------------------------------------------- shedding
def test_deadline_shed_reply_carries_verdict(resnet_setup):
    cfg, prog, image = resnet_setup
    # estimate is enormous: any deadline-carrying request is infeasible
    server, addr, client = _start(
        prog, image,
        scheduler=DeadlineScheduler(step_latency_estimate=100.0))
    try:
        x = _input(cfg, 12)
        with pytest.raises(RequestShed, match="shed"):
            client.infer(input=x, deadline_ms=1.0)
        # no-deadline requests are untouched by the shed policy
        assert client.infer(input=x)["output"].shape[0] == 1
        assert client.telemetry()["serving"]["shed"] == 1
    finally:
        client.close()
        server.stop()


def test_infer_after_shutdown_refused_not_hung(resnet_setup):
    """A plain INFER arriving after the dispatcher has drained away is
    refused explicitly (F_DRAINING) — it is never parked in the scheduler
    where nothing will ever answer it."""
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image)
    try:
        other = Client(addr)
        other.shutdown()
        other.close()
        deadline = time.monotonic() + 15
        while server._loop.alive() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not server._loop.alive()
        with pytest.raises((ServerBusy, ConnectionError, OSError)):
            client.infer(input=_input(cfg, 40))
        assert server.scheduler.pending() == 0
    finally:
        client.close()
        server.stop()


def test_forced_stop_refuses_pending_admissions(resnet_setup):
    """stop(drain=False) still answers every accepted request: pending
    admissions get ERROR/F_DRAINING instead of a silent drop."""
    from repro.serving.scheduler import ScheduledRequest
    from repro.serving.server import _Plain

    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image)
    try:
        sent = []

        class StubRoute:
            def send(self, kind, payload, rid=0, version=1, flags=0):
                sent.append((kind, flags, rid))
                return True

        server._loop.close(drain=True)           # park the dispatcher
        server.scheduler.submit(ScheduledRequest(
            rid=77, tokens_needed=1,
            payload=_Plain(StubRoute(), 77, 2, {}, req=0, t_submit=0)))
        server.stop(drain=False)
        assert sent == [(proto.Msg.ERROR, proto.F_DRAINING, 77)]
    finally:
        client.close()
        server.stop()


# ------------------------------------------------------------ graceful drain
def test_shutdown_drains_queued_requests(resnet_setup):
    cfg, prog, image = resnet_setup
    server, addr, client = _start(prog, image)
    try:
        xs = [_input(cfg, 300 + i) for i in range(5)]
        refs = [client.infer(input=x)["output"] for x in xs]
        rids = [client.infer_async(input=x) for x in xs]
        ack = client.shutdown()                 # queued work still answered
        assert ack["status"] == "draining"
        for rid, ref in zip(rids, refs):
            # the drained backlog may coalesce into one batched dispatch
            assert_batched_close(ref, client.result(rid)["output"])
        deadline = time.monotonic() + 15
        while server._loop._thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not server._loop._thread.is_alive()
    finally:
        client.close()
        server.stop()


# --------------------------------------------------------- partitioned path
def test_server_dispatches_over_tile_mesh(resnet_setup):
    """A server constructed with a TileMesh routes plain-RCB INFERs through
    the partitioned executor path, bit-identical to single-device serving,
    with real inter-tile movement accounted."""
    from repro.core import rhal

    cfg, prog, image = resnet_setup
    mesh = rhal.TileMesh(2)
    server, addr, client = _start(prog, image, mesh=mesh)
    single, saddr, sclient = _start(prog, image)
    try:
        x = _input(cfg, 13)
        out = client.infer(input=x)["output"]
        ref = sclient.infer(input=x)["output"]
        np.testing.assert_array_equal(out, ref)
        assert mesh.moved_bytes() > 0          # cut edges actually streamed
    finally:
        client.close()
        sclient.close()
        server.stop()
        single.stop()


# ------------------------------------------------------------- LM over wire
def _lm_setup(rng, **engine_kw):
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.common import init_params
    from repro.serving.engine import ServingEngine

    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    engine_kw.setdefault("max_batch", 2)
    engine_kw.setdefault("max_seq", 64)
    return cfg, params, ServingEngine(cfg, params, **engine_kw)


def test_lm_engine_over_network(rng):
    """INFER with a prompt routes through ServingEngine continuous
    batching; pipelined tokens match a local engine run token for token."""
    from repro.serving.engine import Request, ServingEngine

    cfg, params, eng = _lm_setup(rng)
    server = InferenceServer(engine=eng)
    addr = server.start()
    client = Client(addr)
    try:
        prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
                   for _ in range(3)]
        rids = [client.infer_async(prompt=p, max_new=3) for p in prompts]
        outs = [client.result(rid)["tokens"] for rid in rids]

        ref_eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
        refs = [Request(rid=i, prompt=p, max_new=3)
                for i, p in enumerate(prompts)]
        for r in refs:
            ref_eng.submit(r)
        ref_eng.run_until_drained()
        for out, r in zip(outs, refs):
            assert list(out) == r.out_tokens
    finally:
        client.close()
        server.stop()


def test_lm_inflight_cap_gives_backpressure(rng):
    """The engine path is bounded too: pipelining past the in-flight cap
    gets ERROR/F_BUSY instead of unbounded scheduler/inflight growth."""
    cfg, params, eng = _lm_setup(rng, max_batch=1)
    server = InferenceServer(engine=eng, max_queue=2)
    addr = server.start()
    client = Client(addr)
    try:
        prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        rids = [client.infer_async(prompt=prompt, max_new=8)
                for _ in range(6)]
        tokens, busy = [], 0
        for rid in rids:
            try:
                tokens.append(list(client.result(rid)["tokens"]))
            except ServerBusy:
                busy += 1
        assert busy >= 1                       # cap enforced
        assert tokens                          # admitted ones complete...
        assert all(t == tokens[0] for t in tokens)   # ...identically
    finally:
        client.close()
        server.stop()


def test_lm_bad_prompt_rejected_engine_survives(rng):
    """An over-long prompt is refused with an ERROR before touching the
    engine; the dispatcher and engine keep serving afterwards."""
    cfg, params, eng = _lm_setup(rng)        # max_seq=64
    server = InferenceServer(engine=eng)
    addr = server.start()
    client = Client(addr)
    try:
        long_prompt = rng.randint(0, cfg.vocab_size, (62,)).astype(np.int32)
        with pytest.raises(RuntimeError, match="max_seq"):
            client.infer(prompt=long_prompt, max_new=8)
        ok = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        assert len(client.infer(prompt=ok, max_new=3)["tokens"]) >= 3
    finally:
        client.close()
        server.stop()


def test_mixed_lm_and_rcb_requests_one_server(resnet_setup, rng):
    """A server with BOTH an engine and a provisioned RCB program routes
    each request by shape without cross-contaminating admission state."""
    cfg_r, prog, image = resnet_setup
    _, _, eng = _lm_setup(rng)
    server = InferenceServer(engine=eng)
    addr = server.start()
    client = Client(addr)
    try:
        client.provision(image, prog.encode())
        x = _input(cfg_r, 21)
        ref = client.infer(input=x)["output"]
        prompt = np.arange(6, dtype=np.int32)
        rid_lm = client.infer_async(prompt=prompt, max_new=3)
        rid_r = client.infer_async(input=x)
        toks = client.result(rid_lm)["tokens"]
        np.testing.assert_array_equal(client.result(rid_r)["output"], ref)
        assert len(toks) >= 3
    finally:
        client.close()
        server.stop()


# ----------------------------------------------------- integrity (ISSUE 7)
def test_client_result_timeout_on_never_replying_server():
    """Satellite: a request id orphaned by a server that never replies
    raises TimeoutError instead of parking the waiter forever."""
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    client = Client(lst.getsockname())
    conn, _ = lst.accept()                  # accept, then go silent
    try:
        rid = client.infer_async(input=np.zeros(4, np.float32))
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="no reply"):
            client.result(rid, timeout=0.4)
        assert time.monotonic() - t0 < 5.0  # bounded, not parked
        # the receive slot was handed back: a second waiter can still
        # time out too (a wedged slot would hang it forever)
        with pytest.raises(TimeoutError):
            client.result(rid + 1, timeout=0.2)
        # and infer(timeout=) surfaces the same thing end-to-end
        with pytest.raises(TimeoutError):
            client.infer(input=np.zeros(4, np.float32), timeout=0.2)
    finally:
        client.close()
        conn.close()
        lst.close()


def test_watchdog_preempts_hung_dispatch_end_to_end(rng):
    """ISSUE 7 tentpole: a dispatch wedged in a DMA redemption blows its
    EWMA-derived deadline, the watchdog kills the hung tile group
    (quarantining its arena), the stage fails over, and the client gets
    the bit-identical answer — a hang becomes bounded latency."""
    import chaos
    from repro.core import rhal, rimfs as rimfs_mod
    depth, n = 4, 16
    prog = rctc.compile_gemm_chain(depth, n)
    image = rimfs_mod.pack(rctc.gemm_chain_weights(depth, n))
    server = InferenceServer(mesh=rhal.TileMesh(2), watchdog_floor=0.3,
                             watchdog_slack=8.0, watchdog_poll=0.01)
    addr = server.start()
    client = Client(addr)
    try:
        client.provision(image, prog.encode())
        x = rng.randn(n, n).astype(np.float32)
        ref = client.infer(input=x)          # warms the scheduler EWMA
        undo, state = chaos.hang_until_killed(server.mesh, 1)
        try:
            out = client.infer(input=x, timeout=30)
        finally:
            undo()
        assert state["released"]             # the kill broke the wedge
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        assert server.platform.telemetry.counter(
            "watchdog_preemptions") >= 1
        assert not server.mesh.alive(1)      # hung group killed...
        assert server.mesh.group(1).driver.arena.poisoned   # ...poisoned
    finally:
        client.close()
        server.stop()

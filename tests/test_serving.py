"""Serving: wire protocol, socket server, LM engine with batched requests."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import rbl, rctc, rhal, rimfs, tracing
from repro.core.executor import Executor
from repro.models import resnet as rn
from repro.models import transformer as tf
from repro.models.common import init_params
from repro.serving import protocol as proto
from repro.serving.engine import (Request, ServingEngine, pack_params_image,
                                  params_from_rimfs)
from repro.serving.scheduler import DeadlineScheduler
from repro.serving.server import Client, InferenceServer

from test_tracing import _gate_dispatcher


def test_frame_roundtrip():
    payload = b"hello aeg" * 100
    kind, back = proto.decode_frame(
        proto.encode_frame(proto.Msg.INFER_REQUEST, payload))
    assert kind == proto.Msg.INFER_REQUEST and back == payload


def test_frame_crc_detects_corruption():
    frame = bytearray(proto.encode_frame(proto.Msg.TELEMETRY, b"x" * 64))
    frame[20] ^= 1
    with pytest.raises(proto.ProtocolError, match="CRC"):
        proto.decode_frame(bytes(frame))


def test_frame_v2_roundtrip_carries_request_id_and_flags():
    payload = b"response" * 16
    f = proto.decode_frame_ex(proto.encode_frame(
        proto.Msg.INFER_RESPONSE, payload, request_id=77,
        flags=proto.F_SHED))
    assert f.kind == proto.Msg.INFER_RESPONSE and f.payload == payload
    assert f.request_id == 77 and f.flags == proto.F_SHED and f.version == 2


def test_frame_v1_decodes_through_extended_decoder():
    f = proto.decode_frame_ex(proto.encode_frame(proto.Msg.HEARTBEAT, b"hb"))
    assert (f.kind, f.payload, f.request_id, f.flags, f.version) == \
        (proto.Msg.HEARTBEAT, b"hb", 0, 0, 1)


def test_frame_v2_crc_detects_corruption():
    frame = bytearray(proto.encode_frame(proto.Msg.INFER_RESPONSE,
                                         b"y" * 64, request_id=3))
    frame[22] ^= 1
    with pytest.raises(proto.ProtocolError, match="CRC"):
        proto.decode_frame_ex(bytes(frame))


def test_decode_frame_enforces_length_cap_before_parsing():
    head = proto.HEADER.pack(proto.MAGIC, int(proto.Msg.INFER_REQUEST),
                             0xFFFF_FFF0)
    with pytest.raises(proto.ProtocolError, match="MAX_FRAME"):
        proto.decode_frame_ex(head, max_frame=1 << 10)


def test_decode_frame_rejects_unknown_type():
    head = proto.HEADER.pack(proto.MAGIC, 0x55, 0)
    with pytest.raises(proto.ProtocolError, match="unknown"):
        proto.decode_frame_ex(head + b"\x00" * 4)


def test_tensor_payload_roundtrip(rng):
    t = {"a": rng.randn(3, 4).astype(np.float32),
         "b": rng.randint(0, 9, (2,), dtype=np.int32)}
    back = proto.unpack_tensors(proto.pack_tensors(t))
    for k in t:
        np.testing.assert_array_equal(t[k], back[k])


def test_network_service_end_to_end(rng):
    """Provision ResNet over the wire, run batched inference, read CV
    telemetry — the paper's network-attached deployment."""
    cfg = __import__("repro.configs.resnet18",
                     fromlist=["CONFIG"]).CONFIG.smoke()
    params = rn.init_resnet(jax.random.PRNGKey(0), cfg)
    folded = rn.fold_bn(params)
    prog, image = rctc.compile_resnet18(cfg, folded, batch=2)

    server = InferenceServer()
    addr = server.start()
    try:
        client = Client(addr)
        status = client.provision(image, prog.encode())
        assert status["status"] == "ready"
        x = rng.rand(2, cfg.image_size, cfg.image_size, 3).astype(np.float32)
        for _ in range(5):
            out = client.infer(input=x)
        ref = np.asarray(rn.resnet_forward(cfg, params, jnp.asarray(x)))
        np.testing.assert_allclose(out["output"], ref, atol=1e-5)
        tel = client.telemetry()
        assert tel["n"] >= 4 and "cv_percent" in tel
        client.close()
    finally:
        server.stop()


def test_batched_prefill_matches_sequential_admission(rng):
    """Regression for the grouped-prefill admission path: prompts that
    prefill together as one (k, S) dispatch must produce the SAME tokens
    as the same prompts admitted one at a time (batch-1 prefill each) —
    otherwise engine output becomes admission-timing-dependent."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(3)]

    # grouped: all three admitted in one _admit -> one (3, 6) prefill
    eng_b = ServingEngine(cfg, params, max_batch=3, max_seq=64)
    batched = [Request(rid=i, prompt=p, max_new=4)
               for i, p in enumerate(prompts)]
    for r in batched:
        eng_b.submit(r)
    eng_b.run_until_drained()

    # sequential: one slot -> every prefill is batch-1
    eng_s = ServingEngine(cfg, params, max_batch=1, max_seq=64)
    serial = []
    for i, p in enumerate(prompts):
        r = Request(rid=i, prompt=p, max_new=4)
        eng_s.submit(r)
        eng_s.run_until_drained()
        serial.append(r)

    for rb, rs in zip(batched, serial):
        assert rb.out_tokens == rs.out_tokens


def test_lm_engine_batched_requests(rng):
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, (8,))
                    .astype(np.int32),
                    max_new=4)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) >= 4 for r in reqs)
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out_tokens)


def test_engine_feeds_scheduler_latency_ewma(rng):
    """The admission policy's EWMA must track REAL decode latencies, not
    the constructor default (eta/shedding ran on 1e-2 forever)."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    sched = DeadlineScheduler(step_latency_estimate=123.0)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                        scheduler=sched)
    eng.submit(Request(rid=0, prompt=rng.randint(
        0, cfg.vocab_size, (4,)).astype(np.int32), max_new=3))
    eng.run_until_drained()
    # EWMA moved off the seed value toward measured step latency (which is
    # far below 123 s on any machine)
    assert sched.est < 123.0
    assert sched.est > 0.0


def test_engine_routes_through_scheduler_and_sheds(rng):
    """ISSUE 4 satellite: submit() routes through scheduler.submit and
    _admit() through scheduler.admit — an infeasible deadline is shed
    BEFORE any compute, marked done with an observable verdict."""
    import time as time_mod
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    sched = DeadlineScheduler()
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                        scheduler=sched)
    prompt = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    good = Request(rid=0, prompt=prompt, max_new=3)
    bad = Request(rid=1, prompt=prompt, max_new=3,
                  deadline=time_mod.monotonic() - 1.0)   # already past
    eng.submit(good)
    eng.submit(bad)
    assert sched.pending() == 2       # queued in the scheduler, not FIFO
    eng.run_until_drained()
    assert bad.done and bad.shed and "shed" in bad.verdict
    assert bad.out_tokens == []       # no compute spent on the shed request
    assert good.done and not good.shed and good.verdict == "admitted"
    assert len(good.out_tokens) >= 3
    assert sched.shed_count == 1


def test_engine_from_rimfs_zero_reupload(rng):
    """Repeated engine construction over one RIMFS image re-binds pinned
    weights: the driver's DMA counters must not move the second time."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    img = pack_params_image(params)
    fs = rimfs.mount(img)
    drv = rhal.make_eager_driver()
    eng1 = ServingEngine.from_rimfs(cfg, fs, driver=drv, max_batch=2,
                                    max_seq=64)
    uploaded = drv.stats.get("dma_bytes", 0)
    assert uploaded > 0
    snapshot = dict(drv.stats)
    eng2 = ServingEngine.from_rimfs(cfg, fs, driver=drv, max_batch=2,
                                    max_seq=64)
    for key in ("dma", "dma_async", "dma_bytes"):
        assert drv.stats.get(key, 0) == snapshot.get(key, 0), key
    # both engines decode identically from the shared pinned weights
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    r1 = Request(rid=0, prompt=prompt, max_new=3)
    r2 = Request(rid=1, prompt=prompt, max_new=3)
    eng1.submit(r1)
    eng2.submit(r2)
    eng1.run_until_drained()
    eng2.run_until_drained()
    assert r1.out_tokens == r2.out_tokens


def test_engine_accepts_tile_mesh(rng):
    """ServingEngine provisions from a TileMesh in place of one driver:
    weights pin into the primary tile group's arena (same zero-reupload
    residency), the mesh rides on the engine, and decode matches a
    single-driver engine token for token."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    fs = rimfs.mount(pack_params_image(params))
    mesh = rhal.TileMesh(2)
    eng_m = ServingEngine.from_rimfs(cfg, fs, driver=mesh, max_batch=2,
                                     max_seq=64)
    assert eng_m.mesh is mesh
    primary = mesh.primary
    uploaded = primary.stats.get("dma_bytes", 0)
    assert uploaded > 0                       # pinned into group 0's arena
    snapshot = dict(primary.stats)
    ServingEngine.from_rimfs(cfg, fs, driver=mesh, max_batch=2, max_seq=64)
    assert primary.stats.get("dma_bytes", 0) == snapshot.get("dma_bytes", 0)
    drv = rhal.make_eager_driver()
    eng_d = ServingEngine.from_rimfs(cfg, fs, driver=drv, max_batch=2,
                                     max_seq=64)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    r1 = Request(rid=0, prompt=prompt, max_new=3)
    r2 = Request(rid=1, prompt=prompt, max_new=3)
    eng_m.submit(r1)
    eng_d.submit(r2)
    eng_m.run_until_drained()
    eng_d.run_until_drained()
    assert r1.out_tokens == r2.out_tokens


def test_params_rimfs_roundtrip_matches(rng):
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    fs = rimfs.mount(pack_params_image(params))
    back = params_from_rimfs(cfg, fs)
    flat_a = jax.tree_util.tree_leaves(params)
    flat_b = jax.tree_util.tree_leaves(back)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lm_engine_matches_offline_decode(rng):
    """Engine tokens == straight greedy decode with the same params."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    prompt = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)

    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    req = Request(rid=0, prompt=prompt, max_new=4)
    eng.submit(req)
    eng.run_until_drained()

    # offline: full forward re-run per token (slow but unimpeachable)
    toks = list(prompt)
    out = []
    for _ in range(4):
        logits, _, _ = tf.forward_full(
            cfg, params, jnp.asarray(np.asarray(toks))[None, :])
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        toks.append(t)
    assert req.out_tokens[:4] == out


# ------------------------------------------------- plain-RCB weight residency
CHAIN_DEPTH, CHAIN_N = 4, 16


@pytest.fixture(scope="module")
def chain():
    prog = rctc.compile_gemm_chain(CHAIN_DEPTH, CHAIN_N)
    files = rctc.gemm_chain_weights(CHAIN_DEPTH, CHAIN_N)
    return prog, files, rimfs.pack(files)


def _chain_x(seed: int) -> np.ndarray:
    return np.random.RandomState(seed).randn(CHAIN_N, CHAIN_N) \
        .astype(np.float32)


def _serve(chain, **kw):
    prog, _, image = chain
    server = InferenceServer(**kw)
    client = Client(server.start())
    client.provision(image, prog.encode())
    return server, client


def _infer_coalesced(server, client, xs) -> list:
    """Send ``xs`` while the dispatcher is held, so they ride one batched
    dispatch; returns the replies in order."""
    inner, idle = server._loop.handler, server._loop.on_idle
    gate, started = _gate_dispatcher(server)
    before = server.batched_stats["dispatches"]
    rids = [client.infer_async(input=x) for x in xs]
    assert started.wait(10)
    deadline = time.monotonic() + 10
    while server.scheduler.pending() < len(xs) and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    gate.set()
    outs = [client.result(rid, timeout=30)["output"] for rid in rids]
    server._loop.handler, server._loop.on_idle = inner, idle
    assert server.batched_stats["dispatches"] == before + 1
    return outs


def test_provision_pins_weights_on_the_executor_driver(chain):
    """PROVISION uploads the program's weights through the executor's
    driver once; a solo and a batched request then move no weight byte."""
    _, files, _ = chain
    server, client = _serve(chain, batch_window=8)
    try:
        drv = server.executor.driver
        weights = {n: b for n, b in server._bound.buffers.items()
                   if n in files}
        assert set(weights) == set(files)
        assert all(isinstance(b, jax.Array) for b in weights.values())
        assert drv.stats["dma_bytes"] == sum(f.nbytes for f in
                                             files.values())
        moved = drv.stats["dma_bytes"]
        since = time.perf_counter_ns()
        client.infer(input=_chain_x(0))
        _infer_coalesced(server, client, [_chain_x(i) for i in (1, 2, 3)])
        assert drv.stats["dma_bytes"] == moved
        issues = [s for s in tracing.spans(since) if s.name == "aeg.issue"]
        assert len(issues) == 2
        assert [s.stats["weight_h2d_bytes"] for s in issues] == [0, 0]
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("mode", ["solo", "batched"])
def test_pinned_replies_match_a_host_view_bind(chain, mode):
    """The pinned binding serves the bits a host-view bind of the same
    program computes, through ``run`` and through ``run_batched``."""
    prog, _, image = chain
    xs = [_chain_x(10 + i) for i in range(3)]
    host = rbl.bind(prog, rimfs=rimfs.mount(image))
    assert not any(isinstance(b, jax.Array) for b in host.buffers.values())
    server, client = _serve(chain, batch_window=8)
    try:
        if mode == "solo":
            got = [client.infer(input=x)["output"] for x in xs]
            ref = [np.asarray(Executor().run(host, inputs={"input": x})
                              ["output"]) for x in xs]
        else:
            got = _infer_coalesced(server, client, xs)
            ref = [o["output"] for o in Executor().run_batched(
                host, [{"input": x} for x in xs])]
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)
    finally:
        client.close()
        server.stop()


def test_tile_mesh_server_keeps_a_host_view_primary_bind(chain):
    """Over a TileMesh each tile group binds against its own driver, so
    the primary binding is not pinned a second time."""
    _, files, _ = chain
    server, client = _serve(chain, mesh=rhal.TileMesh(2))
    try:
        assert all(isinstance(server._bound.buffers[n], np.ndarray)
                   for n in files)
        assert server.executor.driver.stats.get("dma_bytes", 0) == 0
        client.infer(input=_chain_x(0))
    finally:
        client.close()
        server.stop()


def test_reprovision_releases_the_replaced_image(chain):
    """A second PROVISION pins the new image and unpins the one it
    replaces: the driver's arena holds one image's weights, not two."""
    prog, files, image = chain
    server, client = _serve(chain)
    try:
        arena = server.executor.driver.arena
        held = arena.bytes_in_use
        assert held >= sum(f.nbytes for f in files.values())
        client.provision(image, prog.encode())
        assert arena.bytes_in_use == held
        assert all(isinstance(server._bound.buffers[n], jax.Array)
                   for n in files)
        client.infer(input=_chain_x(0))
    finally:
        client.close()
        server.stop()

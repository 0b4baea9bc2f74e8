"""Brown-out overload control plane (ISSUE 10): degradation-ladder walk
with hysteresis, typed shed verdicts on the wire, terminal (infeasible)
verdicts that never burn retries, client retry-after hints, LM decode
clamping, and the tile-group circuit breaker."""
import threading
import time

import numpy as np
import pytest

from repro.core import rctc, rhal, rimfs
from repro.serving.overload import (MAX_RUNG, BrownoutController,
                                    OverloadConfig)
from repro.serving.server import (Client, InferenceServer, RequestShed,
                                  ServerBusy, _Work)

DEPTH, N = 6, 16


@pytest.fixture(scope="module")
def chain_setup():
    prog = rctc.compile_gemm_chain(DEPTH, N)
    files = rctc.gemm_chain_weights(DEPTH, N)
    return prog, files, rimfs.pack(files)


def _start(prog, image, mesh_groups=0, **kw):
    mesh = rhal.TileMesh(mesh_groups) if mesh_groups else None
    server = InferenceServer(mesh=mesh, **kw)
    addr = server.start()
    client = Client(addr)
    client.provision(image, prog.encode())
    return server, addr, client


def _x(seed=0):
    return np.random.RandomState(seed).randn(N, N).astype(np.float32)


def _heat(server, n, seconds=0.4):
    """Feed the dispatcher's queue-wait telemetry over-threshold samples
    (the ladder's pressure signal), deterministically."""
    for _ in range(n):
        server._loop.queue_wait.record_latency(seconds)


def _wedge_dispatcher(server):
    gate = threading.Event()
    entered = threading.Event()

    def ctl():
        entered.set()
        gate.wait(30)

    # the bounded dispatch queue may still be draining a previous burst;
    # retry the control submit until a slot frees instead of asserting
    # on a racy snapshot
    deadline = time.time() + 5
    while not server._loop.submit(
            _Work(frame=None, route=None, control=ctl)):
        assert time.time() < deadline, "dispatch queue never drained"
        time.sleep(0.01)
    assert entered.wait(5)
    return gate


# ----------------------------------------------------------------- ladder
def test_ladder_walks_down_and_back_with_hysteresis(chain_setup):
    """Hot queue-wait p99 ticks descend one rung per escalate_ticks; cool
    ticks climb back one rung per recover_ticks. Each rung's service
    changes (batch window, LM clamp, priority ceiling) apply and revert
    together, and a single noisy tick never moves the ladder."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image)
    try:
        saved_window = server.batch_window
        cfg = OverloadConfig(p99_high=0.1, min_window=2, escalate_ticks=2,
                             recover_ticks=2, max_new_clamp=4,
                             shed_priority=2)
        over = BrownoutController(server, cfg)
        rungs = []
        for _ in range(2 * MAX_RUNG):
            _heat(server, cfg.min_window, 0.4)
            over.tick()
            rungs.append(over.rung)
        assert rungs[0] == 0 and rungs[1] == 1   # hysteresis held tick 1
        assert over.rung == MAX_RUNG
        assert server.batch_window == 1
        assert server.max_new_clamp == cfg.max_new_clamp
        assert server.scheduler.priority_ceiling == cfg.shed_priority
        assert over.breaker.state == "closed"    # no failing group: rung 4
        over.tick()                              # trips nothing
        assert over.rung == MAX_RUNG             # one cool tick holds
        for _ in range(2 * MAX_RUNG + 2):
            over.tick()
        assert over.rung == 0
        assert server.batch_window == saved_window
        assert server.max_new_clamp is None
        assert server.scheduler.priority_ceiling is None
        moves = [(p["from"], p["to"]) for k, p in over.events
                 if k == "brownout_rung"]
        assert moves[:4] == [(0, 1), (1, 2), (2, 3), (3, 4)]
        assert moves[-1] == (1, 0)
        assert over.summary()["name"] == "normal"
    finally:
        client.close()
        server.stop()


def test_ladder_reads_fresh_waits_past_ring_capacity(chain_setup):
    """Once the queue-wait ring is full, each tick still sees the samples
    since the last one: the ladder descends on hot ones and climbs back
    on cool ones."""
    from repro.core.rtpm import Telemetry
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image)
    try:
        server._loop.queue_wait = Telemetry(capacity=4)
        _heat(server, 8, 0.001)                 # the ring is full
        cfg = OverloadConfig(p99_high=0.1, min_window=2, escalate_ticks=1,
                             recover_ticks=1)
        over = BrownoutController(server, cfg)
        for _ in range(3):
            _heat(server, 3, 0.4)
            over.tick()
        assert over.rung == 3
        # three requests, and the wait of the last tick's control op
        assert over.history[-1]["obs"]["window"] >= 3
        _heat(server, 3, 0.001)
        over.tick()
        assert over.rung == 2
    finally:
        client.close()
        server.stop()


# ----------------------------------------------------------- typed sheds
def test_rung3_sheds_low_priority_with_typed_verdict(chain_setup):
    """At rung 3, admissions at or past the priority ceiling get an
    honest machine-readable refusal: kind "brownout", retryable, with a
    retry-after hint. Urgent classes keep full bit-identical service,
    and dropping the rung restores the shed class."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image)
    try:
        over = BrownoutController(server, OverloadConfig(shed_priority=2))
        x = _x(1)
        ref = client.infer(input=x)
        over.set_rung(3, reason="test")
        with pytest.raises(RequestShed) as ei:
            client.infer(input=x, priority=5)
        e = ei.value
        assert e.kind == "brownout"
        assert e.retryable is True
        assert e.retry_after_ms >= 1
        out = client.infer(input=x)              # priority 1: still served
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        over.tick()                              # honest accounting
        shed_n = sum(p["n"] for k, p in over.events
                     if k == "brownout_shed")
        assert shed_n == 1
        over.set_rung(0, reason="test")
        out = client.infer(input=x, priority=5)  # capacity returned
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
    finally:
        client.close()
        server.stop()


def test_infeasible_deadline_is_terminal_never_retried(chain_setup):
    """An infeasible deadline is a TERMINAL verdict: re-sending the same
    request cannot help, so a retry-enabled client fails fast without
    burning a single retry."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image)
    try:
        cl = Client(addr, retries=5, backoff=0.01)
        with pytest.raises(RequestShed) as ei:
            cl.infer(input=_x(2), deadline_ms=0.0)
        e = ei.value
        assert e.kind == "infeasible"
        assert e.retryable is False
        assert e.retry_after_ms == 0
        assert cl.retry_stats["retries"] == 0
        cl.close()
    finally:
        client.close()
        server.stop()


def test_client_honors_retry_after_hint(chain_setup):
    """Busy refusals carry a retry_after_ms hint; a retrying client
    sleeps at least that long instead of hammering the same wall, and
    counts every honored hint."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, max_queue=4)
    try:
        x = _x(3)
        ref = client.infer(input=x)

        # the hint is on the wire even for a zero-retry client: burst
        # into the wedge, THEN release it and collect — waiting on an
        # accepted request while the dispatcher is still wedged would
        # deadlock against our own gate
        gate = _wedge_dispatcher(server)
        plain = Client(addr)
        try:
            rids = [plain.infer_async(input=x) for _ in range(10)]
        finally:
            gate.set()
        hints, served = [], 0
        for rid in rids:
            try:
                plain.result(rid)
                served += 1
            except ServerBusy as e:
                assert e.kind == "busy" and e.retryable is True
                hints.append(e.retry_after_ms)
        assert hints and all(h >= 1 for h in hints)
        assert served + len(hints) == 10
        plain.close()

        # retrying clients honor it: a concurrent burst into the wedge
        # fully succeeds, and the hinted counter moves with EVERY busy
        # retry (the server always sends a hint with a busy refusal)
        gate = _wedge_dispatcher(server)
        results, errors, stats = [], [], []
        lock = threading.Lock()

        def worker(cid):
            cl = Client(addr, retries=20, backoff=0.01, retry_seed=cid)
            try:
                for _ in range(6):
                    out = cl.infer(input=x)
                    with lock:
                        results.append(out)
                with lock:
                    stats.append(dict(cl.retry_stats))
            except Exception as e:          # pragma: no cover
                errors.append(e)
            finally:
                cl.close()

        threads = [threading.Thread(target=worker, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.15)                    # let the burst hit the wedge
        gate.set()
        for t in threads:
            t.join(timeout=60)
        assert not errors and len(results) == 24
        for out in results:
            for k in ref:
                np.testing.assert_array_equal(ref[k], out[k])
        assert sum(s["busy"] for s in stats) > 0, \
            "burst never saw backpressure — wedge did not engage"
        for s in stats:
            assert s["hinted"] == s["busy"]
    finally:
        client.close()
        server.stop()


# ---------------------------------------------------------------- LM path
def _lm_server(rng, **over_kw):
    import jax

    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.common import init_params
    from repro.serving.engine import ServingEngine

    cfg = get_config("qwen2-1.5b-smoke")
    params = init_params(jax.random.PRNGKey(0), tf.model_specs(cfg))
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    server = InferenceServer(engine=eng)
    addr = server.start()
    client = Client(addr)
    over = BrownoutController(server, OverloadConfig(**over_kw))
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    return server, client, over, prompt


def test_rung2_clamps_lm_decode_budget(rng):
    """At rung 2 LM admissions get max_new clamped: the same request
    yields a greedy PREFIX of the full answer — degraded honestly, never
    differently. Recovery restores the full budget."""
    server, client, over, prompt = _lm_server(rng, max_new_clamp=2)
    try:
        full = list(client.infer(prompt=prompt, max_new=6)["tokens"])
        short = list(client.infer(prompt=prompt, max_new=2)["tokens"])
        assert len(short) < len(full)
        over.set_rung(2, reason="test")
        clamped = list(client.infer(prompt=prompt, max_new=6)["tokens"])
        # clamped max_new=6 behaves EXACTLY like asking for max_new=2:
        # a greedy prefix of the full answer, never a different answer
        assert clamped == short == full[:len(short)]
        over.set_rung(0, reason="test")
        again = list(client.infer(prompt=prompt, max_new=6)["tokens"])
        assert again == full
    finally:
        client.close()
        server.stop()


def test_lm_brownout_shed_is_typed_and_idempotent_retryable(rng):
    """The engine path sheds with the same typed verdicts; a request
    refused at admission sampled zero tokens, so the verdict is
    retryable — the idempotency guard only blocks mid-sampling sheds."""
    server, client, over, prompt = _lm_server(rng, shed_priority=2)
    try:
        ref = list(client.infer(prompt=prompt, max_new=3)["tokens"])
        over.set_rung(3, reason="test")
        with pytest.raises(RequestShed) as ei:
            client.infer(prompt=prompt, max_new=3, priority=5)
        e = ei.value
        assert e.kind == "brownout"
        assert e.retryable is True              # zero tokens sampled
        assert e.retry_after_ms >= 1
        out = list(client.infer(prompt=prompt, max_new=3)["tokens"])
        assert out == ref                       # urgent class: full service
        over.set_rung(0, reason="test")
    finally:
        client.close()
        server.stop()


# -------------------------------------------------------- circuit breaker
def test_circuit_breaker_trips_probes_and_closes(chain_setup):
    """Rung 4 circuit-breaks the worst FAILING tile group: the kill rides
    the existing quarantine path (failover keeps serving bit-identical),
    the half-open probe golden-checks the revived group against the
    survivors' answer, and only a bit-identical probe closes the
    circuit."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        over = BrownoutController(server, OverloadConfig(
            breaker_cooldown_ticks=1, recover_ticks=100))
        x = _x(5)
        ref = client.infer(input=x)
        mesh = server.mesh
        # this group failed twice on the record (tile_failure events)
        server.platform.post("tile_failure", {"group": 1})
        server.platform.post("tile_failure", {"group": 1})
        rep = over.set_rung(4, reason="test")
        assert rep["tripped"] == 1
        assert over.breaker.state == "open"
        assert not mesh.alive(1)
        out = client.infer(input=x)        # quarantined: failover serves
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        over.tick()                        # cooldown expires: golden probe
        assert over.breaker.state == "closed"
        assert mesh.alive(1)
        kinds = [k for k, _ in over.events]
        assert "circuit_open" in kinds and "circuit_closed" in kinds
        assert over.breaker.stats == {"trips": 1, "probes": 1, "closes": 1}
        out = client.infer(input=x)        # full mesh back in rotation
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        over.set_rung(0, reason="test")
    finally:
        client.close()
        server.stop()

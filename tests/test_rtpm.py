"""RTPM: event dispatch, heartbeats/stragglers, telemetry CV, provisioning,
the ServiceLoop dispatcher worker, and tile-group fault injection (kill a
worker mid-program -> heartbeat detection -> stage re-queue on a survivor
-> reference-identical output)."""
import threading
import time

import numpy as np

import jax

from repro.core import rbl, rctc, rhal, rimfs
from repro.core.executor import Executor
from repro.core.rtpm import EventDispatcher, HeartbeatMonitor, Platform, \
    ServiceLoop, Telemetry


def test_event_dispatch_fanout():
    d = EventDispatcher()
    seen = []
    d.register("x", lambda p: seen.append(("a", p["v"])))
    d.register("x", lambda p: seen.append(("b", p["v"])))
    d.post("x", {"v": 1})
    d.post("y", {})
    assert d.process() == 2
    assert seen == [("a", 1), ("b", 1)]
    assert d.dropped == 1                     # unhandled "y"


def test_heartbeat_failure_and_straggler():
    t = [0.0]
    mon = HeartbeatMonitor(deadline=10.0, straggler_factor=2.0,
                           clock=lambda: t[0])
    for w in ("w0", "w1", "w2"):
        mon.beat(w, step=10)
    t[0] = 6.0
    mon.beat("w0", step=11)                    # w1/w2 now 6s stale (> 10/2)
    v = mon.check()
    assert set(v["stragglers"]) == {"w1", "w2"}
    assert v["failed"] == []
    t[0] = 17.0                                # w1/w2 now 17s stale (> 10)
    mon.beat("w0", step=12)                    # w0 stays healthy
    v = mon.check()
    assert set(v["failed"]) == {"w1", "w2"}
    # dead workers stay dead
    assert mon.check()["failed"] == []


def test_step_lag_marks_straggler():
    t = [0.0]
    mon = HeartbeatMonitor(deadline=100.0, clock=lambda: t[0])
    mon.beat("fast1", step=50)
    mon.beat("fast2", step=51)
    mon.beat("slow", step=10)
    v = mon.check()
    assert "slow" in v["stragglers"]


def test_telemetry_cv():
    tel = Telemetry()
    rng = np.random.RandomState(0)
    for _ in range(1000):
        tel.record_latency(1e-3 + rng.randn() * 1e-6)
    s = tel.summary(warmup=10)
    assert s["n"] == 990
    assert s["cv_percent"] < 1.0
    assert s["p99"] >= s["p50"] >= s["min"]


def test_telemetry_windows_past_capacity():
    """``count()`` numbers every sample, and ``summary(warmup=seen)``
    covers the samples since ``seen`` that the ring still holds, also
    after it wrapped."""
    tel = Telemetry(capacity=4)
    for i in range(10):
        tel.record_latency(float(i))
    assert tel.count() == 10
    s = tel.summary(warmup=8)
    assert s["n"] == 2 and (s["min"], s["max"]) == (8.0, 9.0)
    s = tel.summary(warmup=3)            # 3..5 fell off the ring
    assert s["n"] == 4 and s["min"] == 6.0
    assert tel.summary(warmup=10) == {"n": 0}
    tel.record_latency(10.0)
    tel.record_latency(11.0)
    s = tel.summary(warmup=10)
    assert s["n"] == 2 and s["mean"] == 10.5


def test_platform_provision_bind_run(rng):
    """The paper's 4-phase flow end to end through the Platform."""
    prog = rctc.compile_matmul(16)
    img = rimfs.pack({"b": rng.randn(16, 16).astype(np.float32)})
    plat = Platform()
    plat.provision(image=img, program_bytes=prog.encode())
    assert plat.time_to_service() >= 0
    bound = plat.bind(inputs={"a": rng.randn(16, 16).astype(np.float32)})
    ex = Executor(rtpm=plat)
    out = ex.run(bound)
    assert out["output"].shape == (16, 16)


def test_platform_rejects_corrupt_image(rng):
    import pytest

    from repro.core.rimfs import RIMFSError
    img = bytearray(rimfs.pack({"w": rng.randn(8).astype(np.float32)}))
    img[-2] ^= 0xFF
    with pytest.raises(RIMFSError):
        Platform().provision(image=bytes(img))


# ---------------------------------------------------------------------------
# ServiceLoop (the single-owner dispatcher worker)
# ---------------------------------------------------------------------------

def _wait_until(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def test_service_loop_processes_in_order_and_heartbeats():
    plat = Platform()
    seen = []
    loop = ServiceLoop(plat, seen.append, name="w0", max_queue=16,
                       poll=0.01)
    try:
        assert all(loop.submit(i) for i in range(5))
        assert _wait_until(lambda: len(seen) == 5)
        assert seen == [0, 1, 2, 3, 4]        # one thread, FIFO order
        w = plat.heartbeats.workers["w0"]
        assert w.alive and w.step == 5
        assert loop.stats["processed"] == 5
        assert loop.queue_wait.summary()["n"] == 5
    finally:
        loop.close()


def test_service_loop_backpressure_then_drain():
    plat = Platform()
    gate = threading.Event()
    started = threading.Event()
    seen = []

    def handler(item):
        started.set()
        gate.wait(10)
        seen.append(item)

    loop = ServiceLoop(plat, handler, max_queue=2, poll=0.01)
    assert loop.submit("a")
    assert started.wait(5)                    # "a" dequeued, worker gated
    assert loop.submit("b") and loop.submit("c")
    assert not loop.submit("d")               # queue full -> rejected
    assert loop.stats["rejected"] == 1
    gate.set()
    loop.close(drain=True)                    # graceful: b/c still processed
    assert seen == ["a", "b", "c"]
    assert not loop.submit("e")               # draining rejects new work
    assert loop.stats["rejected"] == 2


def test_service_loop_handler_error_does_not_kill_worker():
    plat = Platform()
    seen = []

    def handler(item):
        if item == "boom":
            raise RuntimeError("boom")
        seen.append(item)

    loop = ServiceLoop(plat, handler, poll=0.01)
    try:
        loop.submit("boom")
        loop.submit("ok")
        assert _wait_until(lambda: seen == ["ok"])
        assert loop.stats["errors"] == 1
        assert loop.stats["processed"] == 2
    finally:
        loop.close()


def test_service_loop_on_idle_pumps_between_items():
    plat = Platform()
    pumped = {"n": 0, "left": 3}

    def on_idle():
        if pumped["left"] > 0:
            pumped["left"] -= 1
            pumped["n"] += 1
            return True
        return False

    loop = ServiceLoop(plat, lambda item: None, poll=0.01, on_idle=on_idle)
    try:
        assert _wait_until(lambda: pumped["n"] == 3)
    finally:
        loop.close()


def test_service_loop_accepted_submits_survive_racing_close():
    """A submit that returned True is never silently dropped by a
    concurrent close(drain=True): the drain sentinel always lands after
    every accepted item."""
    plat = Platform()
    seen = []
    loop = ServiceLoop(plat, seen.append, max_queue=4096, poll=0.005)
    accepted = []

    def produce(base):
        for i in range(300):
            if loop.submit(base + i):
                accepted.append(base + i)

    producers = [threading.Thread(target=produce, args=(t * 1000,))
                 for t in range(4)]
    closer = threading.Thread(target=lambda: loop.close(drain=True))
    for t in producers:
        t.start()
    closer.start()
    for t in producers:
        t.join()
    closer.join()
    assert set(accepted) <= set(seen)


def test_service_loop_forced_close_hands_back_dropped_items():
    """close(drain=False) never silently discards accepted work — every
    dropped item goes to on_drop so its submitter can be refused."""
    plat = Platform()
    gate = threading.Event()
    started = threading.Event()
    handled, dropped = [], []

    def handler(item):
        started.set()
        gate.wait(10)
        handled.append(item)

    loop = ServiceLoop(plat, handler, max_queue=8, poll=0.01,
                       on_drop=dropped.append)
    assert loop.submit("a")
    assert started.wait(5)                    # worker holds "a"
    assert loop.submit("b") and loop.submit("c")
    closer = threading.Thread(target=lambda: loop.close(drain=False))
    closer.start()
    deadline = time.monotonic() + 5
    while len(dropped) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert dropped == ["b", "c"]              # refused, not vanished
    gate.set()
    closer.join(timeout=10)
    assert handled == ["a"]


def test_event_dispatcher_concurrent_posts_lose_nothing():
    d = EventDispatcher()
    seen = []
    d.register("tick", lambda p: seen.append(p["v"]))
    n_threads, per_thread = 4, 200

    def produce(base):
        for i in range(per_thread):
            d.post("tick", {"v": base + i})

    threads = [threading.Thread(target=produce, args=(t * 1000,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    d.process()
    assert sorted(seen) == sorted(t * 1000 + i for t in range(n_threads)
                                  for i in range(per_thread))


# ---------------------------------------------------------------------------
# Tile-group fault injection (partitioned execution under RTPM)
# ---------------------------------------------------------------------------

def _chain_setup(depth=4, n=16, seed=0):
    prog = rctc.compile_gemm_chain(depth, n)
    files = rctc.gemm_chain_weights(depth, n)
    fs = rimfs.mount(rimfs.pack(files))
    x = np.random.RandomState(seed).randn(n, n).astype(np.float32)
    ref = Executor().run(rbl.bind(prog, rimfs=fs, inputs={"input": x}))
    ref = {k: np.asarray(jax.block_until_ready(v)) for k, v in ref.items()}
    return prog, fs, x, ref


def test_tile_failure_detected_and_stage_requeued(rng):
    """Kill a tile group mid-program: HeartbeatMonitor flags it dead,
    Platform re-queues the orphaned stage on a surviving group, and the
    final output is bit-identical to the single-device reference."""
    prog, fs, x, ref = _chain_setup()
    t = {"now": 0.0}
    plat = Platform(deadline=5.0, clock=lambda: t["now"])
    mesh = rhal.TileMesh(2)
    seen = {"failed": [], "requeued": []}
    plat.events.register("worker_failed",
                         lambda p: seen["failed"].append(p))
    plat.events.register("stage_requeued",
                         lambda p: seen["requeued"].append(p))

    def killer(p):
        if p["stage"] == 0:            # group 1's stage has NOT run yet
            mesh.kill(1)
            t["now"] += 10.0           # past the 5 s heartbeat deadline
    plat.events.register("stage_complete", killer)

    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    out = plat.run_partitioned(bound, mesh=mesh, rimfs=fs)

    # detection: the monitor (not the exception path) judged tile1 dead —
    # live groups answered the liveness sweep, the killed one could not
    assert plat.heartbeats.workers["tile1"].alive is False
    assert any("tile1" in p["workers"] for p in seen["failed"])
    # re-queue: stage 1 moved to the surviving group 0
    assert seen["requeued"] and seen["requeued"][0]["from"] == 1
    assert seen["requeued"][0]["to"] == 0
    # output survives the failover bit-identically
    assert set(out) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(
            ref[k], np.asarray(jax.block_until_ready(out[k])))


def test_tile_failure_on_first_stage_fails_over(rng):
    """A group dead BEFORE its first dispatch: the stage never starts
    there — it re-queues and the program still completes correctly."""
    prog, fs, x, ref = _chain_setup()
    t = {"now": 0.0}
    plat = Platform(deadline=5.0, clock=lambda: t["now"])
    mesh = rhal.TileMesh(3)
    mesh.kill(0)
    t["now"] = 10.0                    # group 0 silent past the deadline
    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    out = plat.run_partitioned(bound, mesh=mesh, rimfs=fs)
    assert plat.heartbeats.workers["tile0"].alive is False
    for k in ref:
        np.testing.assert_array_equal(
            ref[k], np.asarray(jax.block_until_ready(out[k])))


def test_all_tiles_dead_raises(rng):
    import pytest
    prog, fs, x, _ = _chain_setup(depth=2)
    mesh = rhal.TileMesh(2)
    mesh.kill(0)
    mesh.kill(1)
    bound = rbl.bind(prog, rimfs=fs, inputs={"input": x})
    with pytest.raises(rhal.TileFailure):
        Executor().run_partitioned(bound, rimfs=fs, mesh=mesh)


def test_heartbeat_ewma_straggler_verdict():
    """Satellite (ISSUE 6): a worker with an established beat rhythm is
    flagged ``straggler`` once its silence exceeds the EWMA of its own
    inter-beat gaps times ``straggler_factor`` — long before the
    wall-clock deadline would notice."""
    t = [0.0]
    mon = HeartbeatMonitor(deadline=1000.0, straggler_factor=3.0,
                           clock=lambda: t[0])
    for i in range(1, 6):                      # rhythm: one beat per 1.0s
        t[0] = float(i)
        mon.beat("rhythmic", step=i)
        mon.beat("other", step=i)
    assert abs(mon.workers["rhythmic"].gap_ewma - 1.0) < 1e-9
    t[0] = 10.0
    mon.beat("other", step=6)                  # keeps beating (gap ewma
    mon.beat("fresh", step=5)                  # adapts); fresh: one beat,
    v = mon.check()                            # no rhythm yet
    assert v["verdicts"]["rhythmic"] == "straggler"   # 5s silent vs ~1s
    assert v["verdicts"]["other"] == "ok"
    assert v["verdicts"]["fresh"] == "ok"      # no EWMA -> no verdict
    assert v["failed"] == []                   # alive, not dead: 5s << 1000s
    t[0] = 10.5
    mon.beat("rhythmic", step=6)               # it was just slow — beats
    assert mon.check()["verdicts"]["rhythmic"] == "ok"


def test_service_loop_close_wedged_handler_times_out_and_hands_back():
    """Satellite (ISSUE 6, extended by ISSUE 7): close(drain=True,
    timeout=...) against a wedged handler honours the timeout, hands
    every still-queued item AND the wedged in-flight item to on_drop
    (its submitter must be refused, not parked forever; downstream
    reply-once guards make a late handler completion harmless), and
    leaves the heartbeat monitor to report the dispatcher dead — no
    indefinite hang, no silently vanished work."""
    t = {"now": 0.0}
    plat = Platform(deadline=5.0, clock=lambda: t["now"])
    gate = threading.Event()
    started = threading.Event()
    handled, dropped = [], []

    def handler(item):
        started.set()
        gate.wait(30)                          # wedged mid-item
        handled.append(item)

    loop = ServiceLoop(plat, handler, max_queue=8, poll=0.01,
                       on_drop=dropped.append)
    try:
        assert loop.submit("a")
        assert started.wait(5)                 # worker holds "a"
        assert loop.submit("b") and loop.submit("c")
        w0 = time.monotonic()
        loop.close(drain=True, timeout=0.4)
        elapsed = time.monotonic() - w0
        assert elapsed < 3.0                   # timeout honoured, no hang
        assert loop.alive()                    # worker is still wedged
        # pending work handed back, then the wedged in-flight item too
        assert dropped == ["b", "c", "a"]
        t["now"] = 10.0                        # silence past the deadline
        v = plat.heartbeats.check()
        assert "dispatcher" in v["failed"]     # monitor calls it dead
    finally:
        gate.set()                             # late unwedge: worker must
    loop._thread.join(timeout=10)              # exit via re-armed sentinel
    assert not loop.alive()
    assert handled == ["a"]

"""Fleet controller: autoscaling decisions, live mesh reshape with zero
dropped requests, hot weight swap (probe / commit / rollback), RIMFS
residency under swap, client backpressure retry, and a chaos-harness
smoke run (ISSUE 6)."""
import threading
import time

import numpy as np
import pytest

from repro.core import rctc, rhal, rimfs
from repro.core.fleet import FleetConfig, FleetController, FleetError
from repro.serving.protocol import F_CANARY
from repro.serving.server import (Client, InferenceServer, ServerBusy,
                                  _Work)

from test_conformance import assert_batched_close

DEPTH, N = 8, 24


@pytest.fixture(scope="module")
def chain_setup():
    prog = rctc.compile_gemm_chain(DEPTH, N)
    files = rctc.gemm_chain_weights(DEPTH, N)
    return prog, files, rimfs.pack(files)


def _start(prog, image, mesh_groups=2, **kw):
    mesh = rhal.TileMesh(mesh_groups) if mesh_groups else None
    server = InferenceServer(mesh=mesh, **kw)
    addr = server.start()
    client = Client(addr)
    client.provision(image, prog.encode())
    return server, addr, client


def _x(seed=0):
    return np.random.RandomState(seed).randn(N, N).astype(np.float32)


def _wedge_dispatcher(server):
    """Park the dispatcher on a gate via a control op (the deterministic
    stand-in for a drain window / long-running dispatch)."""
    gate = threading.Event()
    entered = threading.Event()

    def ctl():
        entered.set()
        gate.wait(30)

    assert server._loop.submit(_Work(frame=None, route=None, control=ctl))
    assert entered.wait(5)
    return gate


# ------------------------------------------------------------- scale cycle
def test_scale_cycle_bit_identical_and_cached_mesh(chain_setup):
    """2 -> 4 -> 8 -> 2 under pipelined traffic: every response
    bit-identical, scaling back reuses the cached original mesh and
    re-uploads zero weight bytes."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        x = _x(1)
        ref = client.infer(input=x)

        def total_dma():
            return sum(g.driver.stats.get("dma_bytes", 0)
                       for g in server.mesh.groups)

        d0 = total_dma()
        client.infer(input=x)
        per_req = total_dma() - d0      # steady per-request movement

        for n_groups in (4, 8):
            rids = [client.infer_async(input=x) for _ in range(3)]
            rep = fleet.scale_to(n_groups)
            assert server.mesh.n_groups == n_groups
            assert rep["from"] != rep["to"] == n_groups
            for rid in rids:            # in-flight across the flip: all ok
                out = client.result(rid)
                for k in ref:
                    np.testing.assert_array_equal(ref[k], out[k])

        rep = fleet.scale_to(2)
        assert rep["cached_mesh"], "original 2-mesh should be cache-hit"
        d2 = total_dma()
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        # back on the original drivers: the request cost its steady
        # per-request bytes, not a weight re-upload
        assert total_dma() - d2 == per_req
        kinds = [k for k, _ in fleet.events]
        assert kinds.count("scale_complete") == 3
    finally:
        client.close()
        server.stop()


def test_autoscaler_decides_up_on_real_backlog(chain_setup):
    """Queue depth from a wedged dispatcher drives the observe->decide
    loop up the ladder after the hysteresis streak; the backlog then
    drains without a single dropped request."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server, FleetConfig(scale_up_depth=6,
                                                    scale_up_ticks=2))
        x = _x(2)
        ref = client.infer(input=x)
        gate = _wedge_dispatcher(server)
        try:
            rids = [client.infer_async(input=x) for _ in range(8)]
            deadline = time.monotonic() + 5     # enqueue is async: wait
            while server.scheduler.pending() < 8:   # for the backlog to
                assert time.monotonic() < deadline  # actually land
                time.sleep(0.005)
            a1 = fleet.decide(fleet.observe())
            a2 = fleet.decide(fleet.observe())
            assert a1 is None                 # streak not yet reached
            assert a2 == ("scale", 4)         # second tick over threshold
        finally:
            gate.set()
        for rid in rids:
            out = client.result(rid)
            for k in ref:
                np.testing.assert_array_equal(ref[k], out[k])
        obs = fleet.observe()                 # drained: pressure gone
        assert fleet.decide(obs) is None and fleet._up_streak == 0
    finally:
        client.close()
        server.stop()


def test_single_dead_group_partial_reshape_zero_survivor_bytes(chain_setup):
    """One dead group in a multi-group mesh is spliced out by a partial
    reshape: the mesh OBJECT survives, only the replaced slot's driver
    changes, and the surviving groups' DMA counters move zero bytes
    during the repair (their residency is never touched)."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=4)
    try:
        fleet = FleetController(server)
        x = _x(3)
        ref = client.infer(input=x)
        mesh = server.mesh
        survivors = {g: mesh.group(g).driver for g in mesh.gids if g != 2}
        dma_before = {g: d.stats.get("dma_bytes", 0)
                      for g, d in survivors.items()}
        old_driver = mesh.group(2).driver
        mesh.kill(2)
        rep = fleet.tick()
        assert rep["action"] == ("replace", 2, "dead")
        assert "error" not in rep
        assert server.mesh is mesh              # same mesh, spliced slot
        assert mesh.group(2).driver is not old_driver
        for g, d in survivors.items():          # survivors untouched
            assert mesh.group(g).driver is d
            assert d.stats.get("dma_bytes", 0) == dma_before[g], \
                f"group {g} moved bytes during a partial reshape"
        assert all(mesh.alive(g) for g in mesh.gids)
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        kinds = [k for k, _ in fleet.events]
        assert "reshape_started" in kinds and "reshape_complete" in kinds
        assert "heal_complete" not in kinds
    finally:
        client.close()
        server.stop()


def test_multi_dead_groups_fall_back_to_full_heal(chain_setup):
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=4)
    try:
        fleet = FleetController(server)
        x = _x(3)
        ref = client.infer(input=x)
        doomed = server.mesh
        server.mesh.kill(1)
        server.mesh.kill(2)
        rep = fleet.tick()
        assert rep["action"] == ("heal", (1, 2))
        assert "error" not in rep
        assert server.mesh is not doomed
        assert all(server.mesh.alive(g) for g in server.mesh.gids)
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        kinds = [k for k, _ in fleet.events]
        assert "heal_started" in kinds and "heal_complete" in kinds
    finally:
        client.close()
        server.stop()


# ---------------------------------------------------------------- hot swap
def test_hot_swap_commits_and_stays_bit_identical(chain_setup):
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        x = _x(4)
        ref = client.infer(input=x)
        old_bound = server._bound
        assert fleet.swap_weights(rimfs.pack(files),
                                  label="repack") == "committed"
        assert server._bound is not old_bound
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        kinds = [k for k, _ in fleet.events]
        assert kinds[-3:] == ["swap_started", "swap_probed",
                              "swap_committed"]
        # probation is REQUEST-count gated: serve enough traffic on the
        # new binding, then the tick floor finalizes it
        for i in range(fleet.cfg.probation_requests):
            client.infer(input=_x(40 + i))
        for _ in range(fleet.cfg.probation_ticks + 1):
            fleet.tick()
        assert not fleet.summary()["swap_in_probation"]
        assert "swap_finalized" in [k for k, _ in fleet.events]
    finally:
        client.close()
        server.stop()


def test_zero_traffic_probation_never_auto_commits(chain_setup):
    """Satellite regression: the swap probation window counts SERVED
    REQUESTS, not wall-clock ticks — an idle fleet can spin the control
    loop forever without the swap silently finalizing (the old image's
    residency stays pinned, so rollback remains a zero-byte flip)."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        client.infer(input=_x(4))
        assert fleet.swap_weights(rimfs.pack(files),
                                  label="idle") == "committed"
        # many times the tick floor, zero traffic: still in probation
        for _ in range(fleet.cfg.probation_ticks * 5):
            rep = fleet.tick()
        assert rep["swap"]["state"] == "probation"
        assert rep["swap"]["served"] == 0
        assert fleet.summary()["swap_in_probation"]
        assert "swap_finalized" not in [k for k, _ in fleet.events]
        # rollback after the idle stretch is still possible and clean
        fleet.rollback(reason="test")
        assert not fleet.summary()["swap_in_probation"]
    finally:
        client.close()
        server.stop()


def test_bad_swap_detected_by_probe_and_rolled_back(chain_setup):
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        x = _x(5)
        ref = client.infer(input=x)
        old_bound, old_fs = server._bound, server.platform.rimfs
        wrong = rctc.gemm_chain_weights(DEPTH, N, seed=123)
        assert fleet.swap_weights(rimfs.pack(wrong),
                                  label="wrong") == "rolled_back"
        # old binding still serving, bit-identically
        assert server._bound is old_bound
        assert server.platform.rimfs is old_fs
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        probed = [p for k, p in fleet.events if k == "swap_probed"]
        assert probed and probed[-1]["ok"] is False
        # a corrupt image never reaches the probe: mount refuses it
        broken = bytearray(rimfs.pack(files))
        broken[-2] ^= 0xFF
        assert fleet.swap_weights(bytes(broken),
                                  label="corrupt") == "rolled_back"
        reasons = [p["reason"] for k, p in fleet.events
                   if k == "swap_rolled_back"]
        assert any(r.startswith("mount:") for r in reasons)
    finally:
        client.close()
        server.stop()


def test_post_swap_miss_spike_triggers_auto_rollback(chain_setup):
    """A committed swap under probation rolls back automatically when
    the deadline-miss (shed) rate spikes; the old binding resumes with
    zero re-upload (its residency was never unpinned)."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server, FleetConfig(miss_spike=0.25,
                                                    spike_min_window=4))
        x = _x(6)
        ref = client.infer(input=x)
        old_bound = server._bound
        assert fleet.swap_weights(rimfs.pack(files),
                                  label="regressing") == "committed"
        server.scheduler.shed_count += 10      # simulated miss spike
        rep = fleet.tick()
        assert rep["swap"]["state"] == "rolled_back"
        assert server._bound is old_bound
        d0 = sum(g.driver.stats.get("dma_bytes", 0)
                 for g in server.mesh.groups)
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        # the post-rollback request moved activations only — the old
        # image's tile residency survived probation untouched, so the
        # weight bytes (len(image) scale) never re-uploaded
        moved = sum(g.driver.stats.get("dma_bytes", 0)
                    for g in server.mesh.groups) - d0
        assert moved < len(image) / 2
        reasons = [p["reason"] for k, p in fleet.events
                   if k == "swap_rolled_back"]
        assert any(r.startswith("miss_spike") for r in reasons)
    finally:
        client.close()
        server.stop()


# ------------------------------------------------------------------ canary
def test_canary_good_image_auto_promotes_bit_identical(chain_setup):
    """fraction=1.0 hash-routes every request through the shadow binding;
    identical weights agree on every SPRT sample, so the controller
    auto-promotes. Agreeing shadow-served replies carry F_CANARY, and
    promotion flips the binding atomically."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        x = _x(8)
        ref = client.infer(input=x)
        old_bound = server._bound
        assert fleet.canary(rimfs.pack(files), fraction=1.0,
                            label="repack") == "started"
        assert server.canary is not None
        flagged = 0
        for _ in range(16):                 # > ~14 agrees the SPRT needs
            rid = client.infer_async(input=x)
            out, flags = client.result(rid, with_flags=True)
            for k in ref:
                np.testing.assert_array_equal(ref[k], out[k])
            if flags & F_CANARY:
                flagged += 1
        assert flagged == 16                # fraction 1.0: all shadow-served
        rep = fleet.tick()
        assert rep["canary"]["state"] == "promote"
        assert server.canary is None and fleet._canary is None
        assert server._bound is not old_bound
        promoted = [p for k, p in fleet.events if k == "canary_promoted"]
        assert promoted and promoted[-1]["disagrees"] == 0
        assert promoted[-1]["stats"]["served_shadow"] == 16
        out = client.infer(input=x)         # promoted binding serves on
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
    finally:
        client.close()
        server.stop()


def test_canary_bad_image_serves_zero_wrong_bytes_then_aborts(chain_setup):
    """A broken canary NEVER serves a byte it is known to have gotten
    wrong: every sampled request that disagrees is answered with the
    primary's bytes (no F_CANARY flag), and the SPRT aborts the rollout
    after min_samples. The primary binding is untouched throughout."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server)
        x = _x(9)
        ref = client.infer(input=x)
        old_bound, old_fs = server._bound, server.platform.rimfs
        wrong = rctc.gemm_chain_weights(DEPTH, N, seed=321)
        assert fleet.canary(rimfs.pack(wrong), fraction=1.0,
                            label="bad") == "started"
        for _ in range(6):
            rid = client.infer_async(input=x)
            out, flags = client.result(rid, with_flags=True)
            assert not (flags & F_CANARY)   # never the shadow's bytes
            for k in ref:                   # always the primary's answer
                np.testing.assert_array_equal(ref[k], out[k])
        rep = fleet.tick()
        assert rep["canary"]["state"] == "abort"
        assert server.canary is None and fleet._canary is None
        assert server._bound is old_bound
        assert server.platform.rimfs is old_fs
        aborted = [p for k, p in fleet.events if k == "canary_aborted"]
        assert aborted and aborted[-1]["reason"] == "sprt"
        assert aborted[-1]["stats"]["served_shadow"] == 0
        assert aborted[-1]["stats"]["disagree"] >= \
            fleet.cfg.canary_min_samples
        out = client.infer(input=x)         # primary serves on, untouched
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
    finally:
        client.close()
        server.stop()


def test_stage_ewma_straggler_replaced_in_place(chain_setup):
    """A group whose stage-busy EWMA sits far above its peer's median for
    straggler_ticks consecutive control-loop ticks is spliced out by a
    partial reshape — the fast peer's driver (and its pinned weights)
    are never touched."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=2)
    try:
        fleet = FleetController(server, FleetConfig(
            straggler_ticks=2, stage_straggler_ratio=2.0))
        x = _x(10)
        ref = client.infer(input=x)
        mesh = server.mesh
        old_slow = mesh.group(1).driver
        fast = mesh.group(0).driver
        # slot 1's stage-busy rhythm sits 25x above its peer's
        fleet._stage_ewma = {0: 0.01, 1: 0.25}
        r1 = fleet.tick()
        assert r1["action"] is None          # hysteresis: streak 1 of 2
        r2 = fleet.tick()
        assert r2["action"] == ("replace", 1, "straggler")
        assert "error" not in r2
        assert server.mesh is mesh           # same mesh, spliced slot
        assert mesh.group(1).driver is not old_slow
        assert mesh.group(0).driver is fast
        assert 1 not in fleet._stage_ewma    # fresh slot: rhythm reset
        out = client.infer(input=x)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k])
        started = [p for k, p in fleet.events if k == "reshape_started"]
        assert started and started[-1]["reason"] == "straggler"
        assert "reshape_complete" in [k for k, _ in fleet.events]
    finally:
        client.close()
        server.stop()


# ------------------------------------------------- RIMFS residency (swap)
def test_shadow_image_residency_no_evict_no_alias_zero_byte_rollback(rng):
    """Satellite: pinning a second weight image while the first is live
    must not evict, move or alias the first image's arena ranges; after
    rolling the shadow back, re-binding the original moves zero bytes."""
    drv = rhal.make_eager_driver()
    files_a = {f"w{i}": rng.randn(16, 16).astype(np.float32)
               for i in range(4)}
    files_b = {f"w{i}": rng.randn(16, 16).astype(np.float32)
               for i in range(4)}
    fs_a = rimfs.mount(rimfs.pack(files_a))
    fs_b = rimfs.mount(rimfs.pack(files_b))

    ra = fs_a.resident(drv)
    ranges_a = ra.pinned_ranges()
    live_a = {n: np.asarray(ra[n]) for n in ra.files()}

    rb = fs_b.resident(drv)                    # the shadow pin
    assert ra.pinned_ranges() == ranges_a      # nothing moved or evicted
    for o1, s1 in ranges_a:                    # no aliasing
        for o2, s2 in rb.pinned_ranges():
            assert o1 + s1 <= o2 or o2 + s2 <= o1
    for n in ra.files():                       # old bytes untouched
        np.testing.assert_array_equal(live_a[n], np.asarray(ra[n]))
        np.testing.assert_array_equal(live_a[n], files_a[n])

    rb.unpin()                                 # rollback: drop the shadow
    before = drv.stats.get("dma_bytes", 0)
    ra2 = fs_a.resident(drv)
    assert ra2 is ra                           # cache hit, same pinning
    assert drv.stats.get("dma_bytes", 0) == before   # zero bytes moved
    drv.arena.check()                          # raises on any violation


# ------------------------------------------------------------ client retry
def test_client_retry_drains_busy_burst(chain_setup):
    """Satellite regression: a burst into a wedged (drain-window-like)
    dispatcher hard-fails without retry, fully succeeds with bounded
    jittered-backoff retry enabled."""
    prog, files, image = chain_setup
    server, addr, client = _start(prog, image, mesh_groups=0, max_queue=4)
    try:
        x = _x(7)
        ref = client.infer(input=x)

        # without retry: the overflow surfaces as ServerBusy
        gate = _wedge_dispatcher(server)
        try:
            plain = Client(addr)
            rids = [plain.infer_async(input=x) for _ in range(12)]
            outcomes = []
            for rid in rids:
                try:
                    outcomes.append(plain.result(rid))
                except ServerBusy:
                    outcomes.append("busy")
        finally:
            gate.set()
        assert "busy" in outcomes
        plain.close()

        # with retry: the same burst shape fully succeeds
        gate = _wedge_dispatcher(server)
        results, errors = [], []

        def worker(cid):
            cl = Client(addr, retries=20, backoff=0.01, retry_seed=cid)
            try:
                for _ in range(6):
                    results.append((cl.infer(input=x),
                                    cl.retry_stats["busy"]))
            except Exception as e:      # pragma: no cover
                errors.append(e)
            finally:
                cl.close()

        threads = [threading.Thread(target=worker, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.15)                # let the burst hit the wedge
        gate.set()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        assert len(results) == 24
        for out, _ in results:
            for k in ref:
                # the released burst coalesces into batched dispatches
                assert_batched_close(ref[k], out[k])
        assert any(busy > 0 for _, busy in results), \
            "burst never saw backpressure — wedge did not engage"
    finally:
        client.close()
        server.stop()


# ------------------------------------------------------------ chaos smoke
def test_chaos_smoke_converges():
    """A reduced chaos scenario (the CI chaos-matrix job runs the full
    one): zero failed requests, bit-identical outputs, all swap/heal
    events present."""
    import chaos
    report = chaos.run_chaos(groups=2, seed=3, requests=24, clients=2,
                             scale_peak=4, pace_s=0.01, dma_delay_s=0.1)
    assert chaos.check_report(report) == []

"""The harness's own helpers: the pause watch and the cache clean-up."""
import gc
import time

import jax

import run as bench_run
from harness import stalls


def test_watch_records_a_collection_and_a_pause():
    w = stalls.Watch(tick_s=0.005, stall_s=0.05)
    try:
        time.sleep(0.02)                # the watch's thread has run once
        t0 = time.perf_counter()
        gc.collect()
        # one C call holds the interpreter lock throughout, as a long
        # collection does: no other thread runs until it returns
        n = 1 << 20
        while True:
            a = time.perf_counter()
            sum(range(n))
            held = time.perf_counter() - a
            if held > 0.2:
                break
            n *= 2
        time.sleep(0.05)
        t1 = time.perf_counter()
    finally:
        w.stop()
    assert any(g == 2 and t0 <= s < t1 for g, s, _ in w.collections)
    assert any(d > 0.1 and a - 0.05 <= s < t1 for s, d in w.stalls)
    assert "pauses over 50 ms, longest" in w.summary(t0, t1)


def test_prune_cache_removes_entries_without_access_time(tmp_path):
    (tmp_path / "jit_a-1-cache").write_bytes(b"x")
    (tmp_path / "jit_a-1-atime").write_bytes(b"t")
    (tmp_path / "jit_b-2-cache").write_bytes(b"y")     # left by a kill
    before = jax.config.jax_compilation_cache_max_size
    try:
        jax.config.update("jax_compilation_cache_max_size", -1)
        assert bench_run.prune_cache(tmp_path) == 0     # no eviction: kept
        jax.config.update("jax_compilation_cache_max_size", 1 << 30)
        assert bench_run.prune_cache(tmp_path) == 1
    finally:
        jax.config.update("jax_compilation_cache_max_size", before)
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "jit_a-1-atime", "jit_a-1-cache"]


def test_watch_dumps_stacks_on_a_long_pause(capfd):
    w = stalls.Watch(tick_s=0.005, stall_s=0.05, dump_s=0.1)
    try:
        time.sleep(0.02)
        n = 1 << 20
        while True:
            a = time.perf_counter()
            sum(range(n))
            if time.perf_counter() - a > 0.3:
                break
            n *= 2
    finally:
        w.stop()
    err = capfd.readouterr().err
    assert "Thread 0x" in err and "test_watch_dumps_stacks" in err


def test_send_frame_writes_the_programs_frame():
    import socket
    import threading

    from harness import load
    from repro.serving import protocol as proto
    body = bytes(range(256)) * 9999
    want = proto.encode_frame(proto.Msg.INFER_REQUEST, body,
                              request_id=123456)
    a, b = socket.socketpair()
    got = []

    def read():
        n = 0
        while n < len(want):
            got.append(b.recv(1 << 20))
            n += len(got[-1])

    reader = threading.Thread(target=read)
    reader.start()
    try:
        load.send_frame(a, proto.Msg.INFER_REQUEST, body, 123456)
        reader.join(timeout=30)
    finally:
        a.close()
        b.close()
    assert b"".join(got) == want

"""The readers of the program's span ring (``harness/program_spans.py`` and
the five metrics over it), by hand on a made-up ring and trace; and the
trace reducers they sit beside, pinned on the recorded v5e trace."""
from types import SimpleNamespace

import numpy as np
import pytest

from harness import program_spans, trace
from harness.load import Record
from harness.registry import BENCH, load_module
from repro.core.tracing import Span

DEV = "/device:TPU:0"
MS = 1_000_000
OFF = -9_000_000_000            # profiler ns = program ns + OFF
W0, W1 = 10_000_000_000, 20_000_000_000     # the window, program ns
METRICS = ("ingress_p95_ms", "queue_wait_p95_ms", "solo_issue_ms",
           "server_p95_ms", "held_idle")


def _req(ids, req, t, recv, unpack, wait):
    """The first three stages of one request from ``t``, their lengths in
    ms. Returns (spans, the time they end)."""
    out, a = [], t
    for name, ms in (("aeg.recv", recv), ("aeg.unpack", unpack),
                     ("aeg.wait", wait)):
        out.append(Span(name, a, a + ms * MS, next(ids), 0, req, None))
        a += ms * MS
    return out, a


def made_up_ring():
    """Request 1 solo, 2 and 3 in one batched dispatch, 4 arrived before
    the window opened and replied inside it."""
    ids = iter(range(1000, 2000))
    spans = []
    one, a = _req(ids, 1, W0, 1, 2, 2)                # ingress 3, wait 2
    d = Span("aeg.dispatch", a, a + 20 * MS, next(ids), 0, 1,
             {"mode": "solo", "n": 1, "reqs": (1,)})
    spans += one + [
        Span("aeg.issue", a, a + 15 * MS, next(ids), d.id, 0,
             {"thunks": 52}),
        Span("aeg.readback", a + 15 * MS, a + 20 * MS, next(ids), d.id, 0,
             None), d,
        Span("aeg.reply", a + 20 * MS, a + 21 * MS, next(ids), 0, 1, None)]
    t2 = W0 + 5_000_000_000
    two, a2 = _req(ids, 2, t2, 2, 3, 4)               # ingress 5, wait 4
    three, a3 = _req(ids, 3, t2 + MS, 3, 4, 3)        # ingress 7, wait 3
    start = max(a2, a3)
    b = Span("aeg.dispatch", start, start + 8 * MS, next(ids), 0, 0,
             {"mode": "batched", "n": 2, "reqs": (2, 3)})
    spans += two + three + [
        Span("aeg.issue", start, start + 2 * MS, next(ids), b.id, 0,
             {"thunks": 1}), b,
        Span("aeg.reply", b.end_ns, b.end_ns + MS, next(ids), 0, 2, None),
        Span("aeg.reply", b.end_ns + MS, b.end_ns + 2 * MS, next(ids), 0, 3,
             None)]
    four, a4 = _req(ids, 4, W0 - 2 * MS, 1, 1, 1)
    spans += four + [Span("aeg.reply", a4, a4 + MS, next(ids), 0, 4, None)]
    return spans


def made_up_run(sends: int = 60, jitter=(-300, 0, 300)):
    """The records and trace of the ring above: ``sends`` sends one per
    10 ms from W0 + 0.5 s, their ``bench.send`` spans on the profiler's
    clock with a small jitter; the device busy through the window but for
    a 10 ms gap inside request 1 and a 30 ms gap outside every request."""
    recs = []
    spans = [["bench.window", W0 + OFF, W1 - W0]]
    for i in range(sends):
        t = W0 + 500 * MS + 10 * MS * i
        recs.append(Record(spec=None, due=t / 1e9, sent=t / 1e9))
        spans.append(["bench.send", t + OFF + jitter[i % len(jitter)], 50])
    g1 = (W0 + 10 * MS, W0 + 20 * MS)
    g2 = (W0 + 6_000 * MS, W0 + 6_030 * MS)
    busy = [(W0, g1[0]), (g1[1], g2[0]), (g2[1], W1)]
    ops = [[DEV, "op", a + OFF, b - a] for a, b in busy]
    flat = {"ops": ops, "modules": [], "spans": spans}
    return SimpleNamespace(window=(W0 / 1e9, W1 / 1e9), flat=flat,
                           all_records=recs, records=recs)


@pytest.fixture
def ring(monkeypatch):
    spans = made_up_ring()
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    return spans


def _read(name, run):
    return load_module(BENCH / "metrics" /
                       f"{name}.resnet_steady.py").read(run)


def test_requests_in_the_window(ring):
    reqs = program_spans.of_run(made_up_run())
    assert [r.req for r in reqs] == [1, 2, 3]
    assert reqs[0].dispatch.stats["mode"] == "solo"
    assert {c.name for c in reqs[0].children} == {"aeg.issue",
                                                  "aeg.readback"}
    assert reqs[1].dispatch is reqs[2].dispatch


def test_metrics_by_hand(ring):
    run = made_up_run()
    pct = lambda xs: float(np.percentile(xs, 95))        # noqa: E731
    assert _read("ingress_p95_ms", run) == pytest.approx(pct([3, 5, 7]))
    assert _read("queue_wait_p95_ms", run) == pytest.approx(pct([2, 4, 3]))
    assert _read("solo_issue_ms", run) == pytest.approx(15.0)
    # request 1: 1 + 2 + 2 ms, then 20 of dispatch and 1 of reply; the
    # batch starts once request 3 (1 ms later, 10 ms of stages) is in, at
    # 11 ms, runs 8, and replies to 2 then 3 a millisecond each
    assert _read("server_p95_ms", run) == pytest.approx(pct([26, 20, 20]))
    # 10 of the 40 idle ms lie inside request 1
    assert _read("held_idle", run) == pytest.approx(25.0)


def test_fit_recovers_the_offset():
    f = program_spans.fit(made_up_run().flat, made_up_run().all_records)
    assert f is not None
    assert abs(f[0] - OFF) <= 1000 and f[1] == 300


def test_fit_refuses_unmatched_or_too_few_sends():
    run = made_up_run()
    run.flat["spans"] = [s for i, s in enumerate(run.flat["spans"])
                         if s[0] != "bench.send" or i % 2]
    assert program_spans.fit(run.flat, run.all_records) is None
    few = made_up_run(sends=program_spans.MIN_SENDS - 1)
    assert program_spans.fit(few.flat, few.all_records) is None


def test_idle_gap_outside_every_request_is_not_held(ring, monkeypatch):
    run = made_up_run()
    # without request 1, neither idle gap lies inside a request
    spans = [s for s in ring if s.req != 1]
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    assert _read("held_idle", run) == 0.0


def test_no_ring_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    run = made_up_run()
    assert [_read(m, run) for m in METRICS] == [None] * len(METRICS)


def test_recorded_trace_reducers_unchanged():
    """What the existing reducers read on the recorded v5e trace."""
    f = trace.load(str(BENCH / "data" / "resnet18_steady_trace.json.gz"))
    assert trace.window(f) == (52722485, 6052818467)
    assert trace.busy_ns(f) == 35942006.0
    assert trace.module_count(f, r"^jit__lambda") == 256
    assert trace.module_count(f, r"^jit_staged") == 20
    assert trace.module_ns(f, r"^jit_staged") == 31673171.0
    assert trace.op_ns(f, lambda n: True) == 35942006.0
    conv = load_module(BENCH / "metrics" / "conv_roofline.resnet_bulk.py")
    assert trace.op_ns(f, conv.is_conv) == 30394247.0
    assert [s for _, s in trace.top_ops(f, 3)] == \
        [0.005317546, 0.005189514, 0.004361096]
    assert trace.idle_gaps(f, 3) == [["unattributed", 0.763206331],
                                     ["unattributed", 0.322234265],
                                     ["unattributed", 0.282709375]]

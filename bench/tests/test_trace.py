"""The reduction from a profiler trace to device times: by hand on a
made-up trace, and against a brute-force timeline on a trace recorded on a
TPU v5e (``bench/data/resnet18_steady_trace.json.gz``: 6 s of the
``resnet18.steady`` cell, flattened by ``harness.trace.flatten``)."""
import numpy as np
import pytest

from harness import trace
from harness.registry import BENCH, load_module

DEV = "/device:TPU:0"


def made_up():
    return {"ops": [[DEV, "a", 0, 10], [DEV, "b", 5, 15],
                    [DEV, "a", 30, 10], [DEV, "c", 45, 20]],
            "modules": [[DEV, "jit_x(1)", 0, 20],
                        [DEV, "jit_y(2)", 30, 35]],
            "spans": [["bench.window", 0, 50], ["bench.send", 22, 6],
                      ["bench.recv", 21, 30]]}


def test_by_hand():
    f = made_up()
    assert trace.window(f) == (0, 50)
    # ops cover [0, 20), [30, 40) and [45, 50) of the window: 35 ns
    assert trace.busy_ns(f) == 35
    assert trace.module_ns(f, r"jit_x") == 20
    assert trace.module_ns(f, r"jit_y") == 20          # cut at the close
    assert trace.module_count(f, r"jit_") == 2
    assert trace.op_ns(f, lambda n: n == "a") == 20
    assert trace.top_ops(f) == [["a", 20e-9], ["b", 15e-9], ["c", 5e-9]]
    # gaps [20, 30) and [40, 45): the first one's middle lies in both
    # spans, and the shorter one names it
    assert trace.idle_gaps(f) == [["bench.send", 10e-9],
                                  ["bench.recv", 5e-9]]


def test_no_window_reads_nothing():
    f = made_up()
    f["spans"] = []
    assert trace.busy_ns(f) is None and trace.idle_gaps(f) == []
    assert trace.module_ns(f, "jit") == 0.0


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(BENCH / "data" / "resnet18_steady_trace.json.gz"))


def test_recorded_busy_matches_a_timeline(recorded):
    lo, hi = trace.window(recorded)
    us = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, _, s, d in recorded["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            us[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    busy = trace.busy_ns(recorded)
    # a microsecond timeline rounds each interval out by < 2 us
    assert abs(us.sum() * 1000 - busy) < 2000 * len(recorded["ops"])
    assert 0 < busy < hi - lo
    gaps = trace.idle_gaps(recorded, k=10 ** 6)
    assert sum(g for _, g in gaps) * 1e9 == pytest.approx(hi - lo - busy,
                                                          abs=10)


def test_recorded_programs_and_convs(recorded):
    # the solo path runs one program per linked op, the batched one a
    # single staged program per dispatch
    assert trace.module_count(recorded, r"^jit__lambda") > 100
    assert trace.module_count(recorded, r"^jit_staged") > 0
    conv = load_module(BENCH / "metrics" / "conv_roofline.resnet_bulk.py")
    t_conv = trace.op_ns(recorded, conv.is_conv)
    t_all = trace.op_ns(recorded, lambda n: True)
    assert 0 < t_conv < t_all
    top = trace.top_ops(recorded)
    assert len(top) == 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)

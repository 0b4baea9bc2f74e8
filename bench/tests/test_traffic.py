"""The traffic generator: seeded, the same work for every seed, and the
lengths clipped and laddered as the mix says."""
import numpy as np

from harness import traffic

CHAT = {"loop": "open", "arrival": "poisson", "rate_per_s": 10.0,
        "connections": 8, "ramp_s": 1.0,
        "request": {"prompt": {"dist": "lognormal", "median": 256,
                               "sigma": 1.0, "min": 64, "max": 1024,
                               "ladder": [64, 128, 256, 512, 1024]},
                    "output": {"dist": "lognormal", "median": 128,
                               "sigma": 0.8, "min": 16, "max": 512}}}
BIG = 2 ** 31 + 12345


def _rows(specs):
    return [(s.due, s.conn, s.prompt_len, s.max_new) for s in specs]


def test_same_seed_same_schedule_and_tokens():
    a = traffic.schedule(CHAT, BIG, 60)
    b = traffic.schedule(CHAT, BIG, 60)
    assert _rows(a) == _rows(b)
    np.testing.assert_array_equal(traffic.prompt_tokens(BIG, 7, 64, 151936),
                                  traffic.prompt_tokens(BIG, 7, 64, 151936))
    assert not np.array_equal(traffic.prompt_tokens(BIG, 7, 64, 151936),
                              traffic.prompt_tokens(BIG + 1, 7, 64, 151936))


def test_seeds_share_the_work_in_another_order():
    a = traffic.schedule(CHAT, 1, 60)
    b = traffic.schedule(CHAT, BIG, 60)
    assert _rows(a) != _rows(b)
    for key in ("prompt_len", "max_new"):
        assert sorted(getattr(s, key) for s in a) == \
            sorted(getattr(s, key) for s in b)
    gaps = lambda s: sorted(np.round(np.diff([x.due for x in s]), 9))
    assert len(a) == len(b) == 600
    # the same gaps but one, which starts the schedule at 0
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2


def test_ladder_and_clipping():
    s = traffic.schedule(CHAT, 3, 100)
    plens = {x.prompt_len for x in s}
    assert plens == {64, 128, 256, 512, 1024}
    news = [x.max_new for x in s]
    assert min(news) == 16 and max(news) == 512
    assert traffic.lengths_reachable(CHAT["request"]["prompt"]) == \
        [64, 128, 256, 512, 1024]
    # median of the output grid is the mix's median
    assert abs(np.median(news) - 128) <= 1


def test_poisson_rate_and_connections():
    s = traffic.schedule(CHAT, 5, 100)
    assert len(s) == 1000
    assert abs(s[-1].due - 100) < 5        # about n / rate seconds long
    assert [x.conn for x in s[:9]] == [0, 1, 2, 3, 4, 5, 6, 7, 0]


def test_closed_loop_has_no_due_times():
    s = traffic.schedule({"loop": "closed", "connections": 8, "pipeline": 4,
                          "ramp_s": 1, "request": {"images": 4}}, 9, 30)
    assert all(x.due is None for x in s) and len(s) == 4096

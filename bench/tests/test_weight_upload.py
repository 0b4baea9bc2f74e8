"""The ``weight_upload_mb_per_req`` reader, by hand on the made-up span ring
of ``test_program_spans``: one solo dispatch and one batched dispatch of two
requests inside the window."""
import pytest

from harness import program_spans
from test_program_spans import _read, made_up_ring, made_up_run

METRIC = "weight_upload_mb_per_req"


def _ring_with(monkeypatch, spans):
    monkeypatch.setattr(program_spans, "ring", lambda: spans)


@pytest.mark.parametrize("nbytes", [46_760_000, 0])
def test_weight_upload_counts_each_dispatch_once(monkeypatch, nbytes):
    # the solo dispatch and the batched one (two requests) each copy
    # ``nbytes`` once: two uploads over the window's three requests
    _ring_with(monkeypatch, [
        s._replace(stats={**s.stats, "weight_h2d_bytes": nbytes})
        if s.name == "aeg.issue" else s for s in made_up_ring()])
    got = _read(METRIC, made_up_run())
    assert got == pytest.approx(2 * nbytes / 3 / 1e6)


def test_weight_upload_reads_nothing_without_the_count(monkeypatch):
    # issue spans of a program that does not stamp the count
    _ring_with(monkeypatch, made_up_ring())
    assert _read(METRIC, made_up_run()) is None


def test_weight_upload_reads_nothing_without_a_ring(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert _read(METRIC, made_up_run()) is None

"""INT8 quantization properties (hypothesis-driven)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.quant import per_channel_scales, quantize_weight


@given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 8),
       st.floats(0.01, 100.0))
@settings(max_examples=60, deadline=None)
def test_quant_error_bounded_by_half_step(kh, kw, cout, magnitude):
    rng = np.random.RandomState(kh * 31 + kw * 7 + cout)
    w = (rng.randn(kh, kw, 3, cout) * magnitude).astype(np.float32)
    s = per_channel_scales(w)
    q = quantize_weight(w, s)
    deq = q.astype(np.float32) * s.reshape(1, 1, 1, -1)
    # symmetric PTQ: |w - deq| <= scale/2 per channel (no clipping occurs
    # because scale = amax/127)
    err = np.abs(w - deq)
    bound = s.reshape(1, 1, 1, -1) / 2 + 1e-7
    assert np.all(err <= bound)


@given(st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_scales_positive_and_cover_amax(cout):
    rng = np.random.RandomState(cout)
    w = rng.randn(3, 3, 2, cout).astype(np.float32)
    s = per_channel_scales(w)
    assert np.all(s > 0)
    q = quantize_weight(w, s)
    assert q.dtype == np.int8
    assert np.all(np.abs(q) <= 127)


def test_zero_weight_channel_safe():
    w = np.zeros((3, 3, 2, 4), np.float32)
    s = per_channel_scales(w)
    q = quantize_weight(w, s)
    assert np.all(q == 0) and np.all(np.isfinite(s))

"""Prefill's share of the engine's device time in the traced window: the
paged prefill program over prefill plus paged decode programs."""
from harness import trace


PREFILL, DECODE = r"paged_prefill_step", r"paged_decode_step"


def read(run):
    if run.flat is None:
        return None
    p = trace.module_ns(run.flat, PREFILL)
    d = trace.module_ns(run.flat, DECODE)
    return 100.0 * p / (p + d) if p + d > 0 else None

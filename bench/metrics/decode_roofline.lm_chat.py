"""Decode's share of its bandwidth roofline: for every decode step the
engine dispatched in the traced window, all the weights plus the live
lanes' cached keys and values, at HBM bandwidth, over the device time of
the decode programs."""
from harness import flops, trace


DECODE = r"paged_decode_step"


def read(run):
    if run.flat is None or run.trace_span is None:
        return None
    lo, hi = run.trace_span
    f, cfg = flops, run.cfg
    wts, kv = f.lm_param_bytes(cfg), f.lm_kv_bytes_per_token(cfg)
    need = 0.0
    for kind, t, live, window, ctx in (e for e in run.log
                                       if e[0] == "decode"):
        if lo <= t < hi and live:
            need += window * wts + kv * (window * ctx
                                         + live * window * (window + 1) / 2)
    t_dev = trace.module_ns(run.flat, DECODE) / 1e9
    if not need or t_dev <= 0:
        return None
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / t_dev

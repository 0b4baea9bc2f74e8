"""The whole model's share of the chip's peak: FLOPs of the prompt tokens
prefilled and the tokens decoded in the traced window (matmuls and causal
attention), over the window times peak FLOP/s."""
from harness import flops


def read(run):
    if run.trace_span is None:
        return None
    lo, hi = run.trace_span
    f, cfg = flops, run.cfg
    total = 0.0
    for e in run.log:
        if not lo <= e[1] < hi:
            continue
        if e[0] == "prefill":
            total += e[3] * f.lm_prefill_flops(cfg, e[2])
        elif e[2]:
            _, _, live, window, ctx = e
            total += f.lm_decode_flops(
                cfg, window * ctx + live * window * (window + 1) / 2,
                live * window)
    if not total:
        return None
    return 100.0 * total / ((hi - lo) * run.peak["bf16_flops_per_s"])

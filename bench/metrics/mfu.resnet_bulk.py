"""The whole model's share of the chip's peak: ResNet FLOPs of the images
replied to in the traced window, over the window times peak FLOP/s.

The peak is the chip's published bfloat16 rate. A configuration served in
float32 at ``"highest"`` precision has each product computed as six
bfloat16 passes, so its share cannot come near 100%: about 1/6 (16.7%) is
the ceiling there, 1/3 at ``"high"``."""
from harness import flops
from harness.stats import done_in


def read(run):
    if run.trace_span is None:
        return None
    imgs = sum(r.units for r in done_in(run.all_records, *run.trace_span))
    t = run.trace_span[1] - run.trace_span[0]
    if not imgs or t <= 0:
        return None
    return 100.0 * flops.resnet_flops(run.cfg) * imgs \
        / (t * run.peak["bf16_flops_per_s"])

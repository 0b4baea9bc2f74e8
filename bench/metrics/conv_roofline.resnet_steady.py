"""Convolutions' share of their roofline: the least time the chip could
take for the convolutions of the images replied to in the traced window
(FLOPs at peak, or bytes at HBM bandwidth, whichever is longer; a weight
read once per dispatch) over the device time of the convolution ops.

The peak is the chip's published bfloat16 rate. A configuration served in
float32 at ``"highest"`` precision has each product computed as six
bfloat16 passes, so its share cannot come near 100%: about 1/6 (16.7%) is
the ceiling there, 1/3 at ``"high"``."""
from harness import flops, trace
from harness.stats import delta, done_in


def is_conv(name: str) -> bool:
    """A device op whose HLO is a convolution, or a fusion rooted at one
    (TPU output fusions, ``kind=kOutput``, and ``kind=kConv``)."""
    head = name.split(", calls=")[0]
    return (" convolution(" in head or "kind=kConv" in head
            or ("fusion(" in head and "kind=kOutput" in head))


def read(run):
    if run.flat is None or run.trace_span is None:
        return None
    imgs = sum(r.units for r in done_in(run.all_records, *run.trace_span))
    solo = delta(run, "processed", "trace")
    batched = delta(run, "batched_requests", "trace")
    disp = delta(run, "batched_dispatches", "trace")
    t_dev = trace.op_ns(run.flat, is_conv) / 1e9
    if not imgs or t_dev <= 0 or None in (solo, batched, disp):
        return None
    dispatches = max(1, solo - batched + disp)
    f = flops
    t_min = max(f.resnet_conv_flops(run.cfg) * imgs
                / run.peak["bf16_flops_per_s"],
                f.resnet_conv_bytes(run.cfg, imgs, dispatches)
                / run.peak["hbm_bytes_per_s"])
    return 100.0 * t_min / t_dev

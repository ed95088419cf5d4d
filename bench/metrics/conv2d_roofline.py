"""conv2d's share of its roofline: the least time every convolution of the
window could take on the chip (the larger of its FLOPs over the peak rate
and its bytes over HBM bandwidth, per convolution and call), over the
device time of the events that implement conv2d.  At ResNet-34's shapes
most convolutions are bound by FLOPs; the 7x7 stem and the 1x1
projections at small batch lean on bytes."""
from bench.opcount.conv2d import conv2d


def read(ctx):
    convs, pats = ctx.work.get("convs"), ctx.ops.get("conv2d")
    if ctx.trace is None or not convs or not pats:
        return None
    t = ctx.trace.op_seconds(pats)
    if t <= 0:
        return None
    peak, bw = ctx.peak["bf16_flops_per_s"], ctx.peak["hbm_bytes_per_s"]
    least = 0.0
    for call in ctx.work["calls"]:
        per_call = 0.0
        for c in convs:
            f, b = conv2d(call["batch"], *c)
            per_call += max(f / peak, b / bw)
        least += call["count"] * per_call
    return 100.0 * least / t

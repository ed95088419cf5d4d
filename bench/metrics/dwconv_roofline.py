"""The depthwise conv kernel's share of its roofline: the least time every
depthwise convolution of the window could take on the chip (the larger of
its FLOPs over the peak rate and its bytes over HBM bandwidth, per
convolution and call; at MobileNetV1's shapes every one is bound by
bytes), over the device time of the events that implement it.  The
convolutions come from the configuration's work counter (``dwconvs`` of
its ``opcount`` file); a configuration without depthwise convolutions, or
a trace without the kernel's events, reads nothing."""
from bench.harness import load_module
from bench.opcount.dwconv import dwconv


def read(ctx):
    pats = ctx.ops.get("depthwise_conv2d")
    path = (ctx.config or {}).get("opcount")
    if ctx.trace is None or not pats or not path:
        return None
    count = load_module(path)
    dws = count.dwconvs(ctx.config) if hasattr(count, "dwconvs") else []
    t = ctx.trace.op_seconds(pats)
    if not dws or t <= 0:
        return None
    peak, bw = ctx.peak["bf16_flops_per_s"], ctx.peak["hbm_bytes_per_s"]
    k = ctx.config["depthwise_kernel"]
    least = 0.0
    for call in ctx.work["calls"]:
        per_call = 0.0
        for d in dws:
            f, b = dwconv(call["batch"], *d, k=k)
            per_call += max(f / peak, b / bw)
        least += call["count"] * per_call
    return 100.0 * least / t

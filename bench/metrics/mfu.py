"""The whole step's share of the chip's peak: the model FLOPs of the work
the traced window completed (counted from shapes by ``bench.opcount``),
over the window, over the peak bf16 rate of the chips used."""


def read(ctx):
    tr, flops = ctx.trace, ctx.work.get("model_flops")
    if tr is None or not flops or tr.window_s <= 0:
        return None
    return 100.0 * flops / (tr.window_s * ctx.peak["bf16_flops_per_s"]
                            * ctx.n_devices)

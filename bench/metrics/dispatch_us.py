"""Mean host time, in us, of one ``CompiledModel.prefill`` call in the
traced window: the ``model.prefill`` spans (clipped to the window), from
the call's entry until the jitted function returns, which is argument
handling and dispatch; the wait for the result lies outside them.  A
program without the spans reads nothing."""
from bench.metrics.decode_tick_ms import mean_ns


def read(ctx):
    ns = mean_ns(ctx.trace, "model.prefill")
    return None if ns is None else ns * 1e-3

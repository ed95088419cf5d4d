"""The layers' projections' share of their roofline: the least time every
projection matmul of the window could take, over the device time of the
events that implement matmul.

Decode ticks count their live rows (on average per tick); each is bound by
reading the weights.  Prefill counts from the engine's counters: its
calls (``serving.prefill.batches``) and their real prompt tokens
(``serving.tokens.prefill_computed``).  Per projection the least time of
the calls together is the larger of their FLOPs over the peak rate and
their bytes (the weights once per call, the tokens' rows in and out) over
HBM bandwidth; no call can be faster than its share of that, so the least
time is never counted too high."""
from bench.opcount.lm import projections
from bench.opcount.matmul import matmul


def read(ctx):
    w, pats = ctx.work, ctx.ops.get("matmul")
    if ctx.trace is None or not pats or not w.get("ticks"):
        return None
    t = ctx.trace.op_seconds(pats)
    if t <= 0:
        return None
    peak, bw = ctx.peak["bf16_flops_per_s"], ctx.peak["hbm_bytes_per_s"]
    proj = projections(ctx.config)
    rows = (w["tokens"] - w["n_requests"]) / w["ticks"]
    calls, tokens = w["prefill_calls"], w["prefill_tokens"]
    total = 0.0
    for k, n in proj:
        f, b = matmul(rows, k, n)
        total += w["ticks"] * max(f / peak, b / bw)
        if calls:
            f, b = matmul(tokens, k, n)
            b += (calls - 1) * matmul(0, k, n)[1]
            total += max(f / peak, b / bw)
    return 100.0 * ctx.config["num_hidden_layers"] * total / t

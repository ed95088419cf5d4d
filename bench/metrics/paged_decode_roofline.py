"""Paged decode attention's share of its roofline: the least time the
window's decode attention could take (the larger of its FLOPs over the peak
rate and the live keys' and values' bytes over HBM bandwidth; it is bound
by bytes), over the device time of the events that implement it.  The work
counts each decode row's live keys, not the blocks a kernel reads."""
from bench.opcount.attention import decode_attention
from bench.opcount.lm import decode_rows


def read(ctx):
    reqs, pats = ctx.work.get("requests"), ctx.ops.get("paged_decode_attention")
    if ctx.trace is None or not reqs or not pats:
        return None
    t = ctx.trace.op_seconds(pats)
    if t <= 0:
        return None
    c = ctx.config
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    f, b = decode_attention(decode_rows(reqs), H, KV, c["hidden_size"] // H)
    n = c["num_hidden_layers"]
    least = max(n * f / ctx.peak["bf16_flops_per_s"],
                n * b / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / t

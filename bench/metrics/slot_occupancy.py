"""Share of the decode slots that produced a token per tick: tokens the
decode ticks generated (``serving.tokens.generated`` less the first token
of each request, which its prefill samples) over ``serving.ticks`` times
``max_batch``."""


def read(ctx):
    w = ctx.work
    if not w.get("ticks"):
        return None
    return 100.0 * (w["tokens"] - w["n_requests"]) / (w["ticks"]
                                                      * w["max_batch"])

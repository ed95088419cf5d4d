"""Device-to-host round trips the engine made per generated token, from its
``serving.host_syncs`` and ``serving.tokens.generated`` counters summed
over the window's ``Engine.run`` calls."""


def read(ctx):
    w = ctx.work
    if not w.get("tokens"):
        return None
    return w["host_syncs"] / w["tokens"]

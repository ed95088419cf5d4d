"""Mean length, in ms, of the serving engine's decode ticks in the traced
window: the ``tick.decode`` spans that ``Engine.run`` opens, which reach
the profile's host line through the ``repro.obs`` profiler bridge, each
clipped to the window.  A program without those spans reads nothing."""


def spans(tr, name):
    """(start, end) in ns of the host events named ``name`` on the window's
    thread, clipped to the window; those outside it are dropped."""
    if tr is None:
        return []
    lo, hi = tr.window
    got = [(max(s, lo), min(e, hi)) for s, e, n in tr.host if n == name]
    return [(s, e) for s, e in got if e > s]


def mean_ns(tr, name):
    got = spans(tr, name)
    return sum(e - s for s, e in got) / len(got) if got else None


def read(ctx):
    ns = mean_ns(ctx.trace, "tick.decode")
    return None if ns is None else ns * 1e-6

"""Host time per decode tick that the chip waits for, in ms: each
``tick.decode`` span of the traced window (clipped to it) less its overlap
with the busy intervals of the first device, averaged over the ticks.  It
is the tick's host work that no device work hides: building the inputs,
sampling, committing tokens, eviction.  A program without the spans reads
nothing."""
import bisect

from bench.metrics.decode_tick_ms import spans


def read(ctx):
    tr = ctx.trace
    ticks = spans(tr, "tick.decode")
    if not ticks or not tr.devices:
        return None
    busy = tr.busy_intervals(sorted(tr.devices)[0])
    starts = [s for s, _ in busy]
    idle = 0.0
    for s, e in ticks:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        covered = 0.0
        while i < len(busy) and busy[i][0] < e:
            covered += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        idle += e - s - covered
    return idle / len(ticks) * 1e-6

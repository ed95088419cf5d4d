"""The readers of the program's spans on a small recorded trace whose
answers are known: a window of 100 us; decode ticks of 20 and 30 us inside
it, one of 20 us that the window cuts to 10 and one before it; prefill
calls of 2 and 4 us and one of 6 us cut to 3; a second host thread whose
spans do not count; and two devices, of which the first is busy for 10,
20 and 5 us of the three ticks."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import devtrace
from bench.harness import reader

DATA = Path(__file__).parent / "data"
METRICS = ("decode_tick_ms.decode", "tick_idle_ms.decode",
           "dispatch_us.online")


def _trace(name):
    from jax.profiler import ProfileData
    return devtrace.from_profile(
        ProfileData.from_text_proto((DATA / name).read_text()))


@pytest.fixture(scope="module")
def spans():
    return SimpleNamespace(trace=_trace("span_trace.pbtxt"))


@pytest.mark.parametrize("metric,want", [
    ("decode_tick_ms.decode", (20 + 30 + 10) / 3 * 1e-3),
    ("tick_idle_ms.decode", (10 + 10 + 5) / 3 * 1e-3),
    ("dispatch_us.online", (2 + 4 + 3) / 3),
])
def test_known_answers(spans, metric, want):
    assert reader(metric)(spans) == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_reads_none(metric):
    # a trace without the spans, as the program before them gives
    assert reader(metric)(SimpleNamespace(
        trace=_trace("small_trace.pbtxt"))) is None
    assert reader(metric)(SimpleNamespace(trace=None)) is None

"""Nested-span tracer with a bounded ring buffer and Chrome-trace export.

One :class:`Tracer` owns a ring buffer of finished span events.  Spans are
opened with :meth:`Tracer.span` (recorded only while the tracer is enabled;
a shared no-op span otherwise — the disabled path is a single boolean
check) or :meth:`Tracer.timed` (always wall-clocked, recorded only while
enabled — the drop-in replacement for hand-rolled ``t0 = perf_counter()``
blocks whose elapsed time feeds existing stats).  Every finished span
becomes one Chrome trace-event dict (``ph="X"`` complete event with
``name``/``cat``/``ts``/``dur``/``pid``/``tid``/``args``), so the export
loads directly in Perfetto or ``chrome://tracing``.

Profiler bridge: once :func:`bridge` is installed (``repro.flow`` installs
``jax.profiler.TraceAnnotation``), every span of every tracer also opens a
profiler annotation of the same name while a profiler session records,
whether or not the ring buffer is on — so the spans land in the device
profile, on the profiler's clock, beside the device's operations.

Thread-safety: the buffer append and tid interning are lock-protected; the
span stack is thread-local, so nesting depth is correct per thread.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import (Any, Callable, ContextManager, Deque, Dict, Iterator, List,
                    Optional)

#: One exported trace event (Chrome trace-event "complete" format).
Event = Dict[str, Any]


def _off() -> bool:
    return False


# the profiler bridge (see bridge()): whether a profiler session records,
# and the annotation a span opens in it
_profiling: Callable[[], bool] = _off
_annotation: Callable[[str], ContextManager[Any]] = contextlib.nullcontext


def bridge(is_active: Callable[[], bool],
           annotation: Callable[[str], ContextManager[Any]]) -> None:
    """Mirror spans into a profiler: while ``is_active()`` is true, each
    span that :meth:`Tracer.span` or :meth:`Tracer.timed` opens also enters
    ``annotation(name)`` and exits it when the span ends.  This package
    imports no profiler; the code that imports one installs it."""
    global _profiling, _annotation
    _profiling, _annotation = is_active, annotation


class Span:
    """One open span.  Usable as a context manager or ended explicitly via
    :meth:`end` (idempotent — the first call wins); ``set()`` attaches
    attributes at any point before the end.  ``elapsed_s`` is valid after
    the span has ended (and live-reads while it is still open).  A span
    records into the ring buffer when ``record``, and opens a profiler
    annotation when ``profiled``."""

    __slots__ = ("_tracer", "_record", "name", "cat", "attrs", "_t0",
                 "_t_end", "depth", "_ended", "_mark")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 attrs: Dict[str, Any], record: bool,
                 profiled: bool) -> None:
        self._tracer = tracer
        self._record = record
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.depth = tracer._push(self) if record else 0
        self._mark: Optional[ContextManager[Any]] = None
        if profiled:
            self._mark = _annotation(name)
            self._mark.__enter__()
        self._t0 = tracer._clock()
        self._t_end: Optional[float] = None
        self._ended = False

    # -- lifecycle -----------------------------------------------------------
    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, **attrs: Any) -> None:
        """Close the span (no-op on a second call) and record its event."""
        if self._ended:
            return
        self._ended = True
        self._t_end = self._tracer._clock()
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
        if attrs:
            self.attrs.update(attrs)
        if self._record:
            self._tracer._pop(self)
            self._tracer._emit(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end()

    # -- timing --------------------------------------------------------------
    @property
    def elapsed_s(self) -> float:
        end = self._t_end if self._t_end is not None else self._tracer._clock()
        return end - self._t0

    @property
    def elapsed_ms(self) -> float:
        return self.elapsed_s * 1e3


class _NoopSpan(Span):
    """The shared disabled-path span: every operation is a no-op and the
    elapsed time is 0.0 (callers needing wall time use ``timed()``)."""

    def __init__(self) -> None:  # no tracer, no clock reads
        pass

    def set(self, **attrs: Any) -> "Span":
        return self

    def end(self, **attrs: Any) -> None:
        return None

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    @property
    def elapsed_s(self) -> float:
        return 0.0


_NOOP = _NoopSpan()


class Tracer:
    """Bounded, thread-safe span recorder.

    * ``enabled=False`` (the default): :meth:`span` returns a shared no-op
      span after one boolean check and one ask of the profiler bridge —
      nothing is timed or stored — unless a profiler records, when the span
      is timed and annotated but not stored.
    * ``max_events`` bounds the ring buffer: the newest events win, the
      oldest are dropped (``n_dropped`` counts them).
    * ``clock`` is injectable (defaults to ``time.perf_counter``) so span
      timelines are deterministic under test.
    """

    def __init__(self, enabled: bool = False, *, max_events: int = 65536,
                 clock: Callable[[], float] = time.perf_counter,
                 pid: int = 0) -> None:
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.enabled = enabled
        self.max_events = max_events
        self.pid = pid
        self._clock = clock
        self._epoch = clock()
        self._buf: Deque[Event] = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tids: Dict[int, int] = {}      # thread ident -> small tid
        self.n_dropped = 0

    # -- span plumbing (internal) -------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def _push(self, span: Span) -> int:
        st = self._stack()
        depth = len(st)
        st.append(span)
        return depth

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:                     # out-of-order end: drop through
            st.remove(span)

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _emit(self, span: Span) -> None:
        t_end = span._t_end if span._t_end is not None else self._clock()
        ev: Event = {
            "name": span.name,
            "cat": span.cat or "span",
            "ph": "X",
            "ts": (span._t0 - self._epoch) * 1e6,
            "dur": (t_end - span._t0) * 1e6,
            "pid": self.pid,
            "tid": self._tid(),
            "args": dict(span.attrs),
            "depth": span.depth,
        }
        with self._lock:
            if len(self._buf) == self.max_events:
                self.n_dropped += 1
            self._buf.append(ev)

    # -- public API ----------------------------------------------------------
    def span(self, name: str, cat: str = "span", **attrs: Any) -> Span:
        """Open a span, recorded while enabled and annotated while a
        profiler records — or the shared no-op span when neither (the
        hot-path contract: one boolean check, one ask of the bridge, no
        clock read)."""
        profiled = _profiling()
        if not self.enabled and not profiled:
            return _NOOP
        return Span(self, name, cat, attrs, self.enabled, profiled)

    def timed(self, name: str, cat: str = "timed", **attrs: Any) -> Span:
        """Open an always-wall-clocked span, recorded only while enabled —
        the one-code-path replacement for hand-rolled stopwatch blocks:
        ``elapsed_s`` is valid whether or not tracing is on."""
        return Span(self, name, cat, attrs, self.enabled, _profiling())

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.n_dropped = 0

    def events(self) -> List[Event]:
        """Snapshot of the ring buffer, oldest first."""
        with self._lock:
            return list(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    # -- export --------------------------------------------------------------
    def to_chrome(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome trace-event JSON (``{"traceEvents": [...]}``): loads in
        Perfetto (ui.perfetto.dev) and ``chrome://tracing``.  Written to
        ``path`` when given; the document is returned either way."""
        events = sorted(self.events(), key=lambda e: (e["ts"], -e["dur"]))
        doc: Dict[str, Any] = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs",
                          "n_dropped": self.n_dropped},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
                f.write("\n")
        return doc

    def to_jsonl(self, path: str) -> None:
        """One JSON event per line (stream-appendable log form)."""
        with open(path, "w") as f:
            for ev in self.events():
                f.write(json.dumps(ev))
                f.write("\n")


def load_trace(path: str) -> List[Event]:
    """Read a trace written by :meth:`Tracer.to_chrome` (a traceEvents
    document or a bare event array) or :meth:`Tracer.to_jsonl`."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:              # JSONL: one event per line
        return [json.loads(line) for line in text.splitlines()
                if line.strip()]
    if isinstance(doc, dict):
        events = doc.get("traceEvents", [])
    else:
        events = doc
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a trace-event document")
    return events


def _iter_spans(events: List[Event]) -> Iterator[Event]:
    for ev in events:
        if ev.get("ph") == "X":
            yield ev


#: Module default: compile-side code (pass runs, flow stages, DSE candidate
#: validation, autotune microbenchmarks) times through this tracer so every
#: stopwatch in the stack is one code path; enable it to watch a compile.
TRACER = Tracer(enabled=False)

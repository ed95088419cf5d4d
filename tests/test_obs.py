"""repro.obs: tracer span semantics, ring-buffer bounding, Chrome export
schema, metrics registry typing, engine tick timelines, the profiler bridge
(spans in a JAX profile, reduced as the benchmark reduces it), and the
exactness gates (byte-identical outputs traced vs untraced and profiled vs
not, <2% disabled overhead)."""
import json
import math
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import devtrace
from bench.harness import reader
from repro import flow as rflow
from repro.configs.base import FlowConfig, ShapeConfig
from repro.launch.obs import summarize
from repro.obs import Counter, Gauge, Histogram, MetricsRegistry, Tracer
from repro.obs import trace as trace_mod
from repro.obs.trace import load_trace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import synthetic_requests


# ---------------------------------------------------------------------------
# tracer unit tests
# ---------------------------------------------------------------------------

def test_span_nesting_and_attributes():
    clock = iter(float(i) for i in range(100))
    tr = Tracer(enabled=True, clock=lambda: next(clock))
    with tr.span("outer", cat="a", x=1) as outer:
        with tr.span("inner", cat="b") as inner:
            inner.set(y=2)
        outer.set(z=3)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # inner ends first
    inner_ev, outer_ev = evs
    assert inner_ev["depth"] == 1 and outer_ev["depth"] == 0
    assert outer_ev["args"] == {"x": 1, "z": 3}
    assert inner_ev["args"] == {"y": 2}
    # deterministic clock: outer spans [t=1, t=4), inner [t=2, t=3)
    assert outer_ev["dur"] == pytest.approx(3e6)
    assert inner_ev["dur"] == pytest.approx(1e6)
    assert inner_ev["ts"] >= outer_ev["ts"]


def test_span_end_idempotent_and_kwargs():
    tr = Tracer(enabled=True)
    sp = tr.span("s", k=1)
    sp.end(done=True)
    sp.end(done=False)       # second end is a no-op
    (ev,) = tr.events()
    assert ev["args"] == {"k": 1, "done": True}


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("a"):
        pass
    sp = tr.span("b")
    sp.end()
    assert len(tr) == 0
    # span() returns the shared no-op instance on the disabled path
    assert tr.span("c") is tr.span("d")


def test_timed_measures_even_when_disabled():
    tr = Tracer(enabled=False)
    sp = tr.timed("work")
    time.sleep(0.002)
    sp.end()
    assert sp.elapsed_s > 0
    assert len(tr) == 0      # measured, not recorded


def test_ring_buffer_bounds_and_drop_count():
    tr = Tracer(enabled=True, max_events=8)
    for i in range(20):
        tr.span(f"s{i}").end()
    assert len(tr) == 8
    assert tr.n_dropped == 12
    assert [e["name"] for e in tr.events()] == [f"s{i}" for i in range(12, 20)]
    tr.clear()
    assert len(tr) == 0 and tr.n_dropped == 0


def test_span_off_and_unprofiled_is_shared_noop_reading_no_clock():
    # repro.flow (imported above) has installed the JAX bridge; no profiler
    # records here, and the ring buffer is off
    reads = []

    def clock():
        reads.append(1)
        return float(len(reads))

    tr = Tracer(enabled=False, clock=clock)
    reads.clear()
    sp = tr.span("a", k=1)
    sp.set(x=2)
    sp.end()
    assert sp is trace_mod._NOOP
    assert reads == []


@pytest.fixture
def fake_profiler(monkeypatch):
    """A bridge to a profiler that always records, logging what each span
    annotates."""
    log = []

    class Mark:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_profiling", lambda: True)
    monkeypatch.setattr(trace_mod, "_annotation", Mark)
    return log


def test_profiled_only_span_annotates_and_stores_nothing(fake_profiler):
    tr = Tracer(enabled=False)
    with tr.span("outer"):
        sp = tr.timed("inner")
        sp.end()
        sp.end()                 # a second end annotates nothing more
    assert sp.elapsed_s >= 0
    assert len(tr) == 0
    assert fake_profiler == [("enter", "outer"), ("enter", "inner"),
                             ("exit", "inner"), ("exit", "outer")]


def test_recorded_span_also_annotates_while_profiled(fake_profiler):
    tr = Tracer(enabled=True)
    tr.span("s", k=1).end()
    (ev,) = tr.events()
    assert ev["name"] == "s" and ev["args"] == {"k": 1}
    assert fake_profiler == [("enter", "s"), ("exit", "s")]


def test_repro_obs_imports_no_jax():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys; import repro.obs; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'numpy')))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.stdout.strip() == "[]"


def test_chrome_export_schema(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="phase", phase="decode"):
        tr.span("inner", cat="sub").end()
    path = str(tmp_path / "t.trace.json")
    doc = tr.to_chrome(path)
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    for ev in doc["traceEvents"]:
        # the fields Perfetto / chrome://tracing require on "X" events
        for field in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert field in ev, f"event missing {field}"
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
    # round-trips through the loader, and the file is valid JSON
    assert load_trace(path) == doc["traceEvents"]
    with open(path) as f:
        json.load(f)


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer(enabled=True)
    for i in range(3):
        tr.span(f"s{i}").end()
    path = str(tmp_path / "t.jsonl")
    tr.to_jsonl(path)
    assert [e["name"] for e in load_trace(path)] == ["s0", "s1", "s2"]


# ---------------------------------------------------------------------------
# metrics registry unit tests
# ---------------------------------------------------------------------------

def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a.count").inc()
    reg.counter("a.count").inc(4)
    reg.gauge("b.val").set(7)
    reg.gauge("b.val").set(3)
    h = reg.histogram("c.dist")
    for v in (0.1, 0.3, 0.2):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a.count"] == 5
    assert snap["b.val"] == 3 and snap["b.val.peak"] == 7
    assert snap["c.dist.count"] == 3
    assert snap["c.dist.mean"] == pytest.approx(0.2)
    assert snap["c.dist.max"] == pytest.approx(0.3)
    # int gauges stay ints (describe() formats them with %d-style fields)
    assert isinstance(snap["b.val"], int)


def test_registry_type_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_counter_is_monotonic():
    c = Counter("c")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_percentile_matches_legacy_formula():
    # the serving report always used nearest-rank:
    #   xs[min(len(xs)-1, ceil(p*len(xs))-1)] over the sorted samples
    for xs in ([0.5], [3.0, 1.0, 2.0], [float(i) for i in range(17)]):
        h = Histogram("h")
        for v in xs:
            h.observe(v)
        s = sorted(xs)
        for p in (0.5, 0.95, 0.99):
            want = s[min(len(s) - 1, int(math.ceil(p * len(s))) - 1)]
            assert h.percentile(p) == want
    assert Histogram("empty").percentile(0.95) == 0.0


def test_gauge_preserves_int_and_float():
    g = Gauge("g")
    g.set(4)
    assert isinstance(g.value, int)
    g.set(4.5)
    assert isinstance(g.value, float)


# ---------------------------------------------------------------------------
# engine integration: tick timeline + exactness gates
# ---------------------------------------------------------------------------

SERVE_SHAPE = ShapeConfig("serve", "decode", 64, 4)


@pytest.fixture(scope="module")
def served():
    cm = rflow.compile("llama3.2-1b", SERVE_SHAPE,
                       FlowConfig(mode="folded", precision="fp32"),
                       smoke=True)
    params = cm.init_params(jax.random.key(0))
    reqs = synthetic_requests(8, cm.cfg.vocab_size, prompt_len=8,
                              max_new_tokens=8)
    return cm, params, reqs


def _run(cm, params, reqs, **ecfg_kw):
    eng = Engine(cm, params, EngineConfig(max_batch=4, max_seq_len=64,
                                          **ecfg_kw))
    return eng, eng.run(reqs)


def test_traced_outputs_byte_identical(served):
    cm, params, reqs = served
    _, r_off = _run(cm, params, reqs)
    eng_on, r_on = _run(cm, params, reqs, trace=True)
    assert [r.tokens for r in r_off.results] == \
           [r.tokens for r in r_on.results]
    assert len(eng_on.tracer) > 0


def test_untraced_engine_records_nothing(served):
    cm, params, reqs = served
    eng, _ = _run(cm, params, reqs)
    assert len(eng.tracer) == 0


def test_tick_timeline_covers_wall_time(tmp_path, served):
    cm, params, reqs = served
    eng, report = _run(cm, params, reqs, trace=True)
    path = str(tmp_path / "run.trace.json")
    eng.tracer.to_chrome(path)
    s = summarize(load_trace(path))
    # phase spans (admit + decode/fori ticks) tile the run loop
    assert s["coverage"] >= 0.95
    phases = {name for name, _, _ in s["phases"]}
    assert "admit" in phases and phases & {"decode", "chunked-prefill",
                                           "spec-verify", "decode-fori"}
    # per-tick attributes: batch bucket, queue depth, pool occupancy,
    # host-sync count
    ticks = [e for e in eng.tracer.events() if e["cat"] == "phase"
             and e["args"].get("phase") != "admit"]
    assert ticks
    for ev in ticks:
        assert {"batch", "queue", "pool_live", "host_syncs"} <= \
            set(ev["args"])
    assert sum(1 for e in eng.tracer.events() if e["cat"] == "run") == 1


def test_trace_phases_chunked_and_spec(served):
    cm, params, reqs = served
    eng, _ = _run(cm, params, reqs, trace=True, prefix_cache=True,
                  chunk_size=4, chunked_prefill=True)
    phases = {e["args"].get("phase") for e in eng.tracer.events()
              if e["cat"] == "phase"}
    assert "chunked-prefill" in phases
    eng, _ = _run(cm, params, reqs, trace=True, speculation="ngram:3")
    phases = {e["args"].get("phase") for e in eng.tracer.events()
              if e["cat"] == "phase"}
    assert "spec-verify" in phases


def test_disabled_tracer_overhead_under_2pct(served):
    # the disabled hot path is one boolean check and one ask of the
    # profiler bridge per span site; bound the
    # replay's total span cost by microbenchmarking that path and scaling
    # by the replay's span-site count, instead of racing two wall-clocks
    cm, params, reqs = served
    eng, report = _run(cm, params, reqs)
    wall = report.metrics["wall_s"]
    tr = eng.tracer
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        tr.span("x")
    per_call = (time.perf_counter() - t0) / n
    # <= ~10 disabled span sites per tick (admit, tick, cow, prepare,
    # model call, sync, commit, evict, + the admission's)
    sites = 10 * (report.metrics["decode_ticks"]
                  + report.metrics["prefill_batches"] + 2)
    assert sites * per_call < 0.02 * wall


def test_injected_clock_is_deterministic(served):
    cm, params, reqs = served

    def fake_clock(state={"t": 0.0}):
        state["t"] += 0.5
        return state["t"]

    eng = Engine(cm, params, EngineConfig(max_batch=4, max_seq_len=64),
                 clock=fake_clock)
    m = eng.run(reqs).metrics
    # every timestamp came from the fake clock: wall and latencies are
    # exact multiples of the 0.5s step, nothing raced perf_counter
    assert m["wall_s"] % 0.5 == pytest.approx(0.0)
    assert m["p50_latency_s"] % 0.5 == pytest.approx(0.0)
    assert m["p50_ttft_s"] % 0.5 == pytest.approx(0.0)
    assert m["wall_s"] > 0


def test_run_report_carries_registry(served):
    cm, params, reqs = served
    _, report = _run(cm, params, reqs, prefix_cache=True)
    assert report.registry is not None
    snap = report.registry.snapshot()
    # dotted-name schema: the documented stable names exist
    for name in ("serving.requests", "serving.tokens.generated",
                 "serving.prefix.hits", "serving.sched.admissions",
                 "pool.blocks.live.peak", "pool.blocks.total",
                 "serving.spec.rollback_tokens"):
        assert name in snap, name
    # the flat report keys are a view over the snapshot
    m = report.metrics
    assert m["n_requests"] == snap["serving.requests"]
    assert m["prefix_hits"] == snap["serving.prefix.hits"]
    assert m["peak_used_blocks"] == snap["pool.blocks.live.peak"]


def test_summarize_cli(tmp_path, served, capsys):
    cm, params, reqs = served
    eng, _ = _run(cm, params, reqs, trace=True)
    path = str(tmp_path / "run.trace.json")
    eng.tracer.to_chrome(path)
    from repro.launch.obs import main
    assert main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "phase" in out and "admit" in out and "coverage" in out


# ---------------------------------------------------------------------------
# profiler bridge: the spans in a JAX profile, reduced as the benchmark does
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """``fn()`` under the JAX profiler inside the benchmark's window
    annotation; its result and the reduced trace."""
    jax.profiler.start_trace(str(tmp_path), profiler_options=devtrace.options())
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, devtrace.load(str(tmp_path))


def _named(tr, name):
    return [(s, e) for s, e, n in tr.host if n == name]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_reach_the_profile(tmp_path, served):
    cm, params, reqs = served
    _, r_off = _run(cm, params, reqs)
    (eng, report), tr = _profiled(tmp_path,
                                  lambda: _run(cm, params, reqs))
    assert [r.tokens for r in report.results] == \
           [r.tokens for r in r_off.results]
    assert len(eng.tracer) == 0          # the ring buffer stayed off
    snap = report.registry.snapshot()
    assert snap["serving.fori.segments"] == 0
    runs = _named(tr, "engine.run")
    ticks = _named(tr, "tick.decode")
    assert len(runs) == 1 and _inside(runs[0], tr.window)
    assert len(ticks) == snap["serving.ticks"] > 0
    assert len(_named(tr, "engine.sync")) == snap["serving.host_syncs"]
    assert len(_named(tr, "model.prefill")) == \
        snap["serving.prefill.batches"]
    assert len(_named(tr, "tick.admit")) >= 1
    # engine.run > tick.decode > one of each step of the tick
    for tick in ticks:
        assert _inside(tick, runs[0])
        for name in ("cow-fork", "engine.prepare", "model.decode",
                     "engine.sync", "engine.commit", "evict"):
            assert sum(_inside(sp, tick) for sp in _named(tr, name)) == 1, \
                name
    assert reader("decode_tick_ms.decode")(SimpleNamespace(trace=tr)) > 0
    # no device plane on the CPU: nothing to measure idle against
    assert reader("tick_idle_ms.decode")(SimpleNamespace(trace=tr)) is None


def test_fori_segments_reach_the_profile(tmp_path, served):
    cm, params, reqs = served
    (_, report), tr = _profiled(tmp_path,
                                lambda: _run(cm, params, reqs, fori_seg=2))
    snap = report.registry.snapshot()
    fori = _named(tr, "tick.fori")
    assert len(fori) == snap["serving.fori.segments"] > 0
    assert len(_named(tr, "engine.sync")) == snap["serving.host_syncs"]
    for seg in fori:
        for name in ("engine.prepare", "model.decode_segment",
                     "engine.sync", "engine.commit"):
            assert sum(_inside(sp, seg) for sp in _named(tr, name)) == 1, \
                name


def test_cnn_prefill_spans_reach_the_profile(tmp_path):
    cm = rflow.compile("lenet5", ShapeConfig("cnn1", "prefill", 1, 1),
                       FlowConfig(mode="folded", precision="fp32"))
    params = cm.init_params(jax.random.key(0))
    cfg = cm.cfg
    images = jax.numpy.ones((1, cfg.image_size, cfg.image_size,
                             cfg.image_channels), jax.numpy.float32)
    prefill = cm.prefill
    np.asarray(prefill(params, {"images": images})[0])     # compile

    def loop(n=5):
        return [np.asarray(prefill(params, {"images": images})[0])
                for _ in range(n)]

    outs, tr = _profiled(tmp_path, loop)
    calls = _named(tr, "model.prefill")
    assert len(calls) == len(outs)
    assert all(_inside(c, tr.window) for c in calls)
    got = reader("dispatch_us.online")(SimpleNamespace(trace=tr))
    assert got == pytest.approx(
        sum(e - s for s, e in calls) / len(calls) * 1e-3)
    assert got > 0


def test_kernel_dispatch_rejections_metric():
    from repro.kernels.registry import DISPATCH_REJECTIONS
    from repro.obs import METRICS
    before = METRICS.counter("kernels.dispatch.rejections").value
    n_before = sum(DISPATCH_REJECTIONS.values())
    cm = rflow.compile("llama3.2-1b", SERVE_SHAPE,
                       FlowConfig(mode="folded", precision="fp32",
                                  kernel_backend="pallas_interpret"),
                       smoke=True)
    params = cm.init_params(jax.random.key(0))
    cm.prefill(params, cm._measure_inputs(0))
    after = METRICS.counter("kernels.dispatch.rejections").value
    n_after = sum(DISPATCH_REJECTIONS.values())
    # the registry counter moves in lockstep with the legacy dict
    assert after - before == n_after - n_before

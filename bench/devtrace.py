"""Device traces: capture one with the JAX profiler around the measured
window, and reduce it to what the per-layer readers need.

* busy time: the union of the intervals in which an operation ran on the
  device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), inside the
  window, averaged over the devices used;
* self time of each operation: its duration less that of the operations
  nested in it (a ``while`` loop holds the operations of its body);
* per-op time: the summed self time of the operations that implement an
  op, found by the patterns of ``ops_map/*.json`` matched against the
  event's name (on the TPU, the HLO instruction with its operand shapes);
* the breakdown: the device operations that took most self time, and the
  idle gaps grouped by the innermost host event that covered each one.

The window is the host annotation ``bench.window`` that the runner puts
around the measured loop.
"""
from __future__ import annotations

import bisect
import glob
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"


@dataclass
class Ops:
    """The operations of one device inside the window, ordered by start."""
    start: np.ndarray        # ns, clipped to the window
    end: np.ndarray          # ns, clipped to the window
    self_ns: np.ndarray      # duration less nested operations
    name: List[str]


@dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[str, Ops]
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: str) -> List[Tuple[float, float]]:
        ops = self.devices[device]
        if not len(ops.start):
            return []
        reach = np.maximum.accumulate(ops.end)
        new = np.ones(len(ops.start), bool)
        new[1:] = ops.start[1:] > reach[:-1]
        starts = ops.start[new]
        ends = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
        return list(zip(starts.tolist(), ends.tolist()))

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(d))
                  for d in self.devices)
        return tot * 1e-9 / len(self.devices)

    def op_seconds(self, patterns: List[str]) -> float:
        """Summed self time of the operations whose name matches any of
        ``patterns``, averaged over devices."""
        if not self.devices or not patterns:
            return 0.0
        rx = re.compile("|".join(f"(?:{p})" for p in patterns))
        tot = 0.0
        for ops in self.devices.values():
            hit = {n: bool(rx.search(n)) for n in set(ops.name)}
            mask = np.fromiter((hit[n] for n in ops.name), bool,
                               len(ops.name))
            tot += float(ops.self_ns[mask].sum())
        return tot * 1e-9 / len(self.devices)

    def top_device_ops(self, n: int = 10) -> List[List]:
        by: Dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for nm, t in zip(ops.name, ops.self_ns.tolist()):
                by[nm] += t * 1e-9 / len(self.devices)
        named: Dict[str, float] = defaultdict(float)
        for nm, t in by.items():
            named[short_name(nm)] += t
        return [[k, v] for k, v in sorted(named.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle time of the first device, grouped by the innermost host
        event that covered each gap's middle; the longest ``n`` groups."""
        if not self.devices:
            return []
        busy = self.busy_intervals(sorted(self.devices)[0])
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        host = sorted(h for h in self.host if h[2] != WINDOW)
        starts = [h[0] for h in host]
        by: Dict[str, float] = defaultdict(float)
        for s, e in gaps:
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid)
            # events that cover the middle began shortly before it
            cover = [h for h in host[max(0, i - 256):i] if mid < h[1]]
            name = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
                else "host (no annotated event)"
            by[name] += (e - s) * 1e-9
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``fusion: bf16[8,128]
    fusion``: the instruction's kind, result shape and opcode, without the
    per-instance number."""
    m = re.match(r"%?([A-Za-z_\-]+?)(?:[._]\d+)* = (\S+?)(?:\{[^}]*\})? "
                 r"([a-z\-]+)\(", hlo)
    if not m:
        return re.sub(r"[._]\d+$", "", hlo)[:120]
    return f"{m.group(1)}: {m.group(2)} {m.group(3)}"[:160]


def ops_map() -> Dict[str, List[str]]:
    """op -> event-name patterns, merged from every ``ops_map/*.json``."""
    from bench.harness import bench_file
    out: Dict[str, List[str]] = defaultdict(list)
    for p in sorted(bench_file("ops_map").glob("*.json")):
        for op, pats in json.loads(p.read_text())["ops"].items():
            out[op].extend(pats)
    return dict(out)


def _device_ops(events, window: Tuple[float, float]) -> Ops:
    lo, hi = window
    rows = sorted((float(e.start_ns), -float(e.start_ns + e.duration_ns),
                   e.name) for e in events
                  if e.start_ns + e.duration_ns > lo and e.start_ns < hi)
    start = np.array([max(r[0], lo) for r in rows], np.float64)
    end = np.array([min(-r[1], hi) for r in rows], np.float64)
    self_ns = end - start
    stack: List[int] = []
    for i in range(len(rows)):           # nested ops: subtract from parent
        while stack and start[i] >= end[stack[-1]]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= end[i] - start[i]
        stack.append(i)
    return Ops(start, end, np.maximum(self_ns, 0.0), [r[2] for r in rows])


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    raw: Dict[str, list] = {}
    host: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw[plane.name] = list(line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                        e.name) for e in line.events]
                win = [e for e in evs if e[2] == WINDOW]
                if win:
                    window = (win[0][0], win[0][1])
                    host = evs
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return Trace(window, {d: _device_ops(evs, window)
                          for d, evs in raw.items()}, host)


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(sorted(paths)[-1]))


def options():
    """Profiler options: device and host events; no Python function
    tracing, which would slow the host loop being measured."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts

"""What every runner shares: finding a cell's files by name, loading the
code that a data file names, building the system under test's
configuration from a configuration file, counting compilations and
collector pauses, and timing set-up phases.

Every file is found under ``ROOT``, the checkout that holds
``BENCHMARK.json``: a cell's configuration by the path its entry gives, its
traffic as ``bench/traffic/<traffic>.json``, its limits as
``bench/limits/<cell>.json``, its runner as ``bench/runners/<kind>.py``
(``kind`` from the traffic file), a per-layer metric's reader as
``bench/metrics/<base>.py``, and a configuration's reference and work
counter by the paths under its ``reference`` and ``opcount`` keys.  So a
new cell, configuration or metric is new files and entries, and no edit.
"""
from __future__ import annotations

import gc
import hashlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, Tuple

ROOT = Path(__file__).resolve().parents[1]
_MODULES: Dict[Path, ModuleType] = {}


def bench_file(*parts: str) -> Path:
    return ROOT.joinpath("bench", *parts)


def load_benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_json(*parts: str) -> Dict:
    return json.loads(bench_file(*parts).read_text())


def load_module(path: str) -> ModuleType:
    """The Python file at ``path`` (relative to ``ROOT``), loaded once."""
    p = (ROOT / path).resolve()
    if p not in _MODULES:
        tag = hashlib.blake2b(str(p).encode(), digest_size=6).hexdigest()
        spec = importlib.util.spec_from_file_location(
            f"bench_file_{p.stem}_{tag}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[p] = mod
    return _MODULES[p]


def resolve_cell(name: str) -> Tuple[Dict, Dict, Dict]:
    """(workload entry, configuration file, traffic file) of cell ``name``."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    return cell, config, load_json("traffic", f"{cell['traffic']}.json")


def runner_class(traffic: Dict):
    return load_module(f"bench/runners/{traffic['kind']}.py").Runner


def reader(name: str):
    """The per-layer reader of metric ``name``: ``bench/metrics/<base>.py``,
    base being the name up to its first dot (``mfu.decode`` -> ``mfu``)."""
    return load_module(f"bench/metrics/{name.split('.')[0]}.py").read


def limits_for(cell: str) -> Dict[str, float]:
    return load_json("limits", f"{cell}.json")["limits"]


def peak_row(kind: str) -> Dict:
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def rates(traffic: Dict, win: Dict) -> Dict[str, float]:
    """The traffic file's ``rates``, ``{metric: count}``: each metric is
    that count of the window over the window's wall-clock seconds."""
    return {name: win[count] / win["elapsed_s"]
            for name, count in traffic["rates"].items()}


def model_config(config: Dict):
    """The system under test's ``ModelConfig`` from the file's ``model``
    group (its ``attention`` sub-group becomes an ``AttentionConfig``)."""
    from repro.configs.base import AttentionConfig, ModelConfig
    kw = dict(config["model"])
    if kw.get("attention") is not None:
        kw["attention"] = AttentionConfig(**kw["attention"])
    return ModelConfig(**kw)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA backend compilations (fresh or loaded from the persistent
    cache both pass through here) while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


class GcPauses:
    """Seconds the Python collector held the process, and its full
    (generation 2) collections, while ``active``."""

    def __init__(self):
        self.seconds = 0.0
        self.full = 0
        self.active = False
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict) -> None:
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class Phases:
    """Wall-clock seconds of the named set-up phases, in order."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    def run(self, name: str, fn):
        t = time.perf_counter()
        out = fn()
        self.seconds[name] = time.perf_counter() - t
        return out

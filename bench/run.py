"""One run of one benchmark cell on the chip:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs`` and its traffic under ``bench/traffic``; builds the
system under test from them with weights and inputs made from ``--seed``;
warms every shape the cell uses (set-up); measures for ``--seconds``; reads
the device's peak memory; frees the program's state; and compares what the
measured window produced with the plain float32 reference.  With
``--trace 1`` the window runs under the JAX profiler and the result holds
the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``); the numbers compared, each with its limit, are the last
lines of standard error and the last key of that object.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 3.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# run as a script, this directory leads sys.path: keep its module names
# from shadowing others
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_DEVICE = 3


def end_to_end_for(bench: Dict, cell: str) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: Dict, cell: str) -> List[Dict]:
    moves = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def setup_jax() -> None:
    """Compile cache at ``<checkout>/.jax_cache``, whatever
    ``JAX_COMPILATION_CACHE_DIR`` said (the program takes the benchmark's
    directory through it), so that two checkouts share nothing; keeping
    every program, however quick to compile, so that a second run in the
    checkout compiles nothing."""
    import jax
    from repro.compile_cache import ENV, enable_compile_cache
    path = str(ROOT / ".jax_cache")
    os.environ[ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(bench: Dict, cell: Dict, config: Dict, traffic: Dict, seed: int,
            seconds: float, trace: bool, limits: Dict[str, float], *,
            backend: str = "auto", t_start: float = _T0) -> Dict:
    """Set up, measure, check: the result object, whose last key holds the
    numbers compared, ``{name: {"value", "limit"}}``."""
    import jax
    import numpy as np
    from bench import devtrace as trace_mod
    from bench.harness import (CompileCounter, GcPauses, Phases, log,
                               peak_row, rates, reader, runner_class)

    devs = jax.devices()
    counter = CompileCounter()
    pauses = GcPauses()
    runner = runner_class(traffic)(cell, config, traffic, seed,
                                   backend=backend)
    phases = Phases()
    phases.seconds["start"] = time.perf_counter() - t_start
    counter.active = True
    runner.setup(phases)
    setup_compiles, counter.count = counter.count, 0
    setup_s = time.perf_counter() - t_start
    log("setup: " + " ".join(f"{k} {v:.3f}s" for k, v in
                             phases.seconds.items())
        + f"; {setup_compiles} programs compiled or loaded; "
          f"setup_s {setup_s:.3f}")

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir, profiler_options=trace_mod.options())
    pauses.active = True
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            win = runner.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    counter.active = pauses.active = False
    pauses.close()
    log(f"window: {json.dumps(win)}; {counter.count} programs compiled or "
        f"loaded inside it; the collector paused it {pauses.seconds:.4f}s "
        f"({pauses.full} full collections)")
    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(peaks) if None not in peaks else None}
    work = runner.work(win)
    runner.release()

    t = time.perf_counter()
    got = runner.check()
    log(f"check: {json.dumps(got)} in {time.perf_counter() - t:.3f}s")
    compared = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    correct = bool(compared) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())

    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}, "device": device}
    if not trace:
        values = dict(rates(traffic, win), setup_s=setup_s)
        for m in end_to_end_for(bench, cell["name"]):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        tr = trace_mod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = SimpleNamespace(trace=tr, work=work, config=config,
                              traffic=traffic, cell=cell,
                              ops=trace_mod.ops_map(),
                              peak=peak_row(device["kind"]),
                              n_devices=cell["chips"])
        for m in per_layer_for(bench, cell["name"]):
            v = reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_device_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["window_compiles"] = counter.count
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from bench.harness import limits_for, load_benchmark, log, resolve_cell
    bench = load_benchmark()
    cell, config, traffic = resolve_cell(args.workload)
    limits = limits_for(args.workload)
    # libtpu's own logs would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found platform {devs[0].platform!r}; this "
            "benchmark measures the chip and runs nowhere else")
        return NO_DEVICE
    if len(devs) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} chips; JAX sees "
            f"{len(devs)}")
        return NO_DEVICE
    setup_jax()
    result = execute(bench, cell, config, traffic, args.seed, args.seconds,
                     bool(args.trace), limits)
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that a cell's limits are set from, on the chip, at the cell's own
size, many seeds in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 2

For each seed: set up the cell, run a short window at its own load (for a
serving cell at least one whole wave, so the longest requests finish), free
the program's state, then read the numbers the output check compares (the
lower reading: the program) and the same numbers with the reference at the
next precisions down in the program's place (the upper reading: the int8
and fp8 controls).  One JSON line per seed; the benchmark's own runs never
run the controls.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for _p in (str(HERE.parent / "src"), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from bench import run
    from bench.harness import Phases, log, resolve_cell, runner_class
    if jax.devices()[0].platform != "tpu":
        log("calibration reads the chip; no TPU found")
        return run.NO_DEVICE
    run.setup_jax()
    cell, config, traffic = resolve_cell(args.workload)
    r = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if r is None:
            r = runner_class(traffic)(cell, config, traffic, seed)
            r.setup(Phases())
        else:
            r.reseed(seed)
        win = r.window(args.seconds)
        r.release()
        rec = {"workload": args.workload, "seed": seed, "window": win,
               "program": r.check()}
        for p in args.controls.split(","):
            rec[p] = r.check_control(p)
        rec["seconds"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

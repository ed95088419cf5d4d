"""Shared by the output-check tests: a whole run at a size a CPU test can
hold (``data/*_tiny.json``): a sound run is correct; the control (the
reference at the next precision down, fp8, in the program's place) and the
faults planted in the timed path are not.

The tiny limits below sit between the tiny readings on the CPU, as the
cells' own limits do at full size (bench/limits/, PERF.md): sound runs read
at most 0.0122 (CNN, seeds 1-3) and 0.0167 (LM, seeds 1-5); the fp8 control
reads at least 0.060 (CNN) and 0.10 to 0.44 (LM, seeds 1, 2, 4, 5; seed 3
puts no served token near a tie at this size and reads 0).  The tests use
seed 1."""
import contextlib
import json
from pathlib import Path


from bench import run
from bench.harness import load_benchmark

DATA = Path(__file__).parent / "data"
CNN_LIMIT = {"worst_rel_err": 0.03}
LM_LIMIT = {"served_logit_gap": 0.04}


def load(name):
    return json.loads((DATA / name).read_text())


def execute_tiny(kind, seed=1, limits=None):
    bench = load_benchmark()
    if kind == "cnn":
        cell = dict(name="resnet34.batch64", chips=1)
        cfg, tr = load("resnet34_tiny.json"), load("batch_tiny.json")
        limits = limits or CNN_LIMIT
    else:
        cell = dict(name="phi4mini.chat_decode", chips=1)
        cfg, tr = load("phi4mini_tiny.json"), load("chat_tiny.json")
        limits = limits or LM_LIMIT
    return run.execute(bench, cell, cfg, tr, seed, 0.2, False, limits,
                       backend="reference")


@contextlib.contextmanager
def patched(obj, name, make):
    old = getattr(obj, name)
    setattr(obj, name, make(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def prefill_fault(fault):
    """CompiledModel.prefill whose logits are broken as ``fault`` says."""
    from repro.flow import CompiledModel

    def make(prop):
        def broken(self):
            fn = prop.fget(self)

            def call(params, batch):
                logits, state, aux = fn(params, batch)
                return fault(logits), state, aux
            return call
        return property(broken)
    return patched(CompiledModel, "prefill", make)



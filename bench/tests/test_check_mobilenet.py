"""The output check of a MobileNetV1 cell, on a tiny configuration
(``data/mobilenetv1_tiny.json``: the published widths at 32 px, 16
classes) with the Pallas kernels in interpret mode: a sound run is
correct; faults planted in the depthwise kernel's calls, and the fp8 and
int8 controls, are not.

The tiny limit sits between the tiny readings on the CPU: sound runs
read 0.0022-0.0031 (seeds 1-3); the int8 control 0.011-0.022, fp8
0.029-0.064; a dropped tap 0.026-0.042, a wrong stride-2 phase
0.027-0.059, a skipped depthwise batch norm 0.34-0.41."""
import dataclasses

import jax.numpy as jnp
import pytest

from bench import run
from bench.harness import Phases, load_benchmark
from bench.runners import cnn
from bench.tests.checkutil import load

LIMIT = {"worst_rel_err": 0.008}
CELL = {"name": "mobilenetv1.batch64", "chips": 1}


def _execute():
    return run.execute(load_benchmark(), CELL, load("mobilenetv1_tiny.json"),
                       load("batch_tiny.json"), 1, 0.2, False, LIMIT,
                       backend="pallas_interpret")


def test_mobilenet_sound_run_is_correct():
    r = _execute()
    assert r["correct"] is True
    assert r["window_compiles"] == 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}


def _drop_tap(fn):
    return lambda x, w, **kw: fn(x, w.at[0, 0].set(0), **kw)


def _stride2_phase(fn):
    def call(x, w, stride=1, **kw):
        if stride == 2:     # read phase (1, 1) where (0, 0) belongs
            x = jnp.pad(x[:, 1:, 1:], ((0, 0), (0, 1), (0, 1), (0, 0)))
        return fn(x, w, stride=stride, **kw)
    return call


def _skip_bn(fn):
    return lambda x, w, bn=None, **kw: fn(x, w, **kw)


@pytest.mark.parametrize("fault", [_drop_tap, _stride2_phase, _skip_bn],
                         ids=["dropped_tap", "stride2_phase", "skipped_bn"])
def test_mobilenet_fault_is_caught(fault, monkeypatch):
    from repro.kernels.registry import REGISTRY
    key = ("depthwise_conv2d", "pallas")
    impl = REGISTRY._impls[key]
    monkeypatch.setitem(REGISTRY._impls, key,
                        dataclasses.replace(impl, fn=fault(impl.fn)))
    assert _execute()["correct"] is False


@pytest.fixture(scope="module")
def measured():
    """A runner after its window, its program's state freed."""
    R = cnn.Runner({"name": "t", "chips": 1}, load("mobilenetv1_tiny.json"),
                   load("batch_tiny.json"), 1, backend="pallas_interpret")
    R.setup(Phases())
    R.window(0.1)
    R.release()
    return R


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_mobilenet_control_fails(measured, precision):
    assert measured.check()["worst_rel_err"] <= LIMIT["worst_rel_err"]
    assert measured.check_control(precision)["worst_rel_err"] > \
        LIMIT["worst_rel_err"]

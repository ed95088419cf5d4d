"""The output check of the CNN cells: a sound run is correct; the fp8
control and the faults planted in the timed path are not (see checkutil)."""
import pytest

from bench.tests.checkutil import CNN_LIMIT, execute_tiny, load, prefill_fault


def test_cnn_sound_run_is_correct():
    r = execute_tiny("cnn")
    assert r["correct"] is True
    assert r["window_compiles"] == 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
def test_cnn_fault_is_caught(fault):
    def alter(lg):
        return lg.at[0].add(lg[0].max() - lg[0].min())

    def half(lg):
        return lg.at[lg.shape[0] // 2:].set(0)

    with prefill_fault({"answer_altered": alter,
                         "half_batch_left_out": half}[fault]):
        r = execute_tiny("cnn")
    assert r["correct"] is False


def test_cnn_control_fails():
    from bench.runners import cnn
    from bench.harness import Phases
    R = cnn.Runner({"name": "t", "chips": 1}, load("resnet34_tiny.json"),
                   load("batch_tiny.json"), 1, backend="reference")
    R.setup(Phases())
    R.window(0.1)
    R.release()
    assert R.check()["worst_rel_err"] <= CNN_LIMIT["worst_rel_err"]
    assert R.check_control("fp8")["worst_rel_err"] > \
        CNN_LIMIT["worst_rel_err"]

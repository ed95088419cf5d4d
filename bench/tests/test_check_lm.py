"""The output check of the serving cells: a sound run is correct; the fp8
control and the faults planted in the timed path are not (see checkutil)."""
import pytest

from bench.tests.checkutil import LM_LIMIT, execute_tiny, load, patched


def test_lm_sound_run_is_correct():
    r = execute_tiny("lm")
    assert r["correct"] is True
    assert r["window_compiles"] == 0
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "half_batch_left_out"])
def test_lm_fault_is_caught(fault):
    from repro.flow import CompiledModel
    from repro.serving.engine import Engine

    if fault == "token_altered":
        def make(old):
            def sample(self, logits, key, temperature):
                tok = old(self, logits, key, temperature)
                return tok.at[0].set((tok[0] + 1) % logits.shape[-1])
            return sample
        ctx = patched(Engine, "_sample", make)
    else:
        def make(prop):
            def broken(self):
                fn = prop.fget(self)

                def call(params, batch, state, idx):
                    logits, st, aux = fn(params, batch, state, idx)
                    h = logits.shape[0] // 2
                    if h:
                        logits = logits.at[h:].set(logits[:logits.shape[0] - h])
                    return logits, st, aux
                return call
            return property(broken)
        ctx = patched(CompiledModel, "decode", make)
    with ctx:
        r = execute_tiny("lm")
    assert r["correct"] is False


def test_lm_control_fails():
    from bench.runners import lm_serve
    from bench.harness import Phases
    R = lm_serve.Runner({"name": "t", "chips": 1}, load("phi4mini_tiny.json"),
                        load("chat_tiny.json"), 1, backend="reference")
    R.setup(Phases())
    R.window(0.1)
    R.release()
    assert R.check()["served_logit_gap"] <= LM_LIMIT["served_logit_gap"]
    assert R.check_control("fp8")["served_logit_gap"] > \
        LM_LIMIT["served_logit_gap"]

"""The trace reduction on a small recorded trace: one TPU plane whose
``while`` loop holds two operations, one more operation after it, and a
host line with the window and one host event inside a device gap."""
import re
from pathlib import Path

import pytest

from bench import devtrace

DATA = Path(__file__).parent / "data" / "small_trace.pbtxt"


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    return devtrace.from_profile(
        ProfileData.from_text_proto(DATA.read_text()))


def test_window_and_busy_union(tr):
    assert tr.window_s == pytest.approx(8e-6)
    # the while loop [1, 5] us covers its body; [6, 7] us is apart
    assert tr.busy_intervals("/device:TPU:0") == [(1000.0, 5000.0),
                                                  (6000.0, 7000.0)]
    assert tr.busy_s() == pytest.approx(5e-6)
    assert 1 - tr.busy_s() / tr.window_s == pytest.approx(3 / 8)


def test_self_time_per_op(tr):
    assert tr.op_seconds([r"custom-call\(bf16\[\d+(,\d+){4}\]"]) == \
        pytest.approx(3e-6)
    assert tr.op_seconds([r"fusion\("]) == pytest.approx(1e-6)
    # the loop's own time is what its body leaves: 4 - 2 - 1 us
    assert tr.op_seconds([r"^%while"]) == pytest.approx(1e-6)
    assert tr.op_seconds([r"^nothing$"]) == 0.0


def test_breakdown(tr):
    top = dict(tr.top_device_ops())
    assert top["conv: bf16[2,8] custom-call"] == pytest.approx(3e-6)
    gaps = dict(tr.idle_gaps())
    assert gaps["sample"] == pytest.approx(1e-6)
    assert gaps["host (no annotated event)"] == pytest.approx(2e-6)


def test_ops_map_finds_the_kernels_by_their_operands():
    ops = devtrace.ops_map()
    conv = ("%closed_call.52 = bf16[64,56,56,64]{3,2,1,0} custom-call("
            "bf16[64,1,58,58,64]{4,3,2,1,0:T(8,128)(2,1)S(1)} %pad.19, "
            "bf16[3,3,64,64]{3,2,1,0} %w), custom_call_target="
            "\"tpu_custom_call\"")
    mm = ("%closed_call.59 = bf16[16,8192]{1,0} custom-call(bf16[16,3072]"
          "{1,0:T(8,128)(2,1)S(1)} %f, bf16[3072,8192]{1,0} %w1, "
          "bf16[3072,8192]{1,0} %w2), custom_call_target=\"tpu_custom_call\"")
    paged = ("%closed_call.57 = bf16[16,8,3,128]{3,2,1,0} custom-call("
             "s32[16,24]{1,0:T(8,128)S(1)} %bt, s32[16]{0:T(128)S(1)} %ln, "
             "bf16[16,8,3,128]{3,2,1,0} %q), custom_call_target="
             "\"tpu_custom_call\"")
    for op, name in (("conv2d", conv), ("matmul", mm),
                     ("paged_decode_attention", paged)):
        hits = {o for o, pats in ops.items()
                if any(re.search(p, name) for p in pats)}
        assert hits == {op}, (op, hits)

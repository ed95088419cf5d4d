"""MobileNetV1's reference, work counter and kernel pattern: the program
(``flow.compile`` with the Pallas kernels in interpret mode) agrees with
``bench/reference/mobilenet.py`` on seeded weights; the configuration file
and the program's graph have the same 13 blocks; the reference keeps its
activations at a usable scale; FLOPs match the paper; the depthwise
kernel's trace pattern and the other kernels' patterns never meet."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.devtrace import ops_map
from bench.opcount import dwconv, mobilenet as count
from bench.reference import mobilenet as reference
from bench.runners.cnn import worst_rel_err

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "mobilenetv1.json").read_text())


def test_program_matches_reference_on_seeded_weights():
    """The SMOKE configuration (64 px, 16 classes) in float32 through the
    normal path, every depthwise conv on the kernel: float32 throughout,
    so the logits differ from the reference only by the order of sums."""
    from repro import flow
    from repro.configs.base import FlowConfig, ShapeConfig
    from repro.configs.mobilenetv1 import SMOKE
    cm = flow.compile(SMOKE, ShapeConfig("s", "prefill", 1, 2),
                      FlowConfig(mode="folded", precision="fp32"),
                      backend="pallas_interpret")
    assert cm.plan.kernels["depthwise_conv2d"] == "pallas_interpret"
    seed = 2 ** 31 + 5
    images = jax.random.normal(jax.random.key(1), (2, 64, 64, 3))
    got = cm.prefill(weights.program_params(cm, seed), {"images": images})[0]
    cfg = dict(CONFIG, image_size=SMOKE.image_size,
               num_classes=SMOKE.vocab_size, dtype="float32")
    assert worst_rel_err(got, reference.forward(cfg, seed, images)) < 1e-5


def test_config_blocks_are_the_programs():
    from repro.models.cnn import _MOBILENET_PLAN
    assert [tuple(b) for b in CONFIG["blocks"]] == _MOBILENET_PLAN
    assert CONFIG["blocks"][-1] == [1024, 1]      # the stated departure


def test_activation_scale_under_seeded_weights():
    """Under bench/weights.py's rule (fan-in 9 for the (3, 3, 1, C)
    depthwise filter) the reference's activations after the stem and after
    each of the 13 blocks keep an RMS between 0.05 and 20 at 224 px: no
    layer vanishes or blows up.  A (3, 3, C, 1) layout would draw the
    filter with fan-in 9C, 22.6x too small at C = 512."""
    images = jax.random.normal(jax.random.key(0), (2, 224, 224, 3),
                               jnp.bfloat16)
    acts = reference.blocks(CONFIG, 2 ** 31 + 7, images)
    assert len(acts) == 14
    rms = [float(jnp.sqrt(jnp.mean(jnp.square(a)))) for a in acts]
    assert all(0.05 < r < 20 for r in rms), rms
    assert acts[-1].shape == (2, 7, 7, 1024)


def test_counts():
    assert count.convs(CONFIG)[0] == (224, 224, 3, 32, 3, 2)
    assert len(count.convs(CONFIG)) == 14          # stem + 13 pointwise
    dws = count.dwconvs(CONFIG)
    assert dws[0] == (112, 112, 32, 1) and dws[-1] == (7, 7, 1024, 1)
    assert [d[3] for d in dws].count(2) == 4
    # 569 million multiply-adds an image (Table 1, rounded to the million)
    assert count.flops_per_image(CONFIG) / 2 == pytest.approx(569e6,
                                                               abs=0.5e6)
    f, b = dwconv.dwconv(2, 7, 7, 8, 2)
    assert f == 2 * 2 * 4 * 4 * 9 * 8
    assert b == 2 * (2 * 7 * 7 * 8 + 9 * 8 + 2 * 4 * 4 * 8)


# event names as the TPU trace gives them: the HLO instruction with its
# operands' shapes and layouts
EVENTS = {
    "depthwise_conv2d": "%_lambda_.1 = bf16[64,56,56,128]{3,2,1,0} "
    "custom-call(bf16[64,56,56,128]{3,2,1,0:T(8,128)(2,1)} %x, "
    "f32[9,128]{1,0} %reshape.3, f32[4,128]{1,0} %fusion.2), "
    'custom_call_target="tpu_custom_call"',
    "conv2d": "%closed_call.4 = bf16[64,56,64,128]{3,2,1,0} "
    "custom-call(bf16[64,1,58,64,128]{4,3,2,1,0} %p, "
    "bf16[3,3,128,128]{3,2,1,0} %w, f32[1,128]{1,0} %s), "
    'custom_call_target="tpu_custom_call"',
    "matmul": "%closed_call.9 = bf16[64,1000]{1,0} "
    "custom-call(bf16[64,1024]{1,0} %x, bf16[1024,1000]{1,0} %w), "
    'custom_call_target="tpu_custom_call"',
}


@pytest.mark.parametrize("op", sorted(EVENTS))
def test_kernel_patterns_are_disjoint(op):
    pats = ops_map()
    for name, patterns in pats.items():
        hit = any(re.search(p, EVENTS[op]) for p in patterns)
        assert hit == (name == op), (name, op)
